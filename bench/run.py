"""Run one cell of the benchmark, from the root of a checkout:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic and metrics are defined by
``BENCHMARK.json`` and the files under ``bench/`` (see ``harness.py``).
A configuration of a new TM kind joins by new files and entries alone:
``kinds/<kind>.py`` and its plain reference ``kinds/<kind>_ref.py`` (the
contract is in ``kinds/coalesced.py``), ``configs/<config>.json`` naming
the kind, a traffic file, the ``stages/`` and ``metrics/`` files of any
new kernel or metric, and its ``BENCHMARK.json`` entries.
The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``) and, last, ``compared``: every number compared with the
plain reference beside its limit.  Exits 2, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    args = harness.parse(argv)
    try:
        return harness.run(args, T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
