"""The lower-precision control of a cell, on the chip, at the cell's size:

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 5

For each seed one run of the cell at its own load (a window of
``--seconds``), then its numbers compared twice: with the reference as
the configuration states it (the sound reading, limit 0 each) and with
the reference one precision step below (the traffic file's
``check.control``) put in the program's place (the control's reading,
which has to exceed a limit).  One JSON line per seed; the benchmark's own
runs never run this.
"""
import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    d = harness.load_cell(args.workload)
    try:
        harness.check_chip(d["cell"]["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.control_readings(d["config"], d["traffic"], seed,
                                     args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": d["traffic"]["check"]["control"],
                          **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
