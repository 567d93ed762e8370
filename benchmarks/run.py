"""Benchmark harness entry — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--smoke]``
(``--smoke`` = FAST=1 sizes — what nightly CI runs; fused_step_bench
additionally drops to a single timing iteration.  ``FAST=1`` env still
works for ad-hoc quick sweeps.)

Prints ``name,us_per_call,derived`` CSV and writes ``BENCH_fused.json``
(machine-readable fused-vs-unfused training-step numbers — uploaded as a
CI artifact to track the perf trajectory PR-over-PR).
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="shrunk sizes + single timing iteration")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names (e.g. "
                         "'fused_step_bench,session_bench') — the "
                         "PR-blocking perf smoke runs just the guarded "
                         "baselines instead of the full nightly sweep")
    args = ap.parse_args()
    if args.smoke:
        # must land before benchmark modules import benchmarks.common
        os.environ["FAST"] = "1"
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from . import (autotune_bench, fig3_opcounts, fig7_clause_skip,
                   fig11_kernels, fig14_weight_bits, fig15_lfsr,
                   fused_step_bench, packed_bench, pod_bench,
                   recovery_bench, serve_bench, session_bench, skip_bench,
                   table1_accuracy, table2_kws6, table2_supp, convtm_bench)
    mods = (table1_accuracy, table2_kws6, table2_supp, fig3_opcounts,
            fig7_clause_skip, fig11_kernels, fig14_weight_bits,
            fig15_lfsr, convtm_bench, fused_step_bench,
            packed_bench, autotune_bench, session_bench, skip_bench,
            pod_bench, serve_bench, recovery_bench)
    if args.only:
        # short selectors for the PR-blocking perf-smoke job
        aliases = {"autotune": "autotune_bench", "lfsr": "fig15_lfsr",
                   "recovery": "recovery_bench"}
        wanted = {aliases.get(w, w) for w in args.only.split(",")}
        names = {m.__name__.rsplit(".", 1)[-1] for m in mods}
        unknown = wanted - names
        assert not unknown, f"unknown benchmark module(s): {unknown}"
        mods = tuple(m for m in mods
                     if m.__name__.rsplit(".", 1)[-1] in wanted)
    print("name,us_per_call,derived")
    for mod in mods:
        try:
            mod.run()
        except Exception:
            print(f"{mod.__name__},-1,ERROR")
            traceback.print_exc()
            sys.exit(1)


if __name__ == "__main__":
    main()
