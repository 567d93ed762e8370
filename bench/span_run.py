"""One traced run of a cell that also reads the program's spans, from the
root of a checkout:

    python3 bench/span_run.py --workload <name> --seed <n> --seconds <s>

It is ``run.py --workload <name> --seed <n> --seconds <s> --trace 1``
(same set-up, window, trace, reference check and result line), followed
by one more JSON line: the readings of ``span_reduce.READERS``, the
end-to-end rate of the traced window (``rate``, rows/s), ``idle_spans``
(top 10) and every ``tm.*`` span's count, total and self seconds.

``harness.run`` reduces the trace and deletes it before the metric
readers run, and reads a fixed set of counters; so for this process
only, ``trace_reduce.reduce``, ``harness.summarize`` and
``harness.ServeRun.counters`` are wrapped to keep the events of the
window, the run's context and the queue-wait counters.  On a program
without spans or those counters the readings are ``null``.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402
import readers  # noqa: E402
import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402

COUNTERS = ("infer_formed", "infer_queue_wait_s")


def main(argv=None, **run_kw) -> int:
    """``run_kw`` goes to ``harness.run`` (``root``, ``require_chip``)."""
    args = harness.parse(argv)
    args.trace = 1
    seen: dict = {}
    reduce, summarize = trace_reduce.reduce, harness.summarize
    counters = harness.ServeRun.counters

    def keep_reduce(events, lo, hi, *a, **k):
        seen["spans"] = span_reduce.spans(events, lo, hi)
        seen["idle_spans"] = span_reduce.idle_spans(events, lo, hi)
        return reduce(events, lo, hi, *a, **k)

    def keep_summarize(mode, res, run):
        seen["ctx"] = summarize(mode, res, run)
        return seen["ctx"]

    def with_queue_wait(run):
        st = run.sched.stats()
        return counters(run) | {k: st[k] for k in COUNTERS if k in st}

    trace_reduce.reduce = keep_reduce
    harness.summarize = keep_summarize
    harness.ServeRun.counters = with_queue_wait
    try:
        rc = harness.run(args, T_START, **run_kw)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    finally:
        trace_reduce.reduce, harness.summarize = reduce, summarize
        harness.ServeRun.counters = counters
    ctx = dict(seen["ctx"], spans=seen["spans"])
    kind = "train" if ctx["mode"] == "fit" else "infer"
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "metrics": {n: f(ctx) for n, f in span_reduce.READERS.items()},
        "rate": readers.rate(ctx, kind),
        "idle_spans": seen["idle_spans"][:10],
        "spans": seen["spans"]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
