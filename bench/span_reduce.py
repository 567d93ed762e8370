"""The program's own spans in a flattened profiler trace, and the four
per-layer numbers that read them or the scheduler's queue-wait counters.

The program marks its host work with ``tm.*`` spans
(``src/repro/runtime/spans.py``), which the profiler records on the host
line of the thread that ran them, on the same clock as the device's
operations.  On the flat events of :func:`trace_reduce.events_from_xplane`:

* :func:`spans`: per span name, the count, total seconds and self seconds
  of the spans that start in the window.  A span's self time is its
  duration less the time its ``tm.`` children on the same line cover.
* :func:`idle_spans`: device-idle seconds by the innermost ``tm.`` span
  covering each idle gap's midpoint (``outside spans`` where none does);
  host lines are searched in order of their ``tm.`` span time, so the
  driver thread comes first.

The readers (:func:`queue_wait_ms`, :func:`encode_ms`, :func:`launch_ms`,
:func:`epoch_prep_ms`) take a run's context as ``metrics/<name>.py``
readers do, with ``ctx["spans"]`` from :func:`spans` and the queue-wait
counters in ``ctx["counters"]``, and return ``None`` where the run has
no such span or counter (a program without them, or no trace).
"""
from __future__ import annotations

import collections

import trace_reduce
from trace_reduce import DEVICE_PREFIX

PREFIX = "tm."
OUTSIDE = "outside spans"


def _host_spans(events: list, lo: float, hi: float, prefix: str) -> dict:
    """{(plane, line): [(start, end, name)]} of the host spans that
    overlap the window."""
    lines = collections.defaultdict(list)
    for p, ln, n, s, e in events:
        if (not p.startswith(DEVICE_PREFIX) and n.startswith(prefix)
                and e > lo and s < hi):
            lines[(p, ln)].append((s, e, n))
    return lines


def spans(events: list, lo: float, hi: float, prefix: str = PREFIX) -> dict:
    """{name: {"count", "total_s", "self_s"}} of the spans named
    ``prefix...`` that start in [lo, hi) (ns)."""
    out: dict = {}
    for line in _host_spans(events, lo, hi, prefix).values():
        line.sort(key=lambda t: (t[0], -t[1]))
        child = [0.0] * len(line)
        stack: list = []
        for k, (s, e, _) in enumerate(line):
            while stack and line[stack[-1]][1] <= s:
                stack.pop()
            if stack:               # spans of one thread nest
                child[stack[-1]] += e - s
            stack.append(k)
        for k, (s, e, n) in enumerate(line):
            if not lo <= s < hi:
                continue
            d = out.setdefault(n, {"count": 0, "total_s": 0.0,
                                   "self_s": 0.0})
            d["count"] += 1
            d["total_s"] += (e - s) / 1e9
            d["self_s"] += (e - s - child[k]) / 1e9
    return out


def device_gaps(events: list, lo: float, hi: float) -> list:
    """Stretches of [lo, hi] in which no operation runs on any device
    plane, as ``trace_reduce.reduce`` finds them."""
    busy = trace_reduce._union([(max(s, lo), min(e, hi))
                                for p, _, _, s, e in events
                                if p.startswith(DEVICE_PREFIX)
                                and e > lo and s < hi])
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def idle_spans(events: list, lo: float, hi: float,
               prefix: str = PREFIX) -> list:
    """[(span name, device-idle seconds)], most first: each idle gap goes
    to the innermost ``prefix...`` span covering its midpoint, on the
    first host line (by span time in the window) that has one."""
    lines = _host_spans(events, lo, hi, prefix)
    order = sorted(lines, key=lambda k: -sum(
        e - s for s, e in trace_reduce._union(
            trace_reduce._clip([(a, b) for a, b, _ in lines[k]], lo, hi))))
    gaps = device_gaps(events, lo, hi)
    mids = [(gs + ge) / 2 for gs, ge in gaps]
    covers = [trace_reduce._innermost(lines[k], mids) for k in order]
    by: dict = collections.defaultdict(float)
    for j, (gs, ge) in enumerate(gaps):
        what = next((c[j] for c in covers if c[j] is not None), OUTSIDE)
        by[what] += (ge - gs) / 1e9
    return sorted(by.items(), key=lambda kv: -kv[1])


# ------------------------------------------------------------------ readers

def _span(ctx, name):
    d = (ctx.get("spans") or {}).get(name)
    return d if d and d["count"] else None


def queue_wait_ms(ctx):
    """Scheduler: mean wait of an inference request from submit until a
    batch takes it, over the window, ms."""
    c = ctx.get("counters") or {}
    if not c.get("infer_formed") or "infer_queue_wait_s" not in c:
        return None
    return 1e3 * c["infer_queue_wait_s"] / c["infer_formed"]


def encode_ms(ctx):
    """Server: mean duration of one request's pad and encode, ms."""
    d = _span(ctx, "tm.server.encode")
    return 1e3 * d["total_s"] / d["count"] if d else None


def launch_ms(ctx):
    """Server: mean self time of one flush's launch phase, ms."""
    d = _span(ctx, "tm.server.launch")
    return 1e3 * d["self_s"] / d["count"] if d else None


def epoch_prep_ms(ctx):
    """Fit session: dataset binding and epoch planning per epoch, ms."""
    epochs = _span(ctx, "tm.fit.epoch")
    if not epochs:
        return None
    prep = sum(d["total_s"] for d in (_span(ctx, "tm.fit.bind"),
                                      _span(ctx, "tm.fit.plan")) if d)
    return 1e3 * prep / epochs["count"]


READERS = {"queue_wait_ms.infer_rows": queue_wait_ms,
           "encode_ms.infer_rows": encode_ms,
           "launch_ms.infer_rows": launch_ms,
           "epoch_prep_ms.train_rows": epoch_prep_ms}
