"""Reduction of a profiler trace to device time, kernel time per stage and
the host's activity in the device's idle gaps.

A trace is first flattened to plain events, ``(plane, line, name, start_ns,
end_ns)``, by :func:`events_from_xplane`; everything after that works on
the flat list, so the reduction can be checked on a small recorded trace
(``tests/data``) without a chip.

* Device planes are those whose name starts with ``/device:TPU:``; their
  operations are the events of the ``XLA Ops`` line, named by the HLO
  instruction (``%ta_update.1 = ...`` becomes ``ta_update``; a Pallas
  kernel's instruction is named after the function that calls
  ``pallas_call``).  Busy time is the union of the operations' intervals
  inside the window, averaged over the device planes that ran anything.
* Control flow (``while``, ``conditional``) appears as an operation that
  encloses others; only leaf operations are summed per name.
* A stage's kernel time is the summed duration of the leaf operations
  whose name matches one of the stage's patterns (``stages/*.json``).
* An idle gap is a stretch of the window in which no operation runs on
  any device plane; it is attributed to the innermost host event
  covering its midpoint on the busiest host thread that has one
  (``host idle`` where none does).
"""
from __future__ import annotations

import collections
import fnmatch
import glob
import json
import pathlib
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
STAGES = pathlib.Path(__file__).with_name("stages")
_SUFFIX = re.compile(r"\.\d+$")


def op_name(text: str) -> str:
    """``%packed_clause_eval.1 = s32[...] custom-call(...)`` ->
    ``packed_clause_eval``."""
    return _SUFFIX.sub("", text.split(" = ", 1)[0].strip().lstrip("%"))


def events_from_xplane(path: str) -> list:
    """Flat events of an ``.xplane.pb`` file (device op lines and every
    host line)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                out.append((plane.name, line.name,
                            op_name(ev.name) if device else ev.name,
                            float(ev.start_ns), float(ev.end_ns)))
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def stage_patterns() -> dict:
    """{stage: [name patterns]} from every ``stages/*.json`` file."""
    out: dict = collections.defaultdict(list)
    for f in sorted(STAGES.glob("*.json")):
        d = json.loads(f.read_text())
        out[d["stage"]].extend(d["kernels"])
    return dict(out)


def _union(intervals: list) -> list:
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(events: list, span: str) -> tuple:
    """(start_ns, end_ns) of the host span named ``span``."""
    hits = [(s, e) for p, _, n, s, e in events
            if n == span and not p.startswith(DEVICE_PREFIX)]
    if not hits:
        raise ValueError(f"no host span {span!r} in the trace")
    return min(s for s, _ in hits), max(e for _, e in hits)


def reduce(events: list, lo: float, hi: float, stages: dict,
           top: int = 10, skip: tuple = ()) -> dict:
    """Busy and idle seconds, per-stage kernel seconds and the breakdown
    of the window [lo, hi] (ns).  Host spans named in ``skip`` (the span
    that marks the window itself) explain no gap."""
    dev = collections.defaultdict(list)
    names = collections.defaultdict(float)
    for p, _, n, s, e in events:
        if not p.startswith(DEVICE_PREFIX) or e <= lo or s >= hi:
            continue
        dev[p].append((max(s, lo), min(e, hi), n))
    for p, ops in dev.items():
        for s, e, n in _leaves(ops):
            names[n] += (e - s) / 1e9
        dev[p] = [(s, e) for s, e, _ in ops]
    window_s = (hi - lo) / 1e9
    planes = [p for p, iv in dev.items() if iv]
    busy = ([sum(e - s for s, e in _union(dev[p])) / 1e9 for p in planes])
    stage_s = {st: sum(v for n, v in names.items()
                       if any(fnmatch.fnmatchcase(n, pat) for pat in pats))
               for st, pats in stages.items()}
    all_busy = _union([iv for p in planes for iv in dev[p]])
    gaps, t = [], lo
    for s, e in all_busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "devices": len(planes),
        "stage_s": stage_s,
        "device_ops": sorted(names.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": _attribute([ev for ev in events if ev[2] not in skip],
                                gaps, lo, hi)[:top],
    }


def _leaves(ops: list) -> list:
    """The operations of one plane that enclose no other."""
    ops = sorted(ops, key=lambda t: (t[0], -t[1]))
    out = []
    for k, (s, e, n) in enumerate(ops):
        if k + 1 < len(ops) and ops[k + 1][0] < e and ops[k + 1][1] <= e:
            continue
        out.append((s, e, n))
    return out


def _innermost(spans: list, mids: list) -> list:
    """For each midpoint (ascending), the name of the innermost span that
    covers it, or None.  Spans of one thread nest, so a stack suffices."""
    spans = sorted(spans, key=lambda t: (t[0], -t[1]))
    out, stack, i = [], [], 0
    for m in mids:
        while i < len(spans) and spans[i][0] <= m:
            s, e, n = spans[i]
            while stack and stack[-1][1] <= s:
                stack.pop()
            stack.append((s, e, n))
            i += 1
        while stack and stack[-1][1] <= m:
            stack.pop()
        out.append(stack[-1][2] if stack else None)
    return out


def _attribute(events: list, gaps: list, lo: float, hi: float,
               threads: int = 4) -> list:
    """Idle seconds summed by the host event that covers each gap's
    midpoint, innermost first, on the busiest host threads of the window
    in order of busyness."""
    lines = collections.defaultdict(list)
    for p, ln, n, s, e in events:
        if p.startswith(DEVICE_PREFIX) or e <= lo or s >= hi or e <= s:
            continue
        lines[(p, ln)].append((s, e, n))
    order = sorted(lines, key=lambda k: -sum(
        e - s for s, e in _union(_clip([(a, b) for a, b, _ in lines[k]],
                                       lo, hi))))[:threads]
    mids = [(gs + ge) / 2 for gs, ge in gaps]
    covers = [_innermost(lines[k], mids) for k in order]
    by = collections.defaultdict(float)
    for j, (gs, ge) in enumerate(gaps):
        what = next((c[j] for c in covers if c[j] is not None), "host idle")
        by[what] += (ge - gs) / 1e9
    return sorted(by.items(), key=lambda kv: -kv[1])
