"""Arithmetic the metric readers share (``metrics/<name>.py``).

Each reader takes the run's context and returns a number, or ``None``
where the run has nothing to read (no trace, no such work, no such
kernel in the trace): the metric is then left out of the result line.
The work behind a share comes from the kind module of the run's
configuration (``kinds.of(ctx["config"])``).
"""
from __future__ import annotations

import math

import kinds
import work


def p95_ms(ctx):
    """Nearest-rank 95th percentile of the latencies of every inference
    request due in the window; a refused, failed or unanswered request is
    an infinite latency."""
    lat = sorted(ctx.get("infer_latencies_s") or [])
    if not lat:
        return None
    return lat[math.ceil(0.95 * len(lat)) - 1] * 1e3


def rate(ctx, kind):
    """Rows of ``kind`` over the time they took: inference, the rows of the
    requests sent in the window, until the last of them was answered;
    training, the rows of the whole epochs run in the window."""
    v = ctx.get(f"{kind}_rows")
    return v / ctx[f"{kind}_span_s"] if v else None


def requests_per_launch(ctx):
    launches = ctx.get("counters", {}).get("launches")
    return ctx["infer_requests_all"] / launches if launches else None


def cold_share(ctx):
    c = ctx.get("counters", {})
    return 100.0 * c["cold_requests"] / c["requests"] if c.get(
        "requests") else None


def mfu(ctx, kind, ops_per_row):
    """Rows/s of ``kind`` x ``ops_per_row(cfg)`` over the int8 peak, %."""
    r = rate(ctx, kind)
    if r is None or ctx.get("peaks") is None:
        return None
    return 100.0 * ops_per_row(ctx["config"]) * r / ctx["peaks"][
        "int8_ops_per_s"]


def idle(ctx):
    t = ctx.get("trace")
    if not t or not t["devices"] or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline(ctx, stage, w):
    t = ctx.get("trace")
    secs = t["stage_s"].get(stage, 0.0) if t else 0.0
    if not secs or ctx.get("peaks") is None or w is None:
        return None
    return 100.0 * work.roofline_s(w, ctx["peaks"]) / secs


def clause_eval_roofline(ctx):
    if not ctx.get("infer_rows_all"):
        return None
    return roofline(ctx, "clause_eval", kinds.of(ctx["config"]).clause_eval(
        ctx["config"], ctx["infer_rows_all"], ctx["infer_requests_all"]))


def ta_update_roofline(ctx):
    share = ctx.get("active_share")
    if share is None or not ctx.get("train_rows"):
        return None
    return roofline(ctx, "ta_update", kinds.of(ctx["config"]).ta_update(
        ctx["config"], ctx["train_rows"], ctx["train_steps"], share))
