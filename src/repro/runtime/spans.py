"""Named host spans of the serving and training paths.

A span is a ``jax.profiler.TraceAnnotation``: inside a
``jax.profiler.trace`` window it is recorded in the same trace as the
device's operations, on the host line of the thread that ran it, with its
ids (``cycle=``, ``epoch=``) as event stats; outside one it does nothing.
The profiler keeps and writes the spans, so there is no switch, buffer or
exporter here.  Spans nest on a thread, which gives each its parent.

Spans wrap host code only, never a jitted or traced function.  Every name
starts with ``tm.``; README "Tracing" lists what each covers.
"""
from __future__ import annotations

import jax

# scheduler (launch/scheduler.py)
SCHED_CYCLE = "tm.sched.cycle"            # one driver cycle (cycle=n)
SCHED_FORM = "tm.sched.form"              # heads, EDF sort, dequeue
SCHED_RESOLVE = "tm.sched.resolve"        # collect + future resolution
SCHED_MEMBERSHIP = "tm.sched.membership"  # EWMA, promotions, swaps
SCHED_WAIT = "tm.sched.wait"              # idle wait / batch-window sleep
SCHED_SUBMIT = "tm.sched.submit"          # admission (client thread)
# server (launch/serve_tm.py)
SERVER_ENCODE = "tm.server.encode"        # pad (+ encode) of one request
SERVER_ENCODE_BATCH = "tm.server.encode_batch"  # stack, put, raw launch
SERVER_LAUNCH = "tm.server.launch"        # bank sync, stacked + cold launch
SERVER_COLLECT = "tm.server.collect"      # fetch + decode of one flush
SERVER_FETCH = "tm.server.fetch"          # the host sync of a collect
SERVER_TRAIN = "tm.server.train"          # one online training step
# fit session (core/dtm.py)
FIT_BIND = "tm.fit.bind"                  # encode + stage the dataset
FIT_ENCODE = "tm.fit.encode"              # one staged chunk (chunk=n)
FIT_PLAN = "tm.fit.plan"                  # permutation + plan device_put
FIT_EPOCH = "tm.fit.epoch"                # the epoch's scan dispatch
FIT_FETCH = "tm.fit.fetch"                # step stats device_get + record


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """Context manager recording ``name`` (with ``ids`` as event stats)
    while a profiler trace is active."""
    return jax.profiler.TraceAnnotation(name, **ids)
