"""Durable streaming continual learning: train-while-serve, crash, and
bit-identical recovery.

Two tenants learn online through ``submit_train`` while serving
inference off the same program-major launches.  An async checkpoint
writer makes every applied step durable off the hot path; an injected
transient launch fault is absorbed by the retry budget while gold-SLA
traffic keeps flowing.  Then the process state is thrown away and
``api.serve(None, durable_dir=...)`` cold-starts the whole roster —
specs, SLAs, per-tenant programs, PRNGs, and step counters — from disk,
continuing exactly where the "crashed" server stopped.

PYTHONPATH=src python examples/train_while_serve.py
"""
import os
import shutil
import tempfile

import numpy as np

from repro import api
from repro.api import TMSpec
from repro.launch.scheduler import GOLD, STANDARD, SchedulerConfig
from repro.launch.serve_tm import demo_batch
from repro.runtime.fault import FaultInjector, FaultPlan
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

B = 8
STEPS = 6
roster = {
    "kws-gold": TMSpec.vanilla(features=24, classes=6, clauses=32,
                               T=16, s=4.0),
    "votes-std": TMSpec.regression(features=12, clauses=32, T=32, s=3.0),
}
slas = {"kws-gold": GOLD, "votes-std": STANDARD}


def batches(name, step):
    rng = np.random.default_rng(100 * step + (name == "kws-gold"))
    x = demo_batch(roster[name], B, seed=step)
    if roster[name].kind == "regression":
        return x, rng.random(B).astype(np.float32)
    return x, rng.integers(0, roster[name].classes, B).astype(np.int32)


durable_dir = tempfile.mkdtemp(prefix="dtm_durable_")
try:
    # --- train-while-serve with an injected launch fault ----------------
    inj = FaultInjector(FaultPlan(fail={"launch": (3,)}))   # one transient
    sched = api.serve(roster, batch_slot=B, durable_dir=durable_dir,
                      slas=slas, injector=inj,
                      config=SchedulerConfig(ckpt_interval_s=0.05))
    print(f"engine backend={sched.server.engine.backend}  "
          f"durable_dir={durable_dir}")
    for step in range(STEPS):
        for name in roster:
            x, y = batches(name, step)
            sched.submit_train(name, x, y)
            sched.submit(name, demo_batch(roster[name], B, seed=step + 50))
    sched.drain()
    sched.checkpoint_now()              # durability barrier

    stats = sched.stats()
    assert stats["completed"] == stats["submitted"], "gold requests dropped?"
    print(f"served {stats['completed']} requests "
          f"({stats['trains']} training steps applied), "
          f"retries={stats['retries']} faults={stats['faults']} "
          f"checkpoint_saves={stats['checkpoint']['saves']}")

    probe = {n: demo_batch(roster[n], B, seed=7) for n in roster}
    want = {n: np.asarray(sched.server.predict(n, probe[n])) for n in roster}
    steps_before = {n: sched.server.tenants[n].steps for n in roster}
    del sched                           # the "crash"

    # --- cold-start from disk alone -------------------------------------
    restored = api.serve(None, durable_dir=durable_dir)
    print(f"\nrestored roster: {sorted(restored.server.tenants)}  "
          f"(kws-gold sla={restored.sla_of('kws-gold').name})")
    for n in roster:
        assert restored.server.tenants[n].steps == steps_before[n]
        got = np.asarray(restored.server.predict(n, probe[n]))
        np.testing.assert_array_equal(got, want[n])
        print(f"  {n:10s} step={steps_before[n]} predictions bit-identical")

    # and it keeps LEARNING from where it stopped
    for name in roster:
        x, y = batches(name, STEPS)
        restored.submit_train(name, x, y)
    restored.drain()
    assert all(restored.server.tenants[n].steps == steps_before[n] + 1
               for n in roster)
    print(f"\ncontinued training to step "
          f"{ {n: restored.server.tenants[n].steps for n in roster} }")
    print("durable layout:",
          sorted(os.listdir(os.path.join(durable_dir, "tenants"))))
finally:
    shutil.rmtree(durable_dir, ignore_errors=True)
