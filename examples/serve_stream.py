"""Async continuous-batching serving: one scheduler, four tenants at
different rates and SLA classes.

The four tenants stream requests from the caller thread while the
background driver ("tm-scheduler") owns the device: it coalesces the
per-tenant queue heads into program-major stacked launches
earliest-deadline-first, keeps launches pipelined (no host sync on the
hot path), and — with ``resident_slots=3`` — only three tenants ride
the resident bank at a time, the EWMA arrival-rate loop promoting the
hot one and demoting the cold one through routed program swaps.

PYTHONPATH=src python examples/serve_stream.py
"""
import json
import time

import numpy as np

from repro import api
from repro.api import TMSpec
from repro.launch.scheduler import BATCH, GOLD, STANDARD, SchedulerConfig
from repro.launch.serve_tm import demo_batch
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

B = 8
TENANTS = {
    # name: (spec, SLA class, offered share of the request stream)
    "kws-gold": (TMSpec.vanilla(features=24, classes=6, clauses=32,
                                T=16, s=4.0), GOLD, 0.45),
    "mnist-std": (TMSpec.coalesced(features=32, classes=10, clauses=48,
                                   T=24, s=6.0), STANDARD, 0.35),
    "votes-std": (TMSpec.regression(features=12, clauses=32, T=32,
                                    s=3.0), STANDARD, 0.15),
    "logs-batch": (TMSpec.vanilla(features=16, classes=2, clauses=16,
                                  T=8, s=3.0), BATCH, 0.05),
}

roster = {n: spec for n, (spec, _, _) in TENANTS.items()}
sched = api.serve(roster, batch_slot=B,
                  config=SchedulerConfig(max_wait_s=0.001,
                                         pipeline_depth=2,
                                         resident_slots=3,
                                         membership_every=4,
                                         min_dwell_ticks=1,
                                         promote_min_qps=1.0),
                  slas={n: sla for n, (_, sla, _) in TENANTS.items()})
print(f"engine backend={sched.server.engine.backend}  "
      f"resident={sched.server.resident_names()} "
      f"(capacity 3 of {len(roster)})")

# warm the stacked path untimed, then stream ~0.5 s of skewed traffic
# from this thread while the background driver serves it
for name in roster:
    sched.submit(name, demo_batch(roster[name], B, seed=0))
sched.drain()

rng = np.random.default_rng(0)
names = list(TENANTS)
shares = np.array([s for _, _, s in TENANTS.values()])
sched.start()
futs, t0 = [], time.perf_counter()
while time.perf_counter() - t0 < 0.5:
    name = names[rng.choice(len(names), p=shares)]
    futs.append((name, sched.submit(
        name, demo_batch(roster[name], B, seed=len(futs)))))
    time.sleep(0.002)
for name, fut in futs:
    preds = fut.result(timeout=60)
    assert preds.shape[0] == B, name
sched.stop()

stats = sched.stats()
print(f"\nserved {stats['completed']}/{stats['submitted']} requests in "
      f"{stats['launches']} stacked launches  "
      f"(promotions={stats['promotions']} demotions={stats['demotions']})")
print(f"resident now: {sched.server.resident_names()}  "
      f"cold-path requests: {stats['server']['cold_requests']}")
print(f"mean queue wait: {1e3 * stats['infer_queue_wait_s'] / stats['infer_formed']:.2f} ms "
      f"over {stats['infer_formed']} requests taken into batches")
print("\nper-tenant:")
for name, st in stats["tenants"].items():
    print(f"  {name:12s} sla={st['sla']:8s} completed={st['completed']:3d} "
          f"ewma={st['ewma_qps']:7.1f}/s resident={st['resident']}")
print("\nfull stats:")
print(json.dumps(stats, indent=2, default=str))

# submitted/completed include the len(roster) warm-up requests
assert stats["completed"] == stats["submitted"] == len(futs) + len(roster)
assert stats["launches"] < stats["completed"], "no coalescing happened?"
