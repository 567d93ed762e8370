"""Pod-scale serving benchmark -> BENCH_pod.json.

Three measurements (ROADMAP Open item 1 acceptance):

* **tenant scaling** — stacked-flush throughput of a TMServer hosting
  K tenants PER DEVICE at D in {1, 2, 4}: D devices serve D·K tenants
  in the same number of launches as one device serves K (the
  tenant-parallel :class:`repro.launch.pod.PodBank`).  The headline
  ``scaling_ratio_4x`` is wall(4K tenants, D=4) / wall(K tenants, D=1)
  — the acceptance bar is <= 2x ON A HOST THAT CAN RUN THE DEVICES IN
  PARALLEL.  Forced CPU host devices are threads of one process: when
  the container grants fewer cores than devices they SERIALIZE, so the
  strict ratio degenerates to >= 4x by construction — the report carries
  ``host_cpu_cores`` / ``serialized_host`` so a reader (and the
  regression guard baseline) can tell which regime produced the number.
* **equal-work sharding tax** — wall(4K tenants, D=4) / wall(the SAME
  4K-tenant roster stacked on one device).  Total compute is identical
  on both sides, so this isolates what the mesh costs (input scatter,
  per-device dispatch) and is meaningful on ANY host, serialized or
  not.
* **clause sharding** — step time of one over-budget machine
  clause-sharded over 4 devices vs the same machine single-device
  (bit-identical results; on fake host devices the collective overhead
  usually LOSES wall-clock — the number documents that cost; on a real
  mesh it is what makes the over-VMEM machine runnable at all).

Everything runs in ONE process, on meshes built from the first D of
``jax.devices()`` — a process that holds a chip keeps it, so no child
process could measure on it.  D values beyond the devices present are
not measured and the report says so; on a one-chip host only D=1 runs
and the D=4 ratios are absent.  Rehearse the 4-device path on a CPU by
starting the process with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``:

    python -m benchmarks.pod_bench
"""
from __future__ import annotations

import json
import os
import time

from .common import FAST, row

DEVICE_COUNTS = (1, 2, 4)
OUT = "BENCH_pod.json"


def _measure(devices: int) -> dict:
    """Measure on a mesh of this process's first ``devices`` devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.core.prng import PRNG
    from repro.launch import pod
    from repro.launch.mesh import make_clause_mesh, make_tenant_mesh
    from repro.launch.serve_tm import TMServer, demo_batch

    assert jax.device_count() >= devices, (jax.device_count(), devices)

    k_per_dev = 2 if FAST else 4
    batch_slot = 16 if FAST else 32
    rounds = 3 if FAST else 8
    features = 64 if FAST else 256
    clauses = 32 if FAST else 64

    spec = api.TMSpec.coalesced(features=features, classes=4,
                                clauses=clauses, T=16, s=4.0)
    engine = api.compile(api.tile_for(spec))

    def _flush_wall(n_tenants: int) -> float:
        """Median per-round wall of serving ``n_tenants`` (one stacked
        flush per round) on this process's device mesh."""
        mesh = make_tenant_mesh(devices) if devices > 1 else None
        srv = TMServer(engine, batch_slot=batch_slot, mesh=mesh)
        for i in range(n_tenants):
            srv.register(f"t{i}", spec, seed=i)
        lits = {f"t{i}": engine.encode(
            spec, jnp.asarray(demo_batch(spec, batch_slot, seed=i)))
            for i in range(n_tenants)}

        def flush_all():
            for name, ls in lits.items():
                srv.enqueue(name, ls, encoded=True)
            out = srv.flush()
            for v in out.values():
                np.asarray(v)

        flush_all()                               # compile + warm
        ts = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            flush_all()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    n_tenants = k_per_dev * devices
    wall = _flush_wall(n_tenants)
    result = {
        "devices": devices,
        "tenants": n_tenants,
        "batch_slot": batch_slot,
        "rounds": rounds,
        "host_cpu_cores": len(os.sched_getaffinity(0)),
        "flush_wall_s": wall,
        "tenants_per_s": n_tenants / wall,
        "requests_per_s": n_tenants * batch_slot / wall,
    }
    if devices == 1:
        # the SAME 4x roster crammed on one device — denominator of the
        # equal-work sharding-tax ratio (identical total compute)
        result["flush_wall_4k_s"] = _flush_wall(4 * k_per_dev)

    if devices >= 4:
        # clause-sharded step of one machine whose padded R spreads
        # 4-ways, vs the identical single-device step
        big = api.TMSpec.coalesced(
            features=features, classes=4,
            clauses=256 if FAST else 512, T=32, s=4.0)
        big_engine = api.compile(api.tile_for(big))
        plan = api.plan_for(make_clause_mesh(devices), big,
                            vmem_budget=api.plan_for(
                                make_clause_mesh(devices),
                                big).program_bytes // devices)
        stm = pod.ShardedTM(big_engine, make_clause_mesh(devices))
        prog = big_engine.lower(big, jax.random.PRNGKey(0))
        prng = PRNG.create(big.tm_config(), 1)
        blits = big_engine.encode(big, jnp.asarray(
            demo_batch(big, batch_slot, seed=0)))
        lab = jnp.zeros((batch_slot,), jnp.int32)

        def _time(fn, p0):
            p, r, _ = fn(p0, prng, blits, lab)     # compile + warm
            jax.block_until_ready(p.ta)
            ts = []
            for _ in range(rounds):
                t0 = time.perf_counter()
                p, r, _ = fn(p, r, blits, lab)
                jax.block_until_ready(p.ta)
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts) * 1e6)

        single_us = _time(big_engine.train_step, prog)
        sharded_us = _time(stm.train_step, stm.shard(prog))
        result["clause_sharded"] = {
            "R": big_engine.R,
            "shards": stm.shards,
            "plan": plan.reason,
            "step_us_single": single_us,
            "step_us_sharded": sharded_us,
            "sharded_vs_single": sharded_us / max(single_us, 1e-9),
        }
    return result


def run() -> dict:
    """Measure every D in :data:`DEVICE_COUNTS` this process has devices
    for, write BENCH_pod.json, print the CSV rows."""
    import jax

    present = len(jax.devices())
    counts = [d for d in DEVICE_COUNTS if d <= present]
    by_devices = {str(d): _measure(d) for d in counts}
    dev = jax.devices()[0]
    cores = len(os.sched_getaffinity(0))
    report = {
        "by_devices": by_devices,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "devices_present": present,
        "host_cpu_cores": cores,
    }
    for d in counts:
        e = by_devices[str(d)]
        row(f"pod_flush_d{d}_k{e['tenants']}", e["flush_wall_s"] * 1e6,
            f"{e['tenants_per_s']:.1f} tenants/s")
    if "4" not in by_devices:
        report["not_measured"] = (
            f"D={[d for d in DEVICE_COUNTS if d > present]}: this process "
            f"has {present} device(s)")
        row("pod_scaling_4x", float("nan"),
            f"not measured: {report['not_measured']}")
    else:
        d1, d4 = by_devices["1"], by_devices["4"]
        report.update({
            # acceptance: 4 devices serve 4K tenants in <= 2x the wall of
            # K tenants on one device (where the devices run in parallel;
            # serialized forced CPU devices degenerate to >= 4x)
            "scaling_ratio_4x": d4["flush_wall_s"]
            / max(d1["flush_wall_s"], 1e-12),
            # equal total compute on both sides: the pure mesh tax (input
            # scatter + per-device dispatch), meaningful on any host
            "equal_work_ratio_4x": d4["flush_wall_s"]
            / max(d1["flush_wall_4k_s"], 1e-12),
            "serialized_host": (dev.platform == "cpu"
                                and cores < d4["devices"]),
            "clause_sharded": d4.get("clause_sharded"),
        })
        regime = (f"SERIALIZED host: {cores} core(s) for 4 devices"
                  if report["serialized_host"] else "parallel devices")
        row("pod_scaling_4x", report["scaling_ratio_4x"] * 100,
            f"{report['scaling_ratio_4x']:.2f}x wall for 4x tenants "
            f"({regime})")
        row("pod_equal_work_4x", report["equal_work_ratio_4x"] * 100,
            f"{report['equal_work_ratio_4x']:.2f}x mesh tax at equal work")
        cs = report["clause_sharded"]
        row(f"pod_clause_sharded_R{cs['R']}", cs["step_us_sharded"],
            f"{cs['sharded_vs_single']:.2f}x vs single-device")
    with open(OUT, "w") as f:
        json.dump(report, f, indent=2)
    return report


def main():
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()


if __name__ == "__main__":
    main()
