"""Smoke run of the main path on TPU: the paper's MNIST roster, served.

Serves the paper's two MNIST models from ``configs/tm_paper.py`` at their
published widths — ``TM_MNIST_COTM`` (784 features, 2000 clauses, 10
classes, T=500, s=10, the 24-bit LFSR) and ``TM_MNIST_VANILLA`` (784
features, 200 clauses per class) — through ``api.serve`` on the Pallas
kernels, and checks every result bit for bit against the same roster and
request stream on a pure-jnp ``backend="ref"`` engine on the same chip.
Data is ``data.datasets.MNIST_LIKE``, generated from ``--seed``.

    python chip_smoke.py            # one chip: serving, online training, fit
    python chip_smoke.py --mesh4    # four chips: tenant-parallel serving and
                                    # clause-sharded training, each against
                                    # its one-device run

One chip runs three phases: B=32 and B=1 inference plus B=32 online
training on a 32-row batch slot (``mxu_popcount`` eval, the ``fused``
train step, the compact TA update); B=1 inference and training on a
1-row slot (``packed_vpu``); and one ``TM.fit`` epoch (the ``lax.scan``
session path).

Lines before the last are diagnostics; the seconds they print are smoke
timings of this one run, not metrics.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits non-zero, printing no result, when JAX finds no TPU or
any check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
CYCLES = 3           # scheduler cycles (steps) per phase
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileMeter:
    """Backend-compile seconds, programs and persistent-cache hits since
    the last :meth:`take` (fed by ``jax.monitoring`` listeners)."""

    def __init__(self):
        self.secs, self.programs, self.hits = 0.0, 0, 0

    def on_duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.secs += secs
            self.programs += 1

    def on_event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1

    def take(self) -> str:
        out = (f"compile {self.secs:.1f} s over {self.programs} programs, "
               f"{self.hits} persistent-cache hits")
        self.secs, self.programs, self.hits = 0.0, 0, 0
        return out


def same(a, b, what: str) -> None:
    """Bit-identity of two pytrees of arrays / ints."""
    import jax
    import numpy as np
    la, lb = jax.tree.leaves(jax.device_get(a)), jax.tree.leaves(
        jax.device_get(b))
    check(len(la) == len(lb), f"{what}: structure differs")
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        check(x.shape == y.shape and x.dtype == y.dtype
              and np.array_equal(x, y), f"{what}: kernel != ref")


def roster():
    from repro import api
    from repro.configs.tm_paper import TM_MNIST_COTM, TM_MNIST_VANILLA
    return {"cotm": api.TMSpec.from_config(TM_MNIST_COTM),
            "vanilla": api.TMSpec.from_config(TM_MNIST_VANILLA)}


def custom_calls(fn, *args) -> int:
    """Pallas TPU kernels in the lowered program of ``fn(*args)`` — an
    interpreted kernel body lowers to plain HLO and counts zero."""
    import jax
    return jax.jit(fn).lower(*args).as_text().count("tpu_custom_call")


def drive(sched, specs, x, y, slot: int, cycles: int, log) -> list:
    """Submit, per cycle and tenant, one training step of ``slot`` rows,
    one inference request of ``slot`` rows and (slot > 1) one of a single
    row; run the scheduler inline until idle.  Returns every future's
    result in submission order."""
    futs, walls = [], []
    n = x.shape[0]
    for c in range(cycles):
        t0 = time.perf_counter()
        for name in specs:
            i = (c * slot) % (n - slot)
            futs.append(sched.submit_train(name, x[i:i + slot],
                                           y[i:i + slot]))
            futs.append(sched.submit(name, x[i:i + slot]))
            if slot > 1:
                futs.append(sched.submit(name, x[i:i + 1]))
        sched.drain()
        walls.append(time.perf_counter() - t0)
    log(f"  smoke timing (not a metric): cycle walls "
        f"{[round(w, 4) for w in walls]} s (first includes compiles)")
    return [f.result() for f in futs]


def check_scheduler(sched, what: str) -> None:
    st = sched.stats()
    for key in ("failed", "faults", "retries"):
        check(st[key] == 0, f"{what}: stats()[{key!r}] = {st[key]}")
    check(st["completed"] == st["submitted"],
          f"{what}: {st['completed']} of {st['submitted']} completed")


def check_programs(a: dict, b: dict, what: str) -> None:
    """TA states, include bitplanes and weights of two rosters."""
    for name in a:
        pa, pb = a[name], b[name]
        same(pa.ta, pb.ta, f"{what}/{name} TA states")
        same(pa.inc, pb.inc, f"{what}/{name} include bitplane")
        same(pa.weights, pb.weights, f"{what}/{name} weights")


def phase_serve(slot: int, x, y, cycles: int, seed: int, log) -> dict:
    """One roster, one request stream, kernel engine vs ref engine."""
    import jax.numpy as jnp
    from repro import api
    specs = roster()
    runs = {}
    for backend in ("kernel", "ref"):
        sched = api.serve(specs, batch_slot=slot, backend=backend, seed=seed)
        results = drive(sched, specs, x, y, slot, cycles, log)
        check_scheduler(sched, f"slot {slot} {backend}")
        runs[backend] = (sched, results)
    (ks, kres), (rs, rres) = runs["kernel"], runs["ref"]
    check(ks.server.engine.backend == "kernel", "engine is not on kernels")
    same(kres, rres, f"slot {slot}: predictions and train stats")
    progs = {b: {n: t.program for n, t in s.server.tenants.items()}
             for b, (s, _) in runs.items()}
    check_programs(progs["kernel"], progs["ref"], f"slot {slot}")
    # class sums and clause outputs of the trained programs on fresh rows
    for name, spec in specs.items():
        outs = {}
        for b, (s, _) in runs.items():
            eng = s.server.engine
            lits = eng.encode(spec, jnp.asarray(x[-slot:]))
            outs[b] = eng.infer(progs[b][name], lits)
        same(outs["kernel"], outs["ref"], f"slot {slot}/{name} class sums")
    eng = ks.server.engine
    spec = specs["cotm"]
    lits = eng.encode(spec, jnp.asarray(x[:slot]))
    tenant = ks.server.tenants["cotm"]
    calls = {"infer": custom_calls(eng.infer, tenant.program, lits),
             "train": custom_calls(eng.train_step, tenant.program,
                                   tenant.prng, lits,
                                   spec.encode_labels(y[:slot]))}
    for stage, k in calls.items():
        check(k > 0, f"slot {slot}: {stage} stage lowers no Pallas kernel")
    log(f"  tpu_custom_calls per lowered stage: {calls}")
    paths = eng.cache_report()["path_per_stage"]
    log(f"  path_per_stage (kernel engine): {paths}")
    return paths


def phase_fit(x, y, seed: int, log) -> dict:
    """One ``TM.fit`` epoch (device-resident scan), kernel vs ref."""
    from repro import api
    spec = roster()["cotm"]
    out = {}
    for backend in ("kernel", "ref"):
        tm = api.TM(spec, backend=backend, seed=seed)
        t0 = time.perf_counter()
        hist = tm.fit(x, y, epochs=1, batch=32)
        log(f"  smoke timing (not a metric): {backend} fit epoch of "
            f"{x.shape[0] // 32} steps {time.perf_counter() - t0:.3f} s "
            "(includes compiles)")
        out[backend] = (tm, hist)
    (kt, kh), (rt, rh) = out["kernel"], out["ref"]
    check(kt.engine.backend == "kernel", "fit engine is not on kernels")
    same(kh, rh, "fit: epoch stats")
    check_programs({"cotm": kt.program}, {"cotm": rt.program}, "fit")
    paths = kt.engine.cache_report()["path_per_stage"]
    log(f"  path_per_stage (kernel engine): {paths}")
    return paths


def sharded_leaves(tree, n: int, what: str) -> None:
    import jax
    for leaf in jax.tree.leaves(tree):
        if not leaf.sharding.is_fully_replicated:
            check(len(leaf.sharding.device_set) == n,
                  f"{what}: a sharded leaf spans "
                  f"{len(leaf.sharding.device_set)} devices, not {n}")


def phase_tenants4(x, y, cycles: int, seed: int, log) -> None:
    """The roster over a 4-chip ``tenants`` mesh vs one device."""
    import jax
    from repro import api
    from repro.launch.mesh import make_tenant_mesh
    specs = roster()
    runs = {}
    for mode, mesh in (("mesh4", make_tenant_mesh(4)), ("one", None)):
        sched = api.serve(specs, batch_slot=32, backend="kernel", seed=seed,
                          mesh=mesh)
        runs[mode] = (sched, drive(sched, specs, x, y, 32, cycles, log))
        check_scheduler(sched, f"tenants {mode}")
    (ps, pres), (os_, ores) = runs["mesh4"], runs["one"]
    same(pres, ores, "tenants mesh4: predictions and train stats")
    bank = ps.server.bank()
    n_sharded = sum(not leaf.sharding.is_fully_replicated
                    for leaf in jax.tree.leaves(bank.progs))
    check(n_sharded > 0, "tenants mesh4: no bank leaf is sharded")
    sharded_leaves(bank.progs, 4, "tenants mesh4 bank")
    check_programs({n: t.program for n, t in ps.server.tenants.items()},
                   {n: t.program for n, t in os_.server.tenants.items()},
                   "tenants mesh4")
    log(f"  bank leaves sharded over 4 devices: {n_sharded}")


def phase_clauses4(x, y, steps: int, seed: int, log) -> None:
    """TM_MNIST_COTM clause-sharded 4 ways (R=2048) vs one device."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.core.prng import PRNG
    from repro.launch import pod
    from repro.launch.mesh import make_clause_mesh
    spec = roster()["cotm"]
    engine = api.compile(api.tile_for(spec), backend="kernel")
    stm = pod.ShardedTM(engine, make_clause_mesh(4))
    log(f"  R={engine.R} clause rows over {stm.shards} shards")
    prog = engine.lower(spec, jax.random.PRNGKey(seed))
    prng = PRNG.create(spec.tm_config(), seed + 1)
    one, sharded = (prog, prng), (stm.shard(prog), prng)
    sharded_leaves(sharded[0], 4, "clauses mesh4 program")
    for s in range(steps):
        lits = engine.encode(spec, jnp.asarray(x[32 * s:32 * (s + 1)]))
        lab = spec.encode_labels(y[32 * s:32 * (s + 1)])
        t0 = time.perf_counter()
        p1, r1, st1 = engine.train_step(*one, lits, lab)
        jax.block_until_ready(p1.ta)
        t1 = time.perf_counter()
        p4, r4, st4 = stm.train_step(*sharded, lits, lab)
        jax.block_until_ready(p4.ta)
        t2 = time.perf_counter()
        log(f"  smoke timing (not a metric): step {s} one-device "
            f"{t1 - t0:.4f} s, clause-sharded {t2 - t1:.4f} s")
        same(st1, st4, f"clauses mesh4 step {s} stats")
        one, sharded = (p1, r1), (p4, r4)
    sharded_leaves(sharded[0], 4, "clauses mesh4 trained program")
    check_programs({"cotm": one[0]}, {"cotm": pod.gather_program(sharded[0])},
                   "clauses mesh4")
    lits = engine.encode(spec, jnp.asarray(x[:32]))
    same(engine.infer(one[0], lits), stm.infer(sharded[0], lits),
         "clauses mesh4 class sums and clauses")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh4", action="store_true",
                    help="four chips: tenant-parallel serving and "
                         "clause-sharded training, each vs one device")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the data and of every model")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.data.datasets import MNIST_LIKE, make_bool_dataset
    from repro.kernels.ops import resolve_interpret
    from repro.launch.compile_cache import use_compile_cache

    def log(msg):
        print(msg, flush=True)

    cache_dir = use_compile_cache()
    meter = CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter.on_duration)
    jax.monitoring.register_event_listener(meter.on_event)
    check(not resolve_interpret(), "Pallas would run in interpret mode")
    count = 4 if args.mesh4 else 1
    check(len(jax.devices()) >= count,
          f"{count} devices needed, {len(jax.devices())} found")
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache_dir}")
    x, y = make_bool_dataset(MNIST_LIKE, 256, seed=args.seed)

    phases = ([("tenants-mesh4", lambda: phase_tenants4(
                   x, y, CYCLES, args.seed, log)),
               ("clauses-mesh4", lambda: phase_clauses4(
                   x, y, CYCLES, args.seed, log))]
              if args.mesh4 else
              [("serve-slot32", lambda: phase_serve(
                   32, x, y, CYCLES, args.seed, log)),
               ("serve-slot1", lambda: phase_serve(
                   1, x, y, CYCLES, args.seed, log)),
               ("fit-epoch", lambda: phase_fit(x[:128], y[:128], args.seed,
                                               log))])
    seen = set()
    for name, run in phases:
        log(f"phase {name}:")
        t0 = time.perf_counter()
        paths = run()
        log(f"  {meter.take()}; smoke timing (not a metric): phase wall "
            f"{time.perf_counter() - t0:.1f} s")
        if paths:
            seen.update(paths.values())
    if not args.mesh4:
        for path in ("packed_vpu", "mxu_popcount", "fused", "compact"):
            check(path in seen, f"path {path} never executed")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
