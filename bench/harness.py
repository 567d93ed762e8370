"""The benchmark harness: one cell of ``BENCHMARK.json``, one run.

A cell names a configuration (``configs/<config>.json``: the model's
TM kind, its sizes, where they come from, and the data recipe) and a
traffic mix (``traffic/<traffic>.json``: the parameters the one generator
reads).  Everything that depends on the kind (the ``api.TMSpec``, the
inputs and tenant programs, the plain reference, the work at published
sizes) is the kind module's, ``kinds/<kind>.py`` (``kinds/coalesced.py``
states the contract).  Its metrics are the ``end_to_end`` (``--trace 0``)
or ``per_layer`` (``--trace 1``) entries of ``BENCHMARK.json`` that list
the cell, or list no cells; each is read by ``metrics/<name>.py``.

Adding a configuration of a new kind therefore adds files and entries
and edits nothing here: ``kinds/<kind>.py`` with its reference beside it
(``kinds/<kind>_ref.py``, importing nothing of the program), the
configuration ``configs/<config>.json`` naming that kind, a traffic file,
any ``stages/<stage>.json`` its kernels need, the ``metrics/<name>.py``
of new metrics, and its ``BENCHMARK.json`` entries.  A configuration of a
kind that is already here needs no kind module or reference of its own.

A run: set-up (data, tenant programs, the served stack or the estimator,
warm-up of every shape the traffic uses) -> the measured window ->
the comparison with the kind's plain reference -> one JSON line.  Lines
before the last are diagnostics.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import pathlib
import queue
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
WINDOW_SPAN = "bench.window"
ANSWER_WAIT_S = 60.0

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class CompileMeter:
    """Backend-compile seconds, programs and persistent-cache hits, fed by
    ``jax.monitoring`` listeners."""

    def __init__(self):
        self.secs, self.programs, self.hits = 0.0, 0, 0

    def on_duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.secs += secs
            self.programs += 1

    def on_event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1

    def install(self) -> "CompileMeter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self


class GcMeter:
    """Python's garbage collections: count, longest and total seconds per
    generation, fed by ``gc.callbacks``."""

    def __init__(self):
        self.t0, self.gens = 0.0, {0: [0, 0.0, 0.0], 1: [0, 0.0, 0.0],
                                   2: [0, 0.0, 0.0]}

    def __call__(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter()
            return
        d = time.perf_counter() - self.t0
        g = self.gens[info["generation"]]
        g[0] += 1
        g[1] = max(g[1], d)
        g[2] += d

    def __enter__(self) -> "GcMeter":
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)


# ------------------------------------------------------------- definitions

def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic and metrics, read
    from ``root`` (data).  The configuration's kind module, like a metric
    reader, is code and comes from this package: ``kinds.of`` finds it,
    here to fail early on a missing kind, later at every use."""
    import kinds
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    config = load_json(root / conf["file"])
    kinds.of(config)
    return {"cell": cell, "config": config,
            "traffic": load_json(root / HERE.name / "traffic"
                                 / f"{cell['traffic']}.json"),
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_chip(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")


# ------------------------------------------------------------------ serving

class Rec:
    """One inference request as the client saw it (times are seconds from
    t0); a closed-loop request names its ``client``, which sends the next
    once this one is answered."""

    __slots__ = ("tenant", "idx", "rows", "due", "sub", "done", "refused",
                 "fut", "client")

    def __init__(self, tenant, idx, rows, due, client=None):
        self.tenant, self.idx, self.rows = tenant, idx, rows
        self.due, self.sub, self.done = due, None, None
        self.refused, self.fut, self.client = False, None, client

    @property
    def answered(self) -> bool:
        return self.refused or self.done is not None

    def ok(self) -> bool:
        return (not self.refused and self.done is not None
                and self.fut.exception() is None)

    def latency(self) -> float:
        return self.done - self.due if self.ok() else math.inf


class ServeRun:
    """A roster of tenant programs behind a started ``TMScheduler``,
    assembled as ``api.serve`` assembles it, with programs the benchmark
    made from the seed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.tr, self.seed = cfg, traffic, seed

    # ---- set-up ------------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro import api
        from repro.launch.scheduler import SchedulerConfig, TMScheduler
        from repro.launch.serve_tm import TMServer
        import data
        import kinds
        cfg, tr = self.cfg, self.tr
        kind = kinds.of(cfg)
        self.mot = kind.motifs(cfg)
        x, _ = kind.rows(cfg, self.mot, self.seed, tr["pool_rows"])
        self.x = np.asarray(x)
        self.spec = kind.spec(cfg)
        self.engine = api.compile(api.tile_for(self.spec))
        self.server = TMServer(self.engine, batch_slot=tr["batch_slot"])
        self.sched = TMScheduler(self.server, config=SchedulerConfig(
            **tr.get("scheduler", {})))
        self.names = [f"t{i:04d}" for i in range(tr["tenants"])]
        # each tenant draws its rows from a stream of its own, so the rows
        # a tenant is sent do not depend on how its requests interleave
        self.rngs = {n: np.random.default_rng([self.seed, 11, i])
                     for i, n in enumerate(self.names)}
        key = jax.random.PRNGKey(0)
        for i, name in enumerate(self.names):
            ta, w = kind.program(cfg, self.mot, tr["programs"], self.seed, i)
            prog = self.engine.lower(self.spec, key, ta=ta, weights=w)
            self.sched.register(name, self.spec, program=prog,
                                seed=data.tenant_seed(self.seed, i))
        self.roster_bytes = sum(self.server.program_nbytes(n)
                                for n in self.names)
        self._warm()
        self.sched.start()

    def _batch(self, name: str, rows: int) -> int:
        """Offset of ``rows`` consecutive pool rows for tenant ``name``."""
        return int(self.rngs[name].integers(0, self.tr["pool_rows"] - rows
                                            + 1))

    def _warm(self) -> None:
        """Every shape the traffic uses, inline: inference at each request
        size on resident and (if the bank is capped) non-resident tenants,
        a bank-membership swap and its reverse."""
        srv = self.server
        resident = srv.resident_names()
        cold = [n for n in self.names if n not in resident]
        for rows in self._infer_sizes():
            for name in resident[:2] + cold[:1]:
                self._submit(Rec(name, self._batch(name, rows), rows, 0.0),
                             warm=True)
        self.sched.drain()
        if cold and srv.resident_names():
            out = srv.resident_names()[-1]
            srv.swap_resident(out, cold[0])
            srv.swap_resident(cold[0], out)

    def _infer_sizes(self) -> list:
        sizes = set()
        if "open" in self.tr:
            sizes.update(r for r, _ in self.tr["open"]["rows"])
        if "closed" in self.tr:
            sizes.add(self.tr["closed"]["rows"])
        return sorted(sizes)

    def _submit(self, rec: Rec, warm: bool = False) -> None:
        from repro.launch.scheduler import Backpressure
        x = self.x[rec.idx:rec.idx + rec.rows]
        try:
            rec.fut = self.sched.submit(rec.tenant, x)
        except Backpressure:
            rec.refused = True
            return
        if not warm:
            rec.fut.add_done_callback(lambda f, r=rec: self._done(r))

    def _done(self, rec: Rec) -> None:
        rec.done = time.perf_counter() - self.t0
        self.q.put(rec)

    # ---- traffic -----------------------------------------------------------
    def _open_schedule(self, seconds: float) -> list:
        """Open-loop arrivals due in [0, seconds): Poisson at ``rate_rps``,
        tenants by Zipf(``zipf``) popularity of their rank, request sizes
        from the ``rows`` mix."""
        o, rng = self.tr["open"], np.random.default_rng([self.seed, 13])
        rate = o["rate_rps"]
        n_max = int(rate * seconds * 1.5 + 100)
        t = np.cumsum(rng.exponential(1.0 / rate, n_max))
        t = t[t < seconds]
        n = len(t)
        p = np.arange(1, len(self.names) + 1, dtype=float) ** -o["zipf"]
        tenants = rng.choice(len(self.names), n, p=p / p.sum())
        sizes, w = zip(*o["rows"])
        rows = rng.choice(sizes, n, p=np.asarray(w, float) / sum(w))
        return [Rec(self.names[k], 0, int(r), float(d))
                for d, k, r in zip(t, tenants, rows)]

    def window(self, seconds: float) -> dict:
        """Drive the traffic for ``seconds``, wait for every answer due in
        it, and return what the client saw."""
        import jax
        tr = self.tr
        opened = self._open_schedule(seconds) if "open" in tr else []
        for rec in opened:
            rec.idx = self._batch(rec.tenant, rec.rows)
        closed = tr.get("closed")
        self.q = queue.SimpleQueue()
        recs: list = []
        c0 = self.counters()
        self.t0 = time.perf_counter()
        late = []

        def now():
            return time.perf_counter() - self.t0

        def send(rec):
            rec.sub = now()
            late.append(rec.sub - rec.due)
            recs.append(rec)
            self._submit(rec)

        def resend(rec):
            if rec.client is None or now() >= seconds:
                return
            send(Rec(rec.tenant, self._batch(rec.tenant, rec.rows), rec.rows,
                     now(), rec.client))

        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            for name in self.names if closed else []:
                send(Rec(name, self._batch(name, closed["rows"]),
                         closed["rows"], 0.0, client=name))
            i = 0
            while True:
                t = now()
                while i < len(opened) and opened[i].due <= t:
                    send(opened[i])
                    i += 1
                try:
                    while True:
                        resend(self.q.get_nowait())
                except queue.Empty:
                    pass
                t = now()
                if t >= seconds and i >= len(opened):
                    break
                nxt = opened[i].due if i < len(opened) else seconds
                wait = nxt - t
                if wait > 0:
                    try:
                        resend(self.q.get(timeout=min(wait, 0.05)))
                    except queue.Empty:
                        pass
            deadline = time.perf_counter() + ANSWER_WAIT_S
            while (any(not r.answered for r in recs)
                   and time.perf_counter() < deadline):
                try:
                    self.q.get(timeout=0.05)
                except queue.Empty:
                    pass
        t_end = now()
        c1 = self.counters()
        return {"seconds": seconds, "records": recs, "late": late,
                "counters": (c0, c1), "t_end": t_end}

    def counters(self) -> dict:
        st = self.sched.stats()
        srv = st["server"]
        return {k: st[k] for k in ("launches", "completed", "promotions",
                                   "failed")} | {
            k: srv[k] for k in ("requests", "cold_requests",
                                "stacked_launches", "coalesced_requests",
                                "membership_swaps")}

    def stop(self) -> None:
        self.sched.stop()

    def path_per_stage(self) -> dict:
        return self.engine.cache_report()["path_per_stage"]

    def free(self) -> None:
        del self.sched, self.server, self.engine
        gc.collect()


def compare_serving(cfg: dict, traffic: dict, seed: int, recs: list,
                    lower=None) -> dict:
    """Every compared number of a serving run against the reference.

    * ``wrong_answers``: answers in a seeded sample whose predictions
      differ from the reference model's;
    * ``unanswered``: requests due in the window with no answer (neither
      a result nor a refusal) a minute past its close.
    ``lower`` (e.g. ``{"weight_bits": 8}``) computes the reference at a
    precision below the configuration's (the control)."""
    import jax.numpy as jnp
    import kinds
    kind = kinds.of(cfg)
    mot = kind.motifs(cfg)
    x = np.asarray(kind.rows(cfg, mot, seed, traffic["pool_rows"])[0])
    hp = kind.hyper(cfg, **(lower or {}))
    infer = [r for r in recs if r.ok()]
    n = traffic["check"]["sample_requests"]
    if len(infer) > n:
        pick = np.random.default_rng([seed, 19]).choice(len(infer), n,
                                                        replace=False)
        infer = [infer[i] for i in sorted(pick)]
    by_tenant: dict = {}
    for r in infer:
        by_tenant.setdefault(r.tenant, []).append(r)
    out = {"wrong_answers": 0,
           "unanswered": sum(not r.answered for r in recs)}
    for name, asks in sorted(by_tenant.items()):
        ta, w = kind.program(cfg, mot, traffic["programs"], seed,
                             int(name[1:]))
        for r in asks:
            want = np.asarray(kind.predict(
                hp, ta, w, jnp.asarray(x[r.idx:r.idx + r.rows])))
            got = np.asarray(r.fut.result())
            out["wrong_answers"] += int(not np.array_equal(got, want))
    return out


def jax_host(tree) -> dict:
    import jax
    return {k: np.asarray(v) for k, v in jax.device_get(tree).items()}


# ------------------------------------------------------------------ fitting

class FitRun:
    """``api.TM.fit`` over the whole training set, epoch after epoch."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.tr, self.seed = cfg, traffic, seed

    def setup(self) -> None:
        import jax
        from repro import api
        import data
        import kinds
        cfg, f = self.cfg, self.tr["fit"]
        kind = kinds.of(cfg)
        mot = kind.motifs(cfg)
        x, y = kind.rows(cfg, mot, self.seed, f["rows"])
        self.x, self.y = np.asarray(x), np.asarray(y)
        self.spec = kind.spec(cfg)
        self.tm = api.TM(self.spec, seed=data.tenant_seed(self.seed, 0))
        self.ta0, self.w0 = kind.program(cfg, mot, self.tr["programs"],
                                         self.seed, 0)
        self.tm.program = self.tm.engine.lower(
            self.spec, jax.random.PRNGKey(0), ta=self.ta0, weights=self.w0)
        self.roster_bytes = sum(a.nbytes for a in
                                jax.tree.leaves(self.tm.program))
        # the first epoch: compiles, and is what the reference follows
        self.first = self.tm.fit(self.x, self.y, epochs=1, batch=f["batch"])
        self.snap = kind.unpad(cfg, np.asarray(self.tm.program.ta),
                               np.asarray(self.tm.program.weights))

    def window(self, seconds: float) -> dict:
        import jax
        f = self.tr["fit"]
        hist = []
        self.t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            while time.perf_counter() - self.t0 < seconds:
                hist += self.tm.fit(self.x, self.y, epochs=1,
                                    batch=f["batch"])
        t_end = time.perf_counter() - self.t0
        return {"seconds": t_end, "epochs": hist, "t_end": t_end,
                "steps": len(hist) * (f["rows"] // f["batch"])}

    def stop(self) -> None:
        pass

    def path_per_stage(self) -> dict:
        return self.tm.engine.cache_report()["path_per_stage"]

    def free(self) -> None:
        del self.tm
        gc.collect()


def compare_fit(cfg: dict, traffic: dict, seed: int, first: list, snap,
                lower=None) -> dict:
    """The first epoch against the reference: ``wrong_ta_states`` and
    ``wrong_weights`` of the program after it, and ``wrong_epoch_stats``
    (the epoch's summed selections, skipped groups and correct rows)."""
    import jax.numpy as jnp
    import data
    import kinds
    kind = kinds.of(cfg)
    f = traffic["fit"]
    mot = kind.motifs(cfg)
    x, y = (np.asarray(a) for a in kind.rows(cfg, mot, seed, f["rows"]))
    ta, w = kind.program(cfg, mot, traffic["programs"], seed, 0)
    B = f["batch"]
    n = f["rows"] - f["rows"] % B
    # TMSession.fit_epochs' batch order: one permutation per fit call
    idx = np.random.default_rng(0).permutation(f["rows"])[:n]
    xs = jnp.asarray(x[idx].reshape(n // B, B, *x.shape[1:]))
    ys = jnp.asarray(y[idx].reshape(n // B, B))
    hp = kind.hyper(cfg, **(lower or {}))
    (r_ta, r_w, _), st = kind.train(hp, ta, w,
                                    data.tenant_seed(seed, 0) + 1, xs, ys)
    st = jax_host(st)
    rec = first[0]
    got = {"selected": rec["selected_clauses"],
           "active_groups": rec["active_groups"],
           "correct": round(rec["train_acc"] * n)}
    return {"wrong_ta_states": int((np.asarray(r_ta) != snap[0]).sum()),
            "wrong_weights": int((np.asarray(r_w) != snap[1]).sum()),
            "wrong_epoch_stats": sum(int(st[k].sum()) != v
                                     for k, v in got.items())}


# -------------------------------------------------------------------- a run

LIMITS = {"wrong_answers": 0, "unanswered": 0, "wrong_ta_states": 0,
          "wrong_weights": 0, "wrong_epoch_stats": 0}


def evidence(R, mode: str, res: dict) -> dict:
    """What the comparison needs from a finished run; frees the run's
    device state (the reference runs after it, so that it sets no
    memory peak)."""
    if mode == "fit":
        ev = {"first": R.first, "snap": R.snap}
    else:
        ev = {"recs": res["records"]}
    R.free()
    return ev


def compare(mode: str, cfg: dict, traffic: dict, seed: int, ev: dict,
            lower=None) -> dict:
    if mode == "fit":
        return compare_fit(cfg, traffic, seed, ev["first"], ev["snap"],
                           lower)
    return compare_serving(cfg, traffic, seed, ev["recs"], lower)


def control_readings(cfg: dict, traffic: dict, seed: int,
                     seconds: float) -> dict:
    """One run of a cell at its own load (a window of ``seconds``), its
    numbers compared with the reference as configured (``sound``) and
    with the reference at the traffic's ``check.control`` precision,
    one step below the configuration's, put in the program's place
    (``control``).  Where the program is sound, the control's numbers
    are those of the lower-precision reference against the configured
    one."""
    mode = "fit" if "fit" in traffic else "serve"
    R = (FitRun if mode == "fit" else ServeRun)(cfg, traffic, seed)
    R.setup()
    res = R.window(seconds) if mode == "serve" else None
    R.stop()
    ev = evidence(R, mode, res)
    return {"sound": compare(mode, cfg, traffic, seed, ev),
            "control": compare(mode, cfg, traffic, seed, ev,
                               traffic["check"]["control"])}


def device_info(devices) -> dict:
    import jax
    d = jax.devices()[0]
    peak = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dv in devices)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(peak)}


def summarize(mode: str, res: dict, run) -> dict:
    """What the metric readers read."""
    ctx = {"window_s": res["seconds"], "mode": mode}
    if mode == "fit":
        f = run.tr["fit"]
        epochs = res["epochs"]
        ctx["train_rows"] = res["steps"] * f["batch"]
        ctx["train_span_s"] = res["seconds"]
        ctx["train_steps"] = res["steps"]
        tot = sum(e["total_groups"] for e in epochs)
        ctx["active_share"] = (sum(e["active_groups"] for e in epochs)
                               / tot if tot else None)
        return ctx
    S = res["seconds"]
    recs = res["records"]
    due = [r for r in recs if r.due < S]
    ctx["infer_latencies_s"] = [r.latency() for r in due]
    # a rate is the rows of every request sent in the window over the time
    # from its start until the last of them was answered: all the work and
    # all the time, with no quantum of a whole batch at the window's edge
    ok = [r for r in due if r.ok()]
    ctx["infer_rows"] = sum(r.rows for r in ok)
    ctx["infer_span_s"] = max((r.done for r in ok), default=S)
    ctx["infer_rows_all"] = sum(r.rows for r in recs if r.ok())
    ctx["infer_requests_all"] = sum(1 for r in recs if r.ok())
    c0, c1 = res["counters"]
    ctx["counters"] = {k: c1[k] - c0[k] for k in c0}
    ctx["attempted"] = len(recs)
    ctx["failed"] = sum(not r.ok() for r in recs)
    return ctx


def run(args, t_start: float, root: pathlib.Path = ROOT,
        require_chip: bool = True, out=sys.stdout, err=sys.stderr) -> int:
    """One run of one cell; prints diagnostics, then the result line."""
    d = load_cell(args.workload, root)
    cfg, traffic, cell = d["config"], d["traffic"], d["cell"]

    def log(msg):
        print(msg, file=err, flush=True)

    import jax
    if require_chip:
        check_chip(cell["chips"])
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    meter = CompileMeter().install()
    mode = "fit" if "fit" in traffic else "serve"
    R = (FitRun if mode == "fit" else ServeRun)(cfg, traffic, args.seed)
    R.setup()
    # the load generator shares the process with the served stack: a full
    # collection in the window would walk every object set-up made (the
    # roster's programs, JAX's caches) and stall both for seconds, in some
    # runs and not others; set-up's objects are frozen out of its reach
    gc.collect()
    gc.freeze()
    log(f"set-up: {time.perf_counter() - t_start} s, {meter.programs} "
        f"programs compiled ({meter.secs} s), {meter.hits} from the cache")
    compiles0 = meter.programs
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    ctx_tr = (jax.profiler.trace(trace_dir, profiler_options=_trace_options())
              if args.trace else contextlib.nullcontext())
    setup_s = time.perf_counter() - t_start
    with ctx_tr, GcMeter() as gcm:
        res = R.window(float(args.seconds))
    compiles = meter.programs - compiles0
    R.stop()
    ctx = summarize(mode, res, R)
    ctx["setup_s"] = setup_s
    ctx["config"], ctx["traffic"] = cfg, traffic
    dev = device_info(jax.devices()[:cell["chips"]])
    log(f"device: {dev}; compile cache {cache}")
    log(f"path_per_stage: {json.dumps(R.path_per_stage(), sort_keys=True)}")
    log(f"compiles inside the window: {compiles}")
    log("garbage collections in the window (count, longest s, total s) "
        f"by generation: {gcm.gens}; frozen at set-up: {gc.get_freeze_count()}")
    log(f"roster bytes: {R.roster_bytes}; peak_bytes_in_use: "
        f"{dev['memory_peak_bytes']}")
    if mode == "serve":
        late = np.asarray(res["late"]) * 1e3
        if late.size:
            log(f"generator lateness ms: p50 {np.percentile(late, 50)} "
                f"p99 {np.percentile(late, 99)} max {late.max()} "
                f"over {late.size} sends")
        log(f"counters over the window: {ctx['counters']}")
    else:
        log(f"epochs in the window: {len(res['epochs'])}")
    t_check = time.perf_counter()
    compared = compare(mode, cfg, traffic, args.seed, evidence(R, mode, res))
    log(f"reference check: {time.perf_counter() - t_check} s")
    trace = None
    if args.trace:
        import trace_reduce as tr_mod
        t_trace = time.perf_counter()
        events = tr_mod.events_from_xplane(tr_mod.find_xplane(trace_dir))
        lo, hi = tr_mod.window_of(events, WINDOW_SPAN)
        trace = tr_mod.reduce(events, lo, hi, tr_mod.stage_patterns(),
                              skip=(WINDOW_SPAN,))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduction: {len(events)} events, "
            f"{time.perf_counter() - t_trace} s")
        ctx["trace"] = trace
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
        log(f"trace: {trace['devices']} device planes, stage seconds "
            f"{trace['stage_s']}")
    import work
    ctx["peaks"] = work.peaks(dev["kind"]) if dev["platform"] == "tpu" \
        else None
    metrics = {}
    for m in (d["per_layer"] if args.trace else d["end_to_end"]):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in
              compared.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct,
              "attempted": ctx.get("attempted", ctx.get("train_steps", 0)),
              "failed": ctx.get("failed", 0),
              "metrics": metrics, "device": dev}
    if trace is not None:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["compared"] = checks
    for k, c in checks.items():
        log(f"compared {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), file=out, flush=True)
    return 0


def _trace_options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 2
    return o


def parse(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)
