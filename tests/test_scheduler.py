"""Async continuous-batching scheduler (launch/scheduler.py).

Tentpole invariant: whatever the batching, pipelining, or bank
membership, scheduled results are BIT-IDENTICAL to the synchronous
per-tenant ``enqueue`` + ``flush`` path — checked single-device here and
on the forced-4-device mesh leg (``XLA_FLAGS=--xla_force_host_platform_
device_count=4``, the ``mesh`` CI leg).
"""
import time

import jax
import numpy as np
import pytest

from repro import api
from repro.launch.mesh import make_tenant_mesh
from repro.launch.scheduler import (BATCH, GOLD, STANDARD, Backpressure,
                                    SchedulerConfig, SLAClass, TMScheduler)
from repro.launch.serve_tm import TMServer, demo_batch, demo_specs

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")

BATCH_SLOT = 16


@pytest.fixture(scope="module")
def roster():
    specs = demo_specs(small=True)
    engine = api.compile(api.tile_for(*specs.values()))
    return specs, engine


def _mk_server(engine, specs, names=None, mesh=None, seed=2):
    srv = TMServer(engine, batch_slot=BATCH_SLOT, mesh=mesh)
    for name in (names or specs):
        srv.register(name, specs[name], seed=seed)
    return srv


def _trace(specs, names, rounds=2):
    """A fixed request trace: (round, tenant, batch) triples with
    varying per-request content and ragged sizes."""
    out = []
    for r in range(rounds):
        for i, name in enumerate(names):
            n = BATCH_SLOT if (r + i) % 2 == 0 else BATCH_SLOT // 2
            out.append((name, demo_batch(specs[name], n,
                                         seed=17 + 7 * r + i)))
    return out


def _sync_results(srv, trace):
    """The synchronous baseline: one enqueue + flush per request."""
    out = []
    for name, x in trace:
        srv.enqueue(name, x)
        out.append(srv.flush()[name])
    return out


# ---------------------------------------------------------------------------
# determinism: scheduled == synchronous flush (tentpole acceptance)
# ---------------------------------------------------------------------------

def test_scheduled_bit_identical_to_sync_flush(roster):
    """Fixed trace, all five TM kinds, a training request mid-trace:
    the scheduler's coalesced/pipelined results match the per-request
    synchronous flush bit-for-bit."""
    specs, engine = roster
    names = sorted(specs)
    srv_ref = _mk_server(engine, specs)
    srv_sch = _mk_server(engine, specs)
    sched = TMScheduler(srv_sch,
                        SchedulerConfig(pipeline_depth=2))

    trace = _trace(specs, names, rounds=2)
    ref = _sync_results(srv_ref, trace)

    futs = [sched.submit(name, x) for name, x in trace]
    sched.drain()
    for (name, _), fut, want in zip(trace, futs, ref):
        assert np.array_equal(fut.result(timeout=1), want), name

    # an on-line training request dirties a bank slot; the next
    # scheduled flush must pick up the fresh program exactly like the
    # synchronous path does (dirty rescatter)
    xt = demo_batch(specs["cotm"], BATCH_SLOT, seed=99)
    yt = np.zeros(BATCH_SLOT, np.int32)
    srv_ref.train("cotm", xt, yt)
    srv_sch.train("cotm", xt, yt)
    trace2 = _trace(specs, names, rounds=1)
    ref2 = _sync_results(srv_ref, trace2)
    futs2 = [sched.submit(name, x) for name, x in trace2]
    sched.drain()
    for (name, _), fut, want in zip(trace2, futs2, ref2):
        assert np.array_equal(fut.result(timeout=1), want), name
    assert sched.completed == len(trace) + len(trace2)
    # coalescing happened: far fewer stacked launches than requests
    assert srv_sch.stacked_launches < srv_ref.stacked_launches


@needs_mesh
def test_scheduled_pod_bit_identical_to_sync_flush(roster):
    """Same invariant on the forced-4-device mesh: the scheduler over a
    pod-sharded server matches the single-device synchronous flush."""
    specs, engine = roster
    names = sorted(specs)
    srv_ref = _mk_server(engine, specs)
    srv_pod = _mk_server(engine, specs, mesh=make_tenant_mesh(4))
    sched = TMScheduler(srv_pod)

    trace = _trace(specs, names, rounds=2)
    ref = _sync_results(srv_ref, trace)
    futs = [sched.submit(name, x) for name, x in trace]
    sched.drain()
    for (name, _), fut, want in zip(trace, futs, ref):
        assert np.array_equal(fut.result(timeout=1), want), name


def test_flush_async_collect_equals_flush(roster):
    """The split launch/fetch path is the flush path."""
    specs, engine = roster
    srv_a = _mk_server(engine, specs)
    srv_b = _mk_server(engine, specs)
    for name in sorted(specs):
        x = demo_batch(specs[name], BATCH_SLOT, seed=5)
        srv_a.enqueue(name, x)
        srv_b.enqueue(name, x)
    out_a = srv_a.flush()
    pf = srv_b.flush_async()
    out_b = srv_b.collect(pf)
    assert set(out_a) == set(out_b)
    for name in out_a:
        assert np.array_equal(out_a[name], out_b[name]), name


# ---------------------------------------------------------------------------
# satellite: empty flush is a cheap no-op (the timer loop calls it)
# ---------------------------------------------------------------------------

def test_empty_flush_is_cheap_noop(roster):
    """flush()/flush_async() with nothing pending: no bank build, no
    stacked launch, no device sync — and an idle scheduler step is
    free."""
    specs, engine = roster
    srv = _mk_server(engine, specs)
    assert srv.flush() == {}
    assert srv.flush_async() is None
    assert srv.collect(None) == {}
    assert srv.stacked_launches == 0 and srv.requests == 0
    assert not srv._banks and not srv._groups     # nothing was built
    sched = TMScheduler(srv)
    assert sched.step() == 0
    assert sched.launches == 0
    # and it is actually cheap: no multi-ms device work on the no-op
    t0 = time.perf_counter()
    for _ in range(100):
        srv.flush()
    assert (time.perf_counter() - t0) < 0.5


# ---------------------------------------------------------------------------
# SLA queues: deadline-aware dequeue + admission control
# ---------------------------------------------------------------------------

def test_deadline_aware_dequeue_order(roster):
    """With a 1-tenant batch cap, gold (5 ms deadline) is served before
    standard (50 ms) before batch (1000 ms) regardless of submit
    order."""
    specs, engine = roster
    names = ["t_batch", "t_std", "t_gold"]
    srv = TMServer(engine, batch_slot=BATCH_SLOT)
    for n in names:
        srv.register(n, specs["cotm"], seed=3)
    sched = TMScheduler(srv, SchedulerConfig(max_batch_tenants=1))
    for n, sla in zip(names, (BATCH, STANDARD, GOLD)):
        sched.set_sla(n, sla)
    order = []
    x = demo_batch(specs["cotm"], BATCH_SLOT, seed=4)
    for n in names:                       # batch-class submitted FIRST
        sched.submit(n, x).add_done_callback(
            lambda _f, n=n: order.append(n))
    sched.drain()
    assert order == ["t_gold", "t_std", "t_batch"]
    assert sched.launches == 3            # one tenant per launch


def test_admission_control_backpressure(roster):
    specs, engine = roster
    srv = TMServer(engine, batch_slot=BATCH_SLOT)
    srv.register("t0", specs["cotm"], seed=3)
    sched = TMScheduler(srv, default_sla=SLAClass(max_queue_depth=2))
    x = demo_batch(specs["cotm"], BATCH_SLOT, seed=4)
    f1, f2 = sched.submit("t0", x), sched.submit("t0", x)
    with pytest.raises(Backpressure, match="depth cap"):
        sched.submit("t0", x)
    assert sched.rejected == 1
    assert sched.stats()["tenants"]["t0"]["rejected"] == 1
    sched.drain()                          # accepted requests still land
    assert f1.result(timeout=1) is not None
    assert f2.result(timeout=1) is not None
    # queue drained — admission is open again
    sched.submit("t0", x)
    sched.drain()


def test_per_tenant_fifo_within_batching(roster):
    """One tenant, several queued requests: served in order, one per
    launch (a bank slot serves one request per flush)."""
    specs, engine = roster
    srv = TMServer(engine, batch_slot=BATCH_SLOT)
    srv.register("t0", specs["cotm"], seed=3)
    sched = TMScheduler(srv)
    xs = [demo_batch(specs["cotm"], BATCH_SLOT, seed=s) for s in range(3)]
    futs = [sched.submit("t0", x) for x in xs]
    sched.drain()
    ref_srv = TMServer(engine, batch_slot=BATCH_SLOT)
    ref_srv.register("t0", specs["cotm"], seed=3)
    ref = _sync_results(ref_srv, [("t0", x) for x in xs])
    for fut, want in zip(futs, ref):
        assert np.array_equal(fut.result(timeout=1), want)
    assert sched.launches == 3


# ---------------------------------------------------------------------------
# pipelining
# ---------------------------------------------------------------------------

def test_pipeline_keeps_launches_in_flight(roster):
    specs, engine = roster
    srv = _mk_server(engine, specs, names=["cotm", "vanilla"])
    sched = TMScheduler(srv, SchedulerConfig(pipeline_depth=2))
    x = demo_batch(specs["cotm"], BATCH_SLOT, seed=4)
    depth_seen = 0
    for _ in range(4):
        sched.submit("cotm", x)
        sched.submit("vanilla", demo_batch(specs["vanilla"], BATCH_SLOT,
                                           seed=5))
        sched.step(force=True)
        depth_seen = max(depth_seen, len(sched._in_flight))
        assert len(sched._in_flight) <= 2
    assert depth_seen == 2                 # launches really overlapped
    sched.drain()
    assert not sched._in_flight
    assert sched.completed == sched.submitted == 8


# ---------------------------------------------------------------------------
# dynamic bank membership
# ---------------------------------------------------------------------------

def test_server_swap_resident_routed(roster):
    """Server-level promote/demote: a swapped tenant takes the demoted
    tenant's bank slot via routed swap_in/swap_out, results match the
    unrestricted server, and the demoted tenant is served cold."""
    specs, engine = roster
    flat = [n for n in sorted(specs) if specs[n].kind != "conv"]
    srv = _mk_server(engine, specs, names=flat)
    srv.set_resident(flat[:2])
    assert srv.resident_names(False) == flat[:2]
    ref = _mk_server(engine, specs, names=flat)

    def serve_one(s, name):
        x = demo_batch(specs[name], BATCH_SLOT, seed=8)
        s.enqueue(name, x)
        return s.flush()[name]

    # a resident request builds the bank; a swapped tenant is served
    # through the cold path — both match the unrestricted server
    assert np.array_equal(serve_one(srv, flat[0]), serve_one(ref, flat[0]))
    assert np.array_equal(serve_one(srv, flat[2]), serve_one(ref, flat[2]))
    assert srv.cold_requests == 1
    route = srv.swap_resident(flat[0], flat[2])
    assert route is not None and route.index == 0
    assert srv.resident_names(False) == [flat[2], flat[1]]
    assert srv.membership_swaps == 1
    # promoted tenant now rides the bank; demoted one goes cold
    before = srv.cold_requests
    assert np.array_equal(serve_one(srv, flat[2]), serve_one(ref, flat[2]))
    assert srv.cold_requests == before
    assert np.array_equal(serve_one(srv, flat[0]), serve_one(ref, flat[0]))
    assert srv.cold_requests == before + 1
    st = srv.stats()
    assert st["resident_tenants"] == 2 and st["swapped_tenants"] == 2


def test_scheduler_promotes_hot_tenant(roster):
    """EWMA membership: sustained traffic to a swapped tenant promotes
    it into the bank (demoting the coldest) and results stay correct."""
    specs, engine = roster
    flat = [n for n in sorted(specs) if specs[n].kind != "conv"]
    srv = _mk_server(engine, specs, names=flat)
    sched = TMScheduler(srv, SchedulerConfig(
        resident_slots=2, membership_every=1, min_dwell_ticks=0,
        promote_min_qps=1e-6, promote_margin=1.01))
    # auto-admission applied the capacity policy: first two resident
    assert srv.resident_names(False) == flat[:2]
    hot = flat[2]
    x = demo_batch(specs[hot], BATCH_SLOT, seed=9)
    ref = _mk_server(engine, specs, names=flat)
    ref.enqueue(hot, x)
    want = ref.flush()[hot]
    results = []
    for _ in range(6):
        f = sched.submit(hot, x)
        sched.drain()
        results.append(f.result(timeout=1))
    assert sched.promotions >= 1 and sched.demotions >= 1
    assert hot in srv.resident_names(False)
    assert len(srv.resident_names(False)) == 2   # capacity respected
    for r in results:                      # cold AND post-promotion hits
        assert np.array_equal(r, want)
    assert srv.cold_requests >= 1          # pre-promotion cold service
    assert sched.stats()["tenants"][hot]["resident"] is True


@needs_mesh
def test_swap_resident_pod_routed(roster):
    """Membership swaps route through the pod bank (padded roster):
    promote into a pad slot via add_resident, then swap_resident, with
    results identical to the single-device unrestricted server."""
    specs, engine = roster
    flat = [n for n in sorted(specs) if specs[n].kind != "conv"]
    srv = _mk_server(engine, specs, names=flat, mesh=make_tenant_mesh(4))
    srv.set_resident(flat[:3])             # pads to 4 slots on the mesh
    ref = _mk_server(engine, specs, names=flat)

    def serve_one(s, name):
        x = demo_batch(specs[name], BATCH_SLOT, seed=8)
        s.enqueue(name, x)
        return s.flush()[name]

    assert np.array_equal(serve_one(srv, flat[0]), serve_one(ref, flat[0]))
    route = srv.add_resident(flat[3])      # fills the pad slot in place
    assert route is not None and route.index == 3
    assert np.array_equal(serve_one(srv, flat[3]), serve_one(ref, flat[3]))
    # demote/promote cycle on the padded roster
    srv.set_resident(flat[:2])
    serve_one(srv, flat[0])                # rebuild bank (2 + 2 pads)
    r2 = srv.swap_resident(flat[0], flat[2])
    assert r2 is not None
    assert np.array_equal(serve_one(srv, flat[2]), serve_one(ref, flat[2]))


# ---------------------------------------------------------------------------
# stats surfaces + thread mode
# ---------------------------------------------------------------------------

def test_server_stats_surface(roster):
    specs, engine = roster
    srv = _mk_server(engine, specs, names=["cotm", "vanilla"])
    srv.set_resident(["cotm"])
    st = srv.stats()
    assert st["queue_depth"] == 0
    assert st["resident_tenants"] == 1 and st["swapped_tenants"] == 1
    assert st["requests"] == 0 and st["stacked_launches"] == 0
    srv.enqueue("cotm", demo_batch(specs["cotm"], BATCH_SLOT, seed=4))
    assert srv.stats()["queue_depth"] == 1
    srv.flush()
    st = srv.stats()
    assert st["queue_depth"] == 0
    assert st["requests"] == 1 and st["stacked_launches"] == 1
    assert st["coalesced_requests"] == 1
    assert st["cold_requests"] == 0


def test_thread_mode_end_to_end(roster):
    """Background flush loop: submits from the caller thread complete
    without any explicit step/drain, with correct results."""
    specs, engine = roster
    srv = _mk_server(engine, specs, names=["cotm", "vanilla"])
    ref = _mk_server(engine, specs, names=["cotm", "vanilla"])
    sched = TMScheduler(srv, SchedulerConfig(max_wait_s=0.001))
    trace = _trace(specs, ["cotm", "vanilla"], rounds=3)
    want = _sync_results(ref, trace)
    sched.start()
    try:
        futs = [sched.submit(name, x) for name, x in trace]
        for (name, _), fut, w in zip(trace, futs, want):
            assert np.array_equal(fut.result(timeout=60), w), name
    finally:
        sched.stop()
    assert sched.completed == len(trace)
    assert sched.stats()["running"] is False
