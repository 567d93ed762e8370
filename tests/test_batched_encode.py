"""Batched literal encode of a serving cycle (launch/serve_tm.py).

A raw request to a resident vanilla, coalesced or regression tenant is
only padded at ``enqueue`` and kept on the host; ``flush_async`` encodes
the flat bank's whole cycle in one transfer and one dispatch
(``ProgramBank.predict_raw``).  Every other request — pre-encoded
literals, head and conv tenants, non-resident tenants, pod mode, and a
cycle that mixes those with host blocks in the flat bank — is encoded per
request as before.  Whatever the path, the answers equal ``predict()``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import TMSpec
from repro.launch.mesh import make_tenant_mesh
from repro.launch.scheduler import TMScheduler
from repro.launch.serve_tm import TMServer, demo_batch, demo_specs
from repro.runtime.fault import InjectedFault

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")

BATCH_SLOT = 32
RAW = ("cotm", "regression", "vanilla")      # feature counts 20, 32, 12


def _specs():
    demo = demo_specs(small=True)
    return {
        "cotm": TMSpec.coalesced(features=20, classes=4, clauses=24, T=16,
                                 s=4.0),
        "vanilla": TMSpec.vanilla(features=12, classes=3, clauses=8, T=16,
                                  s=4.0),
        "regression": TMSpec.regression(features=32, clauses=24, T=64,
                                        s=3.0),
        "idle": TMSpec.coalesced(features=28, classes=2, clauses=16, T=16,
                                 s=4.0),
        "head": demo["head"], "conv": demo["conv"]}


@pytest.fixture(scope="module")
def roster():
    specs = _specs()
    return specs, api.compile(api.tile_for(*specs.values()))


def _server(engine, specs, names=RAW, mesh=None):
    srv = TMServer(engine, batch_slot=BATCH_SLOT, mesh=mesh)
    for i, name in enumerate(names):
        srv.register(name, specs[name], seed=5 + i)
    return srv


def _requests(specs, names, sizes=(1, 7, 32), seed=0):
    return {name: demo_batch(specs[name], sizes[i % len(sizes)],
                             seed=seed + i)
            for i, name in enumerate(names)}


def _counts(srv):
    st = srv.stats()
    return (st["encode_batches"], st["encode_batched_requests"],
            st["encode_eager_requests"])


def _assert_answers_equal_predict(srv, got, reqs):
    assert sorted(got) == sorted(reqs)
    for name, x in reqs.items():
        want = srv.predict(name, x)
        assert np.array_equal(got[name], want), name


def _column_major(x):
    """``x`` as a column-major view into a larger pool, the memory order
    ``np.asarray`` of a TPU array gives."""
    pool = np.asfortranarray(np.concatenate([x, x[::-1]]))
    return pool[:x.shape[0]]


@pytest.mark.parametrize("sizes,order", [
    ((1, 7, 32), "row"), ((32, 1, 7), "row"),
    ((32, 32, 32), "column"), ((7, 32, 1), "column")])
def test_batched_literals_equal_per_request_encode(roster, sizes, order):
    """Each slot of the in-trace encode is ``engine.encode`` of its
    padded request, bit for bit, whichever memory order the request
    came in; an idle slot is all zero."""
    specs, engine = roster
    srv = _server(engine, specs, names=RAW + ("idle",))
    reqs = _requests(specs, RAW, sizes)
    if order == "column":
        reqs = {name: _column_major(x) for name, x in reqs.items()}
    for name, x in reqs.items():
        srv.enqueue(name, x)
    by_name = {name: (x, n) for name, x, n in srv._pending}
    assert all(isinstance(x, np.ndarray) and x.shape[0] == BATCH_SLOT
               for x, _ in by_name.values())
    names = srv.resident_names(conv=False)
    feats, n_feats = srv._stack_raw(names, by_name)
    assert feats.shape == (4, engine.L // 2, BATCH_SLOT)
    assert feats.dtype == np.int8 and n_feats.dtype == np.int32
    lits = np.asarray(jax.jit(engine._encode_bank)(feats, n_feats))
    for k, name in enumerate(names):
        if name == "idle":
            assert n_feats[k] == 0 and not lits[k].any()
            continue
        assert n_feats[k] == specs[name].features
        padded, _ = srv._pad(reqs[name])
        want = np.asarray(engine.encode(specs[name], jnp.asarray(padded)))
        assert lits[k].dtype == want.dtype and np.array_equal(lits[k], want)


@pytest.mark.parametrize("order", ["row", "column"])
def test_all_raw_resident_cycle_is_one_batched_encode(roster, order):
    specs, engine = roster
    srv = _server(engine, specs)
    reqs = _requests(specs, RAW, sizes=(32, 7, 32))
    if order == "column":
        reqs = {name: _column_major(x) for name, x in reqs.items()}
    for name, x in reqs.items():
        srv.enqueue(name, x)
    got = srv.flush()
    assert _counts(srv) == (1, 3, 0)
    st = srv.stats()
    assert st["requests"] == 3 and st["stacked_launches"] == 1
    assert st["coalesced_requests"] == 3 and st["cold_requests"] == 0
    _assert_answers_equal_predict(srv, got, reqs)


@pytest.mark.parametrize("case,counts", [
    # a pre-encoded request mixes the flat bank: all of it per request
    ("encoded", (0, 0, 3)),
    # a head tenant shares the flat bank: all of it per request
    ("head", (0, 0, 4)),
    # conv is its own bank: the flat cycle still batches
    ("conv", (1, 3, 1)),
    # non-resident at enqueue: encoded there, served on the cold path
    ("cold", (1, 2, 1)),
    # demoted after enqueue: its host block is encoded at the flush
    ("demoted", (1, 2, 1)),
])
def test_mixed_cycles_count_eager_requests(roster, case, counts):
    specs, engine = roster
    names = RAW + ((case,) if case in ("head", "conv") else ())
    srv = _server(engine, specs, names=names)
    reqs = _requests(specs, names)
    if case == "cold":
        srv.set_resident(["cotm", "vanilla"])
    for name, x in reqs.items():
        if case == "encoded" and name == "cotm":
            srv.enqueue(name, engine.encode(specs[name], jnp.asarray(x)),
                        encoded=True)
        else:
            srv.enqueue(name, x)
    if case == "demoted":
        srv.set_resident(["cotm", "vanilla"])
    got = srv.flush()
    assert _counts(srv) == counts
    assert srv.stats()["cold_requests"] == (case in ("cold", "demoted"))
    _assert_answers_equal_predict(srv, got, reqs)


def test_cycles_of_other_sizes_and_widths_share_one_executable():
    """Cycles with different numbers of pending requests and feature
    width mixes reuse one raw bank executable; no stage retraces."""
    specs = _specs()
    engine = api.compile(api.tile_for(*specs.values()))
    srv = _server(engine, specs)
    for names, sizes in ((RAW, (1, 7, 32)), (("vanilla",), (5,)),
                         (("cotm", "regression"), (32, 3)),
                         (("regression", "vanilla"), (32, 32))):
        reqs = _requests(specs, names, sizes, seed=len(names))
        if names[0] == "regression":          # one row-, one column-major
            reqs["vanilla"] = _column_major(reqs["vanilla"])
        for name, x in reqs.items():
            srv.enqueue(name, x)
        _assert_answers_equal_predict(srv, srv.flush(), reqs)
    assert _counts(srv) == (4, 8, 0)
    cache = engine.cache_report()
    assert cache["predict_bank_raw"] == 1
    assert cache["predict_bank_list"] == 0
    assert all(v <= 1 for v in cache.values() if isinstance(v, int)), cache


def test_scheduled_raw_cycles_batch_every_request(roster):
    specs, engine = roster
    srv = _server(engine, specs)
    sched = TMScheduler(srv)
    reqs = [_requests(specs, RAW, seed=s) for s in (0, 10)]
    futs = [{n: sched.submit(n, x) for n, x in r.items()} for r in reqs]
    sched.drain()
    st = sched.stats()
    assert st["failed"] == 0 and st["completed"] == 6
    assert _counts(srv) == (st["launches"], 6, 0)
    for r, f in zip(reqs, futs):
        _assert_answers_equal_predict(
            srv, {n: fut.result(timeout=1) for n, fut in f.items()}, r)


def test_batched_launch_fault_is_retried_at_launch(roster, monkeypatch):
    """A transient failure inside the batched encode's launch surfaces at
    the ``launch`` boundary: the queue stays whole, the retry serves it."""
    specs, engine = roster
    srv = _server(engine, specs)
    sched = TMScheduler(srv)
    real, calls = engine.predict_bank_raw, []

    def flaky(*args):
        calls.append(len(calls))
        if len(calls) == 1:
            raise InjectedFault("launch", 0)
        return real(*args)

    monkeypatch.setattr(engine, "predict_bank_raw", flaky)
    reqs = _requests(specs, RAW, seed=3)
    futs = {n: sched.submit(n, x) for n, x in reqs.items()}
    sched.drain()
    st = sched.stats()
    assert len(calls) == 2 and st["retries"] == 1 and st["failed"] == 0
    assert srv.stats()["requests"] == 3 and _counts(srv) == (1, 3, 0)
    _assert_answers_equal_predict(
        srv, {n: f.result(timeout=1) for n, f in futs.items()}, reqs)


@pytest.mark.parametrize("bad", ["too_wide", "one_dim", "text"])
def test_malformed_raw_request_fails_alone_at_enqueue(roster, bad):
    """A raw request the batched encode cannot stack is refused at its
    own ``enqueue`` (as ``engine.encode`` refuses one wider than L/2);
    it is never queued, and the next flush still serves every other
    tenant."""
    specs, engine = roster
    srv = _server(engine, specs)
    reqs = _requests(specs, RAW)
    x = {"too_wide": np.ones((4, engine.L // 2 + 1), np.int8),
         "one_dim": np.ones(12, np.int8),
         "text": np.full((4, 12), "1")}[bad]
    srv.enqueue("cotm", reqs["cotm"])
    with pytest.raises(ValueError):
        srv.enqueue("vanilla", x)
    for name in ("vanilla", "regression"):
        srv.enqueue(name, reqs[name])
    assert srv.stats()["queue_depth"] == 3
    got = srv.flush()
    assert _counts(srv) == (1, 3, 0)
    _assert_answers_equal_predict(srv, got, reqs)


@needs_mesh
def test_pod_mode_encodes_per_request(roster):
    specs, engine = roster
    single = _server(engine, specs)
    pod = _server(engine, specs, mesh=make_tenant_mesh(4))
    reqs = _requests(specs, RAW)
    for name, x in reqs.items():
        pod.enqueue(name, x)
    got = pod.flush()
    assert _counts(pod) == (0, 0, 3)
    _assert_answers_equal_predict(single, got, reqs)
