"""Program spans (runtime/spans.py) and the scheduler's queue-wait
counters: what a ``jax.profiler.trace`` window records of the serve
driver, the server and the fit session, and that recording changes no
result."""
import glob
import warnings

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import api
from repro.launch.scheduler import TMScheduler
from repro.launch.serve_tm import TMServer, demo_batch, demo_specs
from repro.runtime import spans

BATCH_SLOT = 16


@pytest.fixture(scope="module")
def roster():
    specs = demo_specs(small=True)
    engine = api.compile(api.tile_for(*specs.values()))
    return specs, engine


def _scheduler(engine, specs):
    srv = TMServer(engine, batch_slot=BATCH_SLOT)
    for name, spec in specs.items():
        srv.register(name, spec, seed=3)
    return TMScheduler(srv)


def _requests(specs, rounds=2):
    return [(name, demo_batch(spec, BATCH_SLOT - (r + i) % 2 * 5,
                              seed=31 + 7 * r + i))
            for r in range(rounds) for i, (name, spec)
            in enumerate(sorted(specs.items()))]


def _serve(sched, requests):
    futs = [sched.submit(name, x) for name, x in requests]
    sched.drain()
    return [np.asarray(f.result()) for f in futs]


def _traced(tmp_path, fn):
    """Run ``fn`` inside a profiler trace; returns (its result, the
    ``tm.`` host events as (line, name, start_ns, end_ns, stats))."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for k, line in enumerate(plane.lines):
                events += [((plane.name, k), ev.name, ev.start_ns, ev.end_ns,
                            dict(ev.stats)) for ev in line.events
                           if ev.name.startswith("tm.")]
    return out, events


def _named(events, name):
    return [ev for ev in events if ev[1] == name]


@pytest.fixture(scope="module")
def traced_drain(roster, tmp_path_factory):
    specs, engine = roster
    sched = _scheduler(engine, specs)
    return _traced(tmp_path_factory.mktemp("trace"),
                   lambda: _serve(sched, _requests(specs)))


def test_drain_emits_driver_and_server_spans(traced_drain):
    _, events = traced_drain
    for name in (spans.SCHED_CYCLE, spans.SCHED_FORM, spans.SCHED_SUBMIT,
                 spans.SCHED_RESOLVE, spans.SERVER_ENCODE,
                 spans.SERVER_LAUNCH, spans.SERVER_COLLECT,
                 spans.SERVER_FETCH):
        assert _named(events, name), name
    cycles = _named(events, spans.SCHED_CYCLE)
    assert all(isinstance(ev[4].get("cycle"), int) for ev in cycles)
    assert len({ev[4]["cycle"] for ev in cycles}) == len(cycles)
    # 10 requests over 5 tenants: one encode each, one launch per cycle
    assert len(_named(events, spans.SERVER_ENCODE)) == 10
    for name in (spans.SERVER_ENCODE, spans.SERVER_LAUNCH):
        for line, _, s, e, _ in _named(events, name):
            assert any(c[0] == line and c[2] <= s and e <= c[3]
                       for c in cycles), name


def test_spans_change_no_prediction(roster, traced_drain):
    specs, engine = roster
    got, _ = traced_drain
    want = _serve(_scheduler(engine, specs), _requests(specs))
    assert len(got) == len(want) == 10
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)


def test_fit_emits_bind_once_and_three_spans_per_epoch(tmp_path):
    spec = api.TMSpec.coalesced(features=12, classes=3, clauses=10, T=8,
                                s=3.0)
    rng = np.random.default_rng(5)
    x = (rng.random((64, 12)) < 0.5).astype(np.int8)
    y = rng.integers(0, 3, 64)
    tm = api.TM(spec, seed=2)
    hist, events = _traced(tmp_path, lambda: tm.fit(x, y, epochs=2,
                                                     batch=16))
    assert len(hist) == 2
    assert len(_named(events, spans.FIT_BIND)) == 1
    # a flat dataset is staged in one encode, inside the bind
    (bind,) = _named(events, spans.FIT_BIND)
    (enc,) = _named(events, spans.FIT_ENCODE)
    assert enc[0] == bind[0] and bind[2] <= enc[2] and enc[3] <= bind[3]
    for name in (spans.FIT_PLAN, spans.FIT_EPOCH, spans.FIT_FETCH):
        evs = sorted(_named(events, name), key=lambda ev: ev[2])
        assert [ev[4].get("epoch") for ev in evs] == [0, 1], name
    # plan, dispatch and fetch of one epoch run in that order
    for ep in (0, 1):
        plan, epoch, fetch = (next(ev for ev in _named(events, n)
                                   if ev[4]["epoch"] == ep)
                              for n in (spans.FIT_PLAN, spans.FIT_EPOCH,
                                        spans.FIT_FETCH))
        assert plan[3] <= epoch[2] and epoch[3] <= fetch[2]


def test_conv_staging_emits_one_encode_span_per_chunk(tmp_path,
                                                      monkeypatch):
    """Conv input is staged chunk by chunk (``dtm.STAGE_CHUNK_ROWS``
    rows a launch; the last chunk ends at the last row): 40 images in
    chunks of 16 are three ``tm.fit.encode`` spans, ``chunk`` 0..2, each
    inside the one ``tm.fit.bind``."""
    from repro.core import dtm
    monkeypatch.setattr(dtm, "STAGE_CHUNK_ROWS", 16)
    spec = api.TMSpec.conv(img_h=6, img_w=6, patch=3, classes=2, clauses=8,
                           T=8, s=3.0)
    rng = np.random.default_rng(4)
    x = (rng.random((40, 6, 6)) < 0.4).astype(np.int8)
    y = rng.integers(0, 2, 40)
    tm = api.TM(spec, seed=1)
    hist, events = _traced(tmp_path, lambda: tm.fit(x, y, epochs=1,
                                                     batch=8))
    assert len(hist) == 1
    (bind,) = _named(events, spans.FIT_BIND)
    encodes = sorted(_named(events, spans.FIT_ENCODE), key=lambda ev: ev[2])
    assert [ev[4].get("chunk") for ev in encodes] == [0, 1, 2]
    for line, _, s, e, _ in encodes:
        assert line == bind[0] and bind[2] <= s and e <= bind[3]


def test_queue_wait_counters_count_inference_taken_into_batches(roster):
    specs, engine = roster
    sched = _scheduler(engine, specs)
    st0 = sched.stats()
    assert st0["infer_formed"] == 0 and st0["infer_queue_wait_s"] == 0.0
    _serve(sched, _requests(specs, rounds=2))
    st1 = sched.stats()
    assert st1["infer_formed"] == 10 == st1["completed"]
    assert st1["infer_queue_wait_s"] >= 0.0
    # a training request is taken into a batch too, but is not inference
    x = demo_batch(specs["cotm"], BATCH_SLOT, seed=9)
    sched.submit_train("cotm", x, np.zeros(BATCH_SLOT, np.int32))
    _serve(sched, _requests(specs, rounds=1))
    st2 = sched.stats()
    assert st2["infer_formed"] == 15 and st2["trains"] == 1
    assert st2["infer_queue_wait_s"] > st1["infer_queue_wait_s"]


def test_raw_cycles_emit_one_encode_batch_each(roster, tmp_path):
    """Raw requests to resident coalesced, vanilla and regression tenants
    are padded under ``tm.server.encode`` (one per request) and encoded
    together under one ``tm.server.encode_batch`` per cycle, inside that
    cycle's launch."""
    specs, engine = roster
    raw = {n: specs[n] for n in ("cotm", "regression", "vanilla")}
    sched = _scheduler(engine, raw)
    got, events = _traced(tmp_path, lambda: _serve(sched, _requests(raw)))
    assert len(got) == 6
    st = sched.server.stats()
    assert st["encode_batches"] == sched.stats()["launches"] == 2
    assert st["encode_batched_requests"] == 6
    assert st["encode_eager_requests"] == 0
    batches = _named(events, spans.SERVER_ENCODE_BATCH)
    assert len(batches) == 2
    assert len(_named(events, spans.SERVER_ENCODE)) == 6
    launches = _named(events, spans.SERVER_LAUNCH)
    for line, _, s, e, _ in batches:
        assert any(c[0] == line and c[2] <= s and e <= c[3]
                   for c in launches)
