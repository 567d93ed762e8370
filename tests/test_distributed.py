"""Distributed-runtime tests — run in subprocesses with forced host device
counts (the main pytest process keeps the default 1 device, per the
dry-run's isolation requirement)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


@pytest.mark.slow
def test_tm_dp_equals_local_batched():
    """DP psum of integer deltas == single-device batched mode, exactly."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import TMConfig, init_state, COALESCED, to_literals
        from repro.core import feedback
        from repro.core.distributed import dp_train_step, _shard_prng
        cfg = TMConfig(tm_type=COALESCED, features=24, clauses=16, classes=3,
                       T=8, s=3.0, prng_backend="threefry")
        state = init_state(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        x = jnp.asarray((rng.random((16, 24)) < 0.4).astype(np.int8))
        y = jnp.asarray(rng.integers(0, 3, 16).astype(np.int32))
        lits = to_literals(x)
        mesh = jax.make_mesh((8,), ("data",))
        dp_state, _ = dp_train_step(cfg, state, lits, y, mesh, seed=5, chunk=2)
        # local replay: same per-shard streams, summed deltas
        acc_ta = jnp.zeros_like(state.ta)
        acc_w = jnp.zeros_like(state.weights)
        for i in range(8):
            prng = _shard_prng(cfg, 5, jnp.uint32(i))
            _, d_ta, d_w, _, _ = feedback.batched_deltas(
                cfg, state, prng, lits[i*2:(i+1)*2], y[i*2:(i+1)*2], 2)
            acc_ta += d_ta; acc_w += d_w
        ref_state, _ = feedback.apply_deltas(cfg, state, acc_ta, acc_w,
                                             jnp.zeros((16,), jnp.int32),
                                             jnp.int32(0))
        assert (np.asarray(dp_state.ta) == np.asarray(ref_state.ta)).all()
        assert (np.asarray(dp_state.weights) == np.asarray(ref_state.weights)).all()
        print("EXACT")
    """)


@pytest.mark.slow
def test_compressed_psum_shardmap():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.runtime.compression import compressed_psum
        # jax.shard_map with vma checking off, as the engine uses it
        from repro.core.distributed import shard_map
        mesh = jax.make_mesh((8,), ("data",))
        x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 128)),
                        jnp.float32)
        def f(xl):
            y, resid = compressed_psum(xl, "data")
            return y, resid
        g = shard_map(f, mesh, in_specs=(P("data"),),
                      out_specs=(P("data"), P("data")))
        y, resid = g(x)
        want = np.broadcast_to(np.asarray(x).sum(0, keepdims=True), (8, 128))
        got = np.asarray(y)
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < 0.1, rel          # int8 quantisation error bound
        assert np.abs(np.asarray(resid)).max() > 0   # error feedback active
        print("REL", rel)
    """)


@pytest.mark.slow
def test_dp_wire_compaction_exact():
    """Alg-6 WIRE compaction of the TA-delta psum (ISSUE 5): with
    compact_frac set, only the union of active rows crosses the wire —
    bit-exact vs the dense all-reduce, both when the union fits the
    capacity and when it overflows to the dense fallback.  The bucket
    predicate comes from the psum'd bitmap, so all shards take the same
    lax.cond branch (matched collectives)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import TMConfig, init_state, COALESCED, to_literals
        from repro.core.distributed import dp_train_step
        cfg = TMConfig(tm_type=COALESCED, features=24, clauses=64, classes=3,
                       T=8, s=3.0, prng_backend="threefry")
        state = init_state(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        x = jnp.asarray((rng.random((8, 24)) < 0.4).astype(np.int8))
        y = jnp.asarray(rng.integers(0, 3, 8).astype(np.int32))
        lits = to_literals(x)
        mesh = jax.make_mesh((4,), ("data",))
        dense, _ = dp_train_step(cfg, state, lits, y, mesh, seed=5, chunk=2)
        # roomy capacity: the compact branch carries the deltas
        comp, _ = dp_train_step(cfg, state, lits, y, mesh, seed=5, chunk=2,
                                compact_frac=0.5)
        # tiny capacity: overflow -> dense fallback branch, still exact
        tiny, _ = dp_train_step(cfg, state, lits, y, mesh, seed=5, chunk=2,
                                compact_frac=0.02)
        for got in (comp, tiny):
            assert (np.asarray(dense.ta) == np.asarray(got.ta)).all()
            assert (np.asarray(dense.weights)
                    == np.asarray(got.weights)).all()
        print("EXACT")
    """, devices=4)


# NOTE: the seed-era Supervisor/shrink_mesh elastic-restart test was
# retired with the runtime/fault.py rewrite (ISSUE 10) — crash recovery
# for the DTM serving stack (the thing this repo actually ships) is
# covered by tests/test_recovery.py, including its @needs_mesh leg.


@pytest.mark.slow
def test_tm_pod_step_and_alg6_compaction_exact():
    """Pod-scale CoTM step (clause×batch sharding) + Alg-6 feedback
    compaction: bit-exact vs the dense path when K >= #selected/shard."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import TMConfig, init_state, COALESCED, to_literals
        from repro.core.distributed import pod_train_step
        cfg = TMConfig(tm_type=COALESCED, features=24, clauses=32, classes=4,
                       T=8, s=3.0, prng_backend="counter")
        state = init_state(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        lits = to_literals(jnp.asarray((rng.random((16, 24)) < 0.4
                                        ).astype(np.int8)))
        y = jnp.asarray(rng.integers(0, 4, 16).astype(np.int32))
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        s_dense, st = pod_train_step(cfg, state, lits, y, mesh, seed=3)
        s_comp, _ = pod_train_step(cfg, state, lits, y, mesh, seed=3,
                                   compact_k=8)
        assert (np.asarray(s_dense.ta) == np.asarray(s_comp.ta)).all()
        assert (np.asarray(s_dense.weights) ==
                np.asarray(s_comp.weights)).all()
        assert not (np.asarray(s_dense.ta) == np.asarray(state.ta)).all()
        print("POD+ALG6 EXACT", int(st["selected"]))
    """)
