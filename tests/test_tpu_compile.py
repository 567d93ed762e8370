"""Ahead-of-time compile of the Pallas kernels and engine stages for v5e.

Interpret mode (every other test here) runs a kernel's Python body on the
CPU; it cannot see what Mosaic refuses: int32 MXU operands, dynamic
slices of loaded values, blocks off the (8, 128) tiling, VMEM over the
scoped limit.  These tests hand the real TPU compiler a described — not
attached — ``v5e:2x2`` topology and compile each kernel, and the engine's
jitted ``infer``/``train`` stages, at the paper's MNIST-CoTM and KWS-6
widths, and the Conv CoTM's TA-update kernel and epoch scan at its MNIST
geometry.  Nothing runs; a compile that passes is not a chip run.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and the test workers each import every
test file.  The persistent compilation cache is off around the compiles —
an entry compiled for a described chip cannot be read back without one.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.configs.tm_paper import TM_KWS6_COTM, TM_MNIST_COTM
from repro.core.prng import PRNG
from repro.kernels import ops

WIDTHS = {"mnist_cotm": TM_MNIST_COTM, "kws6_cotm": TM_KWS6_COTM}
BATCHES = (1, 32)


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip: a ``SingleDeviceSharding`` to give every
    argument shape.  Compiled Pallas (``REPRO_INTERPRET=0``), no
    persistent compile cache, fresh trace caches."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler in this installation
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mp.setenv("REPRO_INTERPRET", "0")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()            # no interpret-mode trace may be reused
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)
    cc.reset_cache()
    mp.undo()


def _geometry(width: str):
    tile = api.tile_for(api.TMSpec.from_config(WIDTHS[width]))
    L, R, H = tile.padded_dims()
    return L, R, H, tile.packed_words()


def _compile(fn, *args, **kw) -> str:
    """Compile for the described chip; the kernels must be Mosaic custom
    calls, not an interpreted body."""
    assert os.environ["REPRO_INTERPRET"] == "0"
    text = fn.lower(*args, **kw).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _kernel_cases(chip, name: str, B: int, width: str):
    L, R, H, W = _geometry(width)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32, u32, i8 = jnp.int32, jnp.uint32, jnp.int8
    scal = s((), u32)
    front = (s((B, L), i32), s((R, L), i32), s((H, R), i32), s((B,), i32),
             s((B,), i32), s((B, R), u32), s((B, R), u32), s((R,), i32),
             s((H,), i32), s((), i32), s((), i32))
    # the TA update sees both feedback rounds stacked: 2B literal rows
    ta = (s((R, L), jnp.uint8), s((2 * B, L), i8), s((2 * B, R), i32),
          s((2 * B, R), i32), s((2 * B, R), i32), s((L,), i32))
    return {
        "clause_eval": (ops.clause_eval_op, (s((B, L), i32), s((R, L), i32)),
                        {"eval_mode": True}),
        "class_sum": (ops.class_sum_op, (s((B, R), i32), s((H, R), i32)),
                      {}),
        "tm_infer": (ops.tm_infer_op, (s((B, L), i32), s((R, L), i32),
                                       s((H, R), i32)), {}),
        "packed_clause_eval": (ops.packed_clause_eval_op,
                               (s((B, W), u32), s((R, W), u32)),
                               {"eval_mode": True, "n_bits": L}),
        "packed_clause_eval_mxu": (ops.packed_clause_mxu_op,
                                   (s((B, W), u32), s((R, W), u32)),
                                   {"eval_mode": True, "n_bits": L}),
        "fused_step": (ops.fused_step_op, front, {}),
        "ta_update": (ops.ta_update_op, ta + (scal, scal),
                      {"emit_include": True, "prng": "lfsr"}),
        "ta_update_streamed": (ops.ta_update_op, ta + (scal, scal),
                               {"emit_include": True, "stream": True}),
        "ta_update_sparse": (ops.ta_update_compact_op,
                             ta + (s((R, W), u32), scal, scal),
                             {"prng": "lfsr"}),
    }[name]


KERNELS = ("clause_eval", "class_sum", "tm_infer", "packed_clause_eval",
           "packed_clause_eval_mxu", "fused_step", "ta_update",
           "ta_update_streamed", "ta_update_sparse")


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("B", BATCHES)
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(chip, kernel, B, width):
    fn, args, kw = _kernel_cases(chip, kernel, B, width)
    _compile(fn, *args, **kw)


@pytest.fixture(scope="module")
def mnist_engine(chip):
    """The paper's MNIST roster engine on compiled kernels, with one
    lowered CoTM program and PRNG as shapes on the described chip."""
    spec = api.TMSpec.from_config(TM_MNIST_COTM)
    engine = api.compile(api.tile_for(spec), backend="kernel")

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    prog = place(jax.eval_shape(lambda k: engine.lower(spec, k),
                                jax.random.PRNGKey(0)))
    prng = place(jax.eval_shape(lambda: PRNG.create(spec.tm_config(), 1)))
    return engine, prog, prng, place


@pytest.mark.parametrize("B,eval_path,train_path", [
    (1, ops.PATH_PACKED, ops.PATH_PACKED),
    (32, ops.PATH_PACKED_MXU, ops.PATH_FUSED)])
def test_engine_stages_compile_for_v5e(mnist_engine, B, eval_path,
                                       train_path):
    engine, prog, prng, place = mnist_engine
    lits = place(jax.ShapeDtypeStruct((B, engine.W), jnp.uint32))
    labels = place(jax.ShapeDtypeStruct((B,), jnp.int32))
    _compile(engine._infer, prog, lits)
    _compile(engine._train, prog, prng, lits, labels)
    paths = engine.cache_report()["path_per_stage"]
    assert paths["infer"] == eval_path
    assert paths["train"] == train_path
    assert paths["train_ta"] == ops.TA_COMPACT
    assert paths["train_prng"] == "lfsr-inkernel"


def test_epoch_scan_and_bank_compile_for_v5e(mnist_engine):
    engine, prog, prng, place = mnist_engine
    n, B, K = 128, 32, 2
    _compile(engine._fit_epoch, prog, prng,
             place(jax.ShapeDtypeStruct((n, engine.W), jnp.uint32)),
             place(jax.ShapeDtypeStruct((n,), jnp.int32)),
             place(jax.ShapeDtypeStruct((n // B, B), jnp.int32)))
    progs = place(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((K,) + x.shape, x.dtype), prog))
    lits = tuple(place(jax.ShapeDtypeStruct((B, engine.W), jnp.uint32))
                 for _ in range(K))
    _compile(engine._predict_bank_list, progs, lits)


def test_raw_bank_predict_compiles_for_v5e(mnist_engine):
    """The serving cycle's one launch: K raw feature-major [L/2, B] int8
    slots and their feature counts encoded, evaluated and decoded in one
    program."""
    engine, prog, _, place = mnist_engine
    B, K = 32, 2
    progs = place(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((K,) + x.shape, x.dtype), prog))
    _compile(engine._predict_bank_raw, progs,
             place(jax.ShapeDtypeStruct((K, engine.L // 2, B), jnp.int8)),
             place(jax.ShapeDtypeStruct((K,), jnp.int32)))


@pytest.fixture(scope="module")
def conv_engine(chip):
    """The Conv CoTM at its MNIST geometry (28x28 images, 10x10 window:
    361 patches, 272 literals padded to L=384; 2000 clauses, R=2048) on
    compiled kernels, with a lowered program and PRNG as shapes."""
    spec = api.TMSpec.conv(img_h=28, img_w=28, patch=10, classes=10,
                           clauses=2000, T=500, s=10.0, prng_backend="lfsr")
    engine = api.compile(api.tile_for(spec), backend="kernel")
    assert (engine.L, engine.R, engine.P, engine.W) == (384, 2048, 361, 12)

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    prog = place(jax.eval_shape(lambda k: engine.lower(spec, k),
                                jax.random.PRNGKey(0)))
    prng = place(jax.eval_shape(lambda: PRNG.create(spec.tm_config(), 1)))
    return engine, prog, prng, place


def test_conv_ta_update_kernel_compiles_for_v5e(chip):
    B, R, L, W = 32, 2048, 384, 12

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    fb = s((2 * B, R), jnp.int32)
    text = _compile(ops.ta_update_conv_op, s((R, L), jnp.uint8),
                    s((B, R, W), jnp.uint32), fb, fb, fb, s((L,), jnp.int32),
                    s((), jnp.uint32), s((), jnp.uint32), prng="lfsr")
    assert "%ta_update_conv" in text


def test_conv_epoch_scan_compiles_for_v5e(conv_engine):
    engine, prog, prng, place = conv_engine
    n, B = 64, 32
    text = _compile(
        engine._fit_epoch_conv, prog, prng,
        place(jax.ShapeDtypeStruct((n, engine.P, engine.W), jnp.uint32)),
        place(jax.ShapeDtypeStruct((n,), jnp.int32)),
        place(jax.ShapeDtypeStruct((n // B, B), jnp.int32)))
    assert "%ta_update_conv" in text
    paths = engine.cache_report()["path_per_stage"]
    assert paths["train_conv"] == ops.PATH_PACKED_MXU
    assert paths["train_conv_ta"] == "pallas"
    assert paths["train_conv_ta_prng"] == "lfsr-inkernel"
