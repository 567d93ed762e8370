"""Dynamic Tsetlin Machine engine (paper §IV — the core contribution).

The FPGA DTM synthesises ONE datapath (clause matrix ``x×y``, weight matrix
``m×n``, buffers sized to maxima) and then runs *any* TM model — different
feature counts, clause counts, class counts, and even TM type (Vanilla vs
CoTM) — purely by reprogramming iteration counts and remainder *masks*
(Fig 5, Fig 6), with no resynthesis.

TPU/JAX adaptation (DESIGN.md §2.4): the engine jit-compiles its step
functions ONCE for the padded tile grid; a model is a :class:`DTMProgram` —
pure *data* (padded TA/weight arrays + masks + traced hyper-parameters).
Switching model or TM type swaps the program, never the executable.  The
flexibility tests assert cache-size == 1 across model switches.

Unification trick (the paper's own, Eq 3): Vanilla TM is executed on the
CoTM datapath as a *block-diagonal frozen ±1 weight matrix* over a pool of
``classes × clauses/class`` rows; CoTM is a dense learned weight matrix over
a shared pool.  One engine, both algorithms.

Unified front-end (ISSUE 2): the engine also lowers the rest of the TM
family onto the same fixed stage executables —

* **Conv TM** — patch extraction is data prep (:meth:`encode`, staged in
  chunks); per-patch clause evaluation reuses the shared clause datapath
  over a ``[B·P, L]`` view; OR-over-patches / one-firing-patch feedback
  are the conv pre/post stages (``_infer_conv`` / ``_train_conv``,
  compiled once, patch axis padded to ``tile.max_patches`` and masked per
  program), the TA update the ``ta_update_conv`` kernel on the flat
  update's keyed streams.
* **Regression TM** — a *program flag* (``DTMProgram.regression``): the
  same ``_train`` executable computes the error-driven clause selection
  with the Alg-3 fixed-point margin compare and routes it into the shared
  TA-update kernel; weights are frozen unit votes.
* **TM head** — a CoTM program whose booleanizer lives in the spec; the
  engine sees ordinary literals.

``engine.lower(spec, key)`` (spec = :class:`repro.api.TMSpec`, duck-typed)
returns a :class:`DTMProgram`; swapping programs never recompiles any
stage (``cache_report()`` — every executable stays at one jit cache entry).

Bit-packed canonical datapath (ISSUE 3, the paper's Fig 4-6 frugality
story): literals and TA include-actions live as packed uint32 words —
``encode()`` emits ``[B, W]`` packed literals (W = ceil(L/32)), a program
carries a packed include bitplane ``inc [R, W]`` that the TA-update stage
maintains *incrementally* (no per-step host re-threshold of the [R, L] TA
matrix), and TA states are narrowed to uint8 (4 per 32-bit word).  Every
stage resolves its kernel path per call via ``kernels.select_path`` — the
packed VPU path for edge batches, the MXU/fused recasts for throughput
batches — and records the decision in ``cache_report()['path_per_stage']``
so dispatch == execution is observable.

Clause-skip execution (ISSUE 5, the paper's Alg 6 — its headline training
optimisation): the TA-update stage runs COMPACTED — the Alg-3 selection
masks give an active-clause-group bitmap, the active group indices are
prefix-sum-compacted into a fixed-capacity vector (static capacity
buckets, in-trace ``lax.switch``, dense fallback at full capacity) and
only those TA tiles / include-bitplane rows are gathered, updated, and
scattered back.  Bit-identical to the dense update, but wall-clock per
step FALLS as the model converges (the paper's ≈40 % training-time
saving, realised); ``REPRO_SKIP=0`` forces dense.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
# Fig 6d: remainder class sums pinned to min (shared with the kernels)
from repro.kernels.ref import NEG_INF_SUM as _NEG_INF_SUM
from repro.kernels.ref import pack_include as _pack_include
from repro.runtime import spans
from repro.runtime.spans import span
from .booleanize import pack_literals, unpack_literals
from .evaluate import epoch_record
from .prng import PRNG
from .types import COALESCED, TMConfig, TileConfig, VANILLA

# The engine train steps return exactly these int32 scalar stats; the
# epoch scan emits them per step and TMSession sums them host-side into
# the same plain ints the host fit_loop aggregates.
STAT_KEYS = ("selected", "active_groups", "total_groups", "correct",
             "abs_err")

# Conv input is staged in launches of this many rows (TMSession.stage)
STAGE_CHUNK_ROWS = 1024


def choose_patch(fired: jax.Array, u: jax.Array) -> jax.Array:
    """The patch each (datapoint, clause row) is updated against, as
    Granmo et al. choose it: uniformly among the patches on which the
    clause fired — the ``(u mod n)``-th of those ``n``, in patch order —
    and patch 0 where none fired (Type I and II then ignore the
    literals).

    ``fired`` int32 {0,1} [B, P, R], ``u`` uint32 [B, R] -> int32 [B, R]."""
    n = fired.sum(axis=1)                                      # [B, R]
    k = (u % jnp.maximum(n, 1).astype(jnp.uint32)).astype(jnp.int32)
    rank = jnp.cumsum(fired, axis=1) - 1                       # [B, P, R]
    return jnp.argmax((fired > 0) & (rank == k[:, None, :]),
                      axis=1).astype(jnp.int32)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DTMProgram:
    """Run-time model data for the DTM engine (a pytree — all dynamic).

    ta        uint8 [R, L]  padded TA states, narrowed 4-per-32-bit-word
                            (int32 fallback iff ta_bits > 8; mixing TA
                            dtypes across a roster retraces — keep ta_bits
                            uniform per engine for cache-size == 1)
    weights   int32 [H, R]  padded class weights (Vanilla: frozen block ±1)
    cl_mask   int32 [R]     1 = real clause row (Fig 6b)
    l_mask    int32 [L]     1 = real literal column (Fig 6a)
    h_mask    int32 [H]     1 = real class (Fig 6d)
    w_frozen  bool  []      True = Vanilla mode (weights never update)
    T         int32 []      clause-update threshold (runtime hyper-param)
    p_ta      uint32 []     precomputed ⌊2^rand_bits / s⌋ (§IV-B-c)
    boost     bool  []      boost-true-positive flag
    n_states  int32 []      2^ta_bits (TA clip bound; runtime-selectable)
    regression bool []      True = error-driven feedback (Regression TM)
    p_mask    int32 [P]     1 = real patch slot (conv programs; flat: [1,0..])
    inc       uint32 [R, W] packed include bitplane (W = ceil(L/32), bit l
                            of word w = include action of TA (w*32+l)) —
                            maintained incrementally by the train stages;
                            the paper's Fig 5a BRAM include words
    """

    ta: jax.Array
    weights: jax.Array
    cl_mask: jax.Array
    l_mask: jax.Array
    h_mask: jax.Array
    w_frozen: jax.Array
    T: jax.Array
    p_ta: jax.Array
    boost: jax.Array
    n_states: jax.Array
    w_clip: jax.Array
    regression: jax.Array
    p_mask: jax.Array
    inc: jax.Array

    def tree_flatten(self):
        # NOT dataclasses.astuple: that deep-copies every leaf on each
        # flatten, and flatten runs on every jit dispatch (hot path).
        return ((self.ta, self.weights, self.cl_mask, self.l_mask,
                 self.h_mask, self.w_frozen, self.T, self.p_ta, self.boost,
                 self.n_states, self.w_clip, self.regression, self.p_mask,
                 self.inc),
                None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class DTMEngine:
    """Compiled-once tiled TM executor (inference + training).

    ``backend`` selects the compute datapath, resolved ONCE at construction
    (so jit caches stay size-1 across model reprogramming):

    * ``"auto"``   — dispatcher decision: the Pallas kernels when they
      compile natively (TPU / ``REPRO_INTERPRET=0``), the bit-equivalent
      pure-jnp reference otherwise (interpret-mode Pallas is orders of
      magnitude slower than jnp on CPU — see kernels/ops.py).
    * ``"kernel"`` — force the Pallas path (interpret-mode on CPU; used by
      the parity tests).
    * ``"ref"``    — force the jnp reference path.

    Within the chosen backend, every stage additionally resolves its
    kernel path PER CALL from the traced batch size (``select_path``:
    packed VPU at edge batches, MXU/fused recasts above) and honours a
    ``REPRO_KERNEL_PATH`` force end-to-end — the train step runs the
    packed front half under ``packed_vpu`` and the unfused baseline under
    ``mxu``.  All paths are bit-identical; the executed path per stage is
    reported by ``cache_report()["path_per_stage"]``.
    """

    def __init__(self, tile: TileConfig, rand_bits: int = 16,
                 backend: str = "auto"):
        assert backend in ("auto", "kernel", "ref"), backend
        if backend == "auto":
            # any kernel path (fused or a forced REPRO_KERNEL_PATH variant)
            # keeps the Pallas backend; only an explicit "ref" override or
            # interpret mode (CPU) drops to the jnp reference.
            path = kops.select_path(None, batch=None, training=True)
            use_kernel = (path != kops.PATH_REF
                          and not kops.resolve_interpret())
            backend = "kernel" if use_kernel else "ref"
        self.backend = backend
        self._kb = "pallas" if backend == "kernel" else "ref"
        self.tile = tile
        self.rand_bits = rand_bits
        self.L, self.R, self.H = tile.padded_dims()
        self.P = tile.max_patches
        self.W = tile.packed_words()     # packed words per literal row
        # kernel path per stage, recorded at trace time (dispatch ==
        # execution observability; cache_report()["path_per_stage"])
        self._stage_paths: dict = {}
        self._infer = jax.jit(self._infer_impl)
        self._train = jax.jit(self._train_impl)
        # conv stage executables (only ever compiled if a conv program runs)
        self._infer_conv = jax.jit(self._infer_conv_impl)
        self._train_conv = jax.jit(self._train_conv_impl)
        # session epoch executables: a whole training epoch as ONE launch
        # (lax.scan over pre-staged batches; program + PRNG donated so the
        # device state is updated in place epoch over epoch)
        self._fit_epoch = jax.jit(self._fit_epoch_impl,
                                  donate_argnums=(0, 1))
        self._fit_epoch_conv = jax.jit(self._fit_epoch_conv_impl,
                                       donate_argnums=(0, 1))
        # program-bank executables: K stacked programs through one launch
        # (vmap over the leading program axis of every DTMProgram leaf)
        self._infer_bank = jax.jit(self._infer_bank_impl)
        self._infer_conv_bank = jax.jit(self._infer_conv_bank_impl)
        self._train_bank = jax.jit(self._train_bank_impl,
                                   donate_argnums=(0, 1))
        # list-taking variants: per-tenant literal arrays are stacked
        # INSIDE the trace (free at run time) — the serving flush path,
        # which would otherwise pay K eager expand_dims+concatenate ops
        self._infer_bank_list = jax.jit(self._infer_bank_list_impl)
        self._infer_conv_bank_list = jax.jit(
            self._infer_conv_bank_list_impl)
        self._predict_bank_list = jax.jit(self._predict_bank_list_impl)
        # raw-feature variant: a whole serving cycle's requests arrive as
        # ONE int8 block of K [B, L/2] slots and are encoded in-trace
        self._predict_bank_raw = jax.jit(self._predict_bank_raw_impl)
        # staging: encode one chunk of conv input into the staged [N, P, W]
        # literals in place (TMSession.stage)
        self._encode_into = jax.jit(self._encode_into_impl,
                                    static_argnums=(0,), donate_argnums=(1,))

    # ------------------------------------------------------------------ #
    # programming (paper §IV-D-a)                                         #
    # ------------------------------------------------------------------ #
    def program(self, cfg: TMConfig, key: jax.Array,
                ta: Optional[jax.Array] = None,
                weights: Optional[jax.Array] = None) -> DTMProgram:
        """Build run-time program data for a model config (pads + masks)."""
        L, R, H = self.L, self.R, self.H
        f, c, h = cfg.features, cfg.clauses, cfg.classes
        rows = cfg.total_clauses
        assert 2 * f <= L and rows <= R and h <= H, (
            f"model {(2*f, rows, h)} exceeds engine buffers {(L, R, H)}")
        assert cfg.T < (1 << 13)

        half = L // 2
        kt, kw = jax.random.split(key)
        if ta is None:
            j = cfg.include_threshold
            bern = jax.random.bernoulli(kt, 0.5, (rows, cfg.literals))
            ta = j - 1 + bern.astype(jnp.int32)
        # literal layout: [x .. pad | ~x .. pad]; split the 2f TA columns.
        ta_pad = jnp.zeros((R, L), jnp.int32)
        ta_pad = ta_pad.at[:rows, :f].set(ta[:, :f])
        ta_pad = ta_pad.at[:rows, half:half + f].set(ta[:, f:])

        w_pad = jnp.zeros((H, R), jnp.int32)
        if cfg.tm_type == COALESCED:
            if weights is None:
                bw = jax.random.bernoulli(kw, 0.5, (h, c))
                weights = jnp.where(bw, 1, -1).astype(jnp.int32)
            w_pad = w_pad.at[:h, :c].set(weights)
            frozen = False
        else:  # Vanilla: block-diagonal frozen ±1 (Eq 3)
            pol = jnp.where(jnp.arange(c) % 2 == 0, 1, -1).astype(jnp.int32)
            for cls in range(h):
                w_pad = w_pad.at[cls, cls * c:(cls + 1) * c].set(pol)
            frozen = True

        l_mask = jnp.zeros((L,), jnp.int32)
        l_mask = l_mask.at[:f].set(1).at[half:half + f].set(1)
        cl_mask = (jnp.arange(R) < rows).astype(jnp.int32)
        h_mask = (jnp.arange(H) < h).astype(jnp.int32)
        p_ta = jnp.uint32(int(round((1 << self.rand_bits) / cfg.s)))
        # canonical packed layout: TA narrowed to 4 states per 32-bit word,
        # include actions pre-packed 32 per word (training maintains them)
        ta_dtype = jnp.uint8 if cfg.n_states <= 256 else jnp.int32
        return DTMProgram(
            ta=ta_pad.astype(ta_dtype), weights=w_pad, cl_mask=cl_mask,
            l_mask=l_mask, h_mask=h_mask, w_frozen=jnp.asarray(frozen),
            T=jnp.asarray(cfg.T, jnp.int32), p_ta=p_ta,
            boost=jnp.asarray(cfg.boost_true_positive),
            n_states=jnp.asarray(cfg.n_states, jnp.int32),
            w_clip=jnp.asarray(cfg.weight_clip, jnp.int32),
            regression=jnp.asarray(False),
            p_mask=(jnp.arange(self.P) < 1).astype(jnp.int32),
            inc=_pack_include(ta_pad, cfg.n_states))

    def lower(self, spec, key: jax.Array,
              ta: Optional[jax.Array] = None,
              weights: Optional[jax.Array] = None) -> DTMProgram:
        """Lower a :class:`repro.api.TMSpec` (duck-typed: ``kind``,
        ``tm_config()``, ``n_patches``) to run-time program data.

        Every TM variant becomes the same uniform :class:`DTMProgram`
        pytree, so swapping any program for any other never retraces an
        engine executable."""
        cfg = spec.tm_config()
        n_p = int(getattr(spec, "n_patches", 1))
        assert n_p <= self.P, (
            f"spec needs {n_p} patch slots, engine has {self.P} "
            f"(TileConfig.max_patches)")
        # the spec's PRNG emits rand_bits-wide numbers; the engine's
        # fixed-point compares shift by ITS rand_bits — they must agree or
        # the Alg-3 select probabilities silently collapse to ~0 or ~1
        assert cfg.rand_bits == self.rand_bits, (
            f"spec rand_bits={cfg.rand_bits} != engine rand_bits="
            f"{self.rand_bits}")
        prog = self.program(cfg, key, ta=ta, weights=weights)
        if n_p != 1:
            prog = dataclasses.replace(
                prog, p_mask=(jnp.arange(self.P) < n_p).astype(jnp.int32))
        if getattr(spec, "kind", None) == "regression":
            # all clauses vote +1 through a frozen unit weight row; the
            # select path reads the clipped vote count, not class sums
            if weights is None:
                w = jnp.zeros((self.H, self.R), jnp.int32)
                w = w.at[0, :cfg.clauses].set(1)
                prog = dataclasses.replace(prog, weights=w)
            prog = dataclasses.replace(
                prog, w_frozen=jnp.asarray(True),
                regression=jnp.asarray(True))
        return prog

    def _layout(self, bool_feats: jax.Array) -> jax.Array:
        """[..., f] {0,1} -> engine literal layout [..., L] = [x pad|~x pad]."""
        f, half = bool_feats.shape[-1], self.L // 2
        x = bool_feats.astype(jnp.int8)
        z = jnp.zeros((*x.shape[:-1], half - f), jnp.int8)
        return jnp.concatenate([x, z, 1 - x, z], axis=-1)

    def pad_features(self, bool_x: jax.Array,
                     cfg: Optional[TMConfig] = None) -> jax.Array:
        """Host-side literal prep: [B, f] {0,1} -> PACKED [B, W] uint32
        ([x pad | ~x pad] layout, 32 literals per word)."""
        return pack_literals(self._layout(bool_x))

    def encode(self, spec, x: jax.Array) -> jax.Array:
        """Host-side data prep: raw model input -> packed engine literals.

        The canonical on-device representation is bit-packed (Fig 4-6):
        flat kinds (vanilla/coalesced/regression/head) -> ``[B, W]``
        uint32; conv -> ``[B, max_patches, W]`` (patch slots zero-padded;
        the per-program ``p_mask`` hides them from the datapath).
        W = ceil(L/32) — 8× fewer literal bytes than the int8 dense form
        the engine stages unpack on device only when an MXU path needs it."""
        feats = spec.to_bool(x)
        lits = self._layout(feats)
        if lits.ndim == 3:
            lits = jnp.pad(lits, ((0, 0), (0, self.P - lits.shape[1]),
                                  (0, 0)))
        return pack_literals(lits)

    def _encode_into_impl(self, spec, out: jax.Array, x: jax.Array,
                          start: jax.Array) -> jax.Array:
        """:meth:`encode` of rows ``x``, written into ``out`` at row
        ``start`` (one staged chunk)."""
        return jax.lax.dynamic_update_slice_in_dim(
            out, self.encode(spec, x), start, axis=0)

    def refresh_include(self, prog: DTMProgram) -> DTMProgram:
        """Rebuild the packed include bitplane from TA states.

        Only needed when TA states are replaced wholesale from outside the
        engine (checkpoint restore, manual surgery) — the train stages
        maintain ``inc`` incrementally themselves."""
        return dataclasses.replace(
            prog, inc=_pack_include(prog.ta, prog.n_states))

    # ------------------------------------------------------------------ #
    # shared datapath stages                                              #
    # ------------------------------------------------------------------ #
    def _eval_path(self, batch: int, stage: str, lanes: int = 1) -> str:
        """Resolve the clause-eval kernel path for this trace and record it
        (dispatch == execution: the recorded name is the branch taken).

        ``lanes`` is the program-bank width when the stage runs under a
        vmapped bank executable (per-program batch still governs the
        edge-regime choice — see ``select_path``).  The engine hands the
        dispatcher its padded (L, R, H) geometry, so the autotune plan
        cache participates (``REPRO_AUTOTUNE``; kernels/autotune.py)."""
        path = kops.select_path(None, batch=batch, training=False,
                                lanes=lanes, shape=(self.L, self.R, self.H))
        if path == kops.PATH_FUSED:
            # the fused kernel only exists for train steps; eval falls back
            # to its dense front half (documented in README)
            path = kops.PATH_REF if self.backend == "ref" else kops.PATH_MXU
        if self.backend == "ref" and path == kops.PATH_MXU:
            path = kops.PATH_REF    # jnp matmul recast IS the mxu oracle
        # mxu_popcount is NOT remapped on ref: packed_clause_mxu_ref IS the
        # bit-exact jnp recast of the bitplane-matmul kernel.
        self._stage_paths[stage] = path
        return path

    def _ta_prng(self, prng: PRNG, stage: str,
                 streamable: bool = True) -> tuple:
        """Resolve the TA-update random-stream family + provenance for
        this trace and record it (key ``<stage>_prng`` in
        ``path_per_stage``, e.g. ``lfsr-inkernel``).

        The FAMILY follows the model's ``prng_backend``: ``lfsr`` programs
        advance the paper-faithful Galois cluster INSIDE the TA kernels
        (per-TA lanes, ``lfsr_bits`` wide, master refresh per
        ``seed_refresh`` — Fig 8 in place); ``counter``/``threefry`` keep
        the TPU-native counter chains.  The PROVENANCE is
        ``REPRO_TA_PRNG``: ``inkernel`` (default, zero random-bits HBM
        traffic) or ``stream`` (the materialised [B, C, L] baseline,
        bit-identical — benchmarks/fig15_lfsr.py).  A stage with no
        streamed baseline (``streamable=False``: the conv update) always
        generates in place, and says so."""
        family = "lfsr" if prng.backend == "lfsr" else "counter"
        stream = (streamable
                  and kops.resolve_ta_prng() == kops.TA_PRNG_STREAM)
        self._stage_paths[stage + "_prng"] = (
            f"{family}-{'stream' if stream else 'inkernel'}")
        return family, stream

    def _clause_outputs(self, prog: DTMProgram, plits: jax.Array,
                        eval_mode: bool, stage: str,
                        lanes: int = 1) -> jax.Array:
        """Clause-matrix stage: PACKED [N, W] literals -> [N, R] int32.

        Routes per the dispatcher decision for this batch size: the packed
        bitwise path reads ``prog.inc`` directly (no threshold, no unpack);
        the MXU/ref recasts unpack literals + include on device."""
        path = self._eval_path(plits.shape[0], stage, lanes=lanes)
        if path == kops.PATH_PACKED:
            cl = kops.packed_clause_eval_op(plits, prog.inc,
                                            eval_mode=eval_mode,
                                            n_bits=self.L, backend=self._kb)
        elif path == kops.PATH_PACKED_MXU:
            # popcount-as-matmul: same packed operands as packed_vpu, int8
            # bitplane dot products on the systolic array (throughput
            # batches; the autotune seed plan picks this over the dense
            # mxu recast — identical compute, ~8x fewer literal bytes).
            cl = kops.packed_clause_mxu_op(plits, prog.inc,
                                           eval_mode=eval_mode,
                                           n_bits=self.L, backend=self._kb)
        elif path == kops.PATH_MXU:
            lits = unpack_literals(plits, self.L)
            include = unpack_literals(prog.inc, self.L)
            # unfused MXU pair — the dispatcher's "mxu" eval path.  Padded
            # TA columns are zero, so include already honours l_mask.
            cl = kops.clause_eval_op(lits, include, eval_mode=eval_mode)
        else:   # ref: the jnp violation-matmul recast
            lits = unpack_literals(plits, self.L)
            include = unpack_literals(prog.inc, self.L).astype(jnp.int32)
            viol = jax.lax.dot_general(
                (1 - lits.astype(jnp.int32)) * prog.l_mask[None, :], include,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)                      # [N,R]
            cl = (viol == 0)
            if eval_mode:
                nonempty = (include * prog.l_mask[None, :]).max(axis=1)
                cl = cl & (nonempty[None, :] == 1)
            cl = cl.astype(jnp.int32)
        return cl * prog.cl_mask[None, :]

    def _class_sums_raw(self, prog: DTMProgram, cl: jax.Array) -> jax.Array:
        """Weight-matrix stage, UNPINNED: [B, R] clauses -> raw [B, H] sums.

        Split out of :meth:`_class_sums` so clause-sharded execution can
        ``psum`` the per-shard partial sums over the mesh axis FIRST and
        pin the padded classes afterwards — pinning partials before the
        all-reduce would sum the NEG_INF sentinels."""
        if self.backend == "kernel":
            return kops.class_sum_op(cl, prog.weights)
        return jax.lax.dot_general(
            cl, prog.weights,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)                          # [B,H]

    def _pin_class_sums(self, prog: DTMProgram, sums: jax.Array) -> jax.Array:
        """Fig 6d remainder pinning: padded class columns -> NEG_INF."""
        return jnp.where(prog.h_mask[None, :] == 1, sums, _NEG_INF_SUM)

    def _class_sums(self, prog: DTMProgram, cl: jax.Array) -> jax.Array:
        """Weight-matrix stage: [B, R] clauses -> pinned [B, H] sums."""
        return self._pin_class_sums(prog, self._class_sums_raw(prog, cl))

    # ------------------------------------------------------------------ #
    # inference (Eq 1 + Eq 2/3 on the padded grid)                        #
    # ------------------------------------------------------------------ #
    def _infer_impl(self, prog: DTMProgram, lits: jax.Array,
                    lanes: int = 1, stage: str = "infer"):
        cl = self._clause_outputs(prog, lits, eval_mode=True, stage=stage,
                                  lanes=lanes)
        return self._class_sums(prog, cl), cl

    def _infer_conv_impl(self, prog: DTMProgram, plits: jax.Array,
                         lanes: int = 1, stage: str = "infer_conv"):
        """Conv pre/post stages around the shared clause datapath:
        per-patch clause eval on the [B·P, W] view, OR over real patches,
        then the ordinary weight-matrix stage."""
        B, P, W = plits.shape
        cl_p = self._clause_outputs(prog, plits.reshape(B * P, W),
                                    eval_mode=True, stage=stage,
                                    lanes=lanes)
        cl_p = cl_p.reshape(B, P, self.R) * prog.p_mask[None, :, None]
        cl = cl_p.max(axis=1)                                          # [B,R]
        return self._class_sums(prog, cl), cl

    def infer(self, prog: DTMProgram, lits: jax.Array):
        """lits [B, W] packed (from pad_features/encode) ->
        (class_sums [B,H], clause [B,R])."""
        return self._infer(prog, lits)

    def infer_conv(self, prog: DTMProgram, plits: jax.Array):
        """plits [B, P, W] packed (from encode) ->
        (class_sums [B,H], clause [B,R])."""
        return self._infer_conv(prog, plits)

    def predict(self, prog: DTMProgram, lits: jax.Array) -> jax.Array:
        sums, _ = self.infer(prog, lits)
        return jnp.argmax(sums, axis=-1)

    # ------------------------------------------------------------------ #
    # training (Alg 3-6 on the padded grid, batched-delta mode)           #
    # ------------------------------------------------------------------ #
    def _train_front(self, prog: DTMProgram, plits: jax.Array,
                     lits: jax.Array, cls_lab, neg, sel_rand,
                     lanes: int = 1, stage: str = "train"):
        """Training-step front half (clause eval → class sums → Alg-3
        selection, both rounds) through the dispatcher-selected path:

        * ``packed_vpu`` (edge batches or forced) — packed clause eval
          straight off ``prog.inc``, shared class-sum/select stages;
        * ``fused`` — ONE kernel launch, the ``[B, R]`` clause matrix
          never round-trips through HBM between stages;
        * ``mxu`` (forced) — the unfused two-launch baseline;
        * ``ref`` — the bit-equivalent jnp oracle.

        All four are bit-identical; the executed path is recorded under
        ``path_per_stage`` at trace time."""
        wf = prog.w_frozen.astype(jnp.int32)
        path = kops.select_path(None, batch=plits.shape[0], training=True,
                                lanes=lanes, shape=(self.L, self.R, self.H))
        if (self.backend == "ref"
                and path not in (kops.PATH_PACKED, kops.PATH_PACKED_MXU)):
            path = kops.PATH_REF
        self._stage_paths[stage] = path
        if path in (kops.PATH_PACKED, kops.PATH_PACKED_MXU):
            return kops.packed_step_op(
                plits, prog.inc, prog.weights, cls_lab, neg, sel_rand[0],
                sel_rand[1], prog.cl_mask, prog.h_mask, prog.T, wf,
                rand_bits=self.rand_bits, backend=self._kb, n_bits=self.L,
                mxu=(path == kops.PATH_PACKED_MXU))
        include = unpack_literals(prog.inc, self.L)                # [R,L]
        if path == kops.PATH_MXU:
            return kops.unfused_step_op(
                lits, include, prog.weights, cls_lab, neg, sel_rand[0],
                sel_rand[1], prog.cl_mask, prog.h_mask, prog.T, wf,
                rand_bits=self.rand_bits)
        return kops.fused_step_op(
            lits, include, prog.weights, cls_lab, neg, sel_rand[0],
            sel_rand[1], prog.cl_mask, prog.h_mask, prog.T, wf,
            rand_bits=self.rand_bits,
            backend="ref" if path == kops.PATH_REF else self._kb)

    def _train_impl(self, prog: DTMProgram, prng: PRNG, plits: jax.Array,
                    labels: jax.Array, lanes: int = 1,
                    stage: str = "train"):
        """One batched train step through the fused dispatcher path.

        Front half (clause eval → class sums → Alg-3 feedback selection
        for the target and negated rounds) routes per batch size — see
        :meth:`_train_front`.  Back half is the in-kernel-PRNG TA-update
        kernel over both feedback rounds (which also emits the UPDATED
        packed include bitplane — ``prog.inc`` is maintained incrementally,
        never re-thresholded from TA by a consumer), plus jnp weight/stat
        reductions.  ``backend="ref"`` runs the bit-equivalent jnp oracles
        through the same structure.
        """
        B = plits.shape[0]
        # dense literals for the TA-update stage (unpacked ON DEVICE from
        # the canonical packed form; the packed array is what moved)
        lits = unpack_literals(plits, self.L)                          # [B,L]
        n_cls = prog.h_mask.sum()
        reg = prog.regression                                          # bool []

        # batched random draws (one stream position per datapoint)
        prng, c_rand = prng.bits((B,))
        prng, sel_rand = prng.bits((2, B, self.R))
        prng, seed_bits = prng.bits((2,))
        # seed_bits are rand_bits wide — shift by rand_bits (not a fixed 16)
        # so the composed seed keeps 2*rand_bits of entropy
        ta_seed = (seed_bits[0] << jnp.uint32(self.rand_bits)) | seed_bits[1]

        # Regression programs carry the integer vote target in `labels`
        # (may exceed the class count) — the class-indexed machinery below
        # runs on a pinned in-range label so its discarded outputs stay
        # deterministic on every backend.
        cls_lab = jnp.where(reg, 0, labels)
        # negated class among the *valid* classes
        rn = (c_rand % (jnp.maximum(n_cls - 1, 1).astype(jnp.uint32))
              ).astype(jnp.int32)
        neg = jnp.where(rn < cls_lab, rn, rn + 1)                      # [B]

        cl, sums_m, sel_lab, sel_neg = self._train_front(
            prog, plits, lits, cls_lab, neg, sel_rand, lanes=lanes,
            stage=stage)
        # batch accuracy is meaningless against a regression vote target
        correct = jnp.where(reg, 0, (jnp.argmax(sums_m, -1) == labels).sum())

        # Regression TM (program flag): clipped clause-vote count vs the
        # target, P(update) = |err|/2T via the same Alg-3 fixed-point
        # compare; under-prediction grows clauses (Type I), over-prediction
        # prunes them (Type II).  Shares the TA-update kernel below.
        votes = jnp.clip(cl.sum(axis=-1), 0, prog.T)                   # [B]
        err = labels - votes                                           # [B]
        sel_reg = ((sel_rand[0].astype(jnp.int32) * (2 * prog.T))
                   < (jnp.abs(err)[:, None] << self.rand_bits))
        sel_reg = sel_reg.astype(jnp.int32) * prog.cl_mask[None, :]
        abs_err = jnp.abs(err).sum()

        # Type I / Type II split per round (sign of the class's weight row;
        # regression programs split by the sign of the vote error instead)
        w_lab = jnp.take(prog.weights, cls_lab, axis=0)                # [B,R]
        w_neg = jnp.take(prog.weights, neg, axis=0)
        zero = jnp.zeros_like(sel_lab)
        t1_lab = jnp.where(reg, sel_reg * (err > 0)[:, None],
                           sel_lab * (w_lab >= 0))
        t2_lab = jnp.where(reg, sel_reg * (err < 0)[:, None],
                           sel_lab * (w_lab < 0))
        t1_neg = jnp.where(reg, zero, sel_neg * (w_neg < 0))
        t2_neg = jnp.where(reg, zero, sel_neg * (w_neg >= 0))
        sel_lab = jnp.where(reg, sel_reg, sel_lab)
        sel_neg = jnp.where(reg, zero, sel_neg)

        # TA update over both rounds flattened into the batch axis; randoms
        # are generated where they are consumed (counter stream keyed on
        # ta_seed) — no [B, R, L] random tensor ever exists.
        lit2 = jnp.concatenate([lits, lits], axis=0)                   # [2B,L]
        cl2 = jnp.concatenate([cl, cl], axis=0)
        t1 = jnp.concatenate([t1_lab, t1_neg], axis=0)
        t2 = jnp.concatenate([t2_lab, t2_neg], axis=0)
        # Clause-skip execution (Alg 6): clause rows with zero feedback
        # across both rounds have a provably zero TA delta, so the
        # compacted datapath gathers only active clause groups (in-trace
        # capacity-bucket switch — the whole epoch scan stays ONE launch)
        # and maintains only their include-bitplane rows.  Bit-identical
        # to the dense update; dense is forced by REPRO_SKIP=0 or for
        # vmapped program banks (see kernels.select_ta_path).
        ta_path = kops.select_ta_path(lanes, shape=(self.L, self.R, self.H))
        self._stage_paths[stage + "_ta"] = ta_path
        ta_prng, stream = self._ta_prng(prng, stage)
        if ta_path == kops.TA_COMPACT:
            # granularity: the Pallas path gathers whole (yt, xt) VMEM
            # tiles (group is ignored); the jnp ref path has no tiling
            # constraint, so it compacts at ROW granularity — selected
            # clauses are scattered across the pool, and row-level
            # compaction skips every unselected row, not just fully-idle
            # groups
            new_ta, new_inc = kops.ta_update_compact_op(
                prog.ta, lit2, cl2, t1, t2, prog.l_mask, prog.inc,
                seed=ta_seed, p_ta=prog.p_ta, rand_bits=self.rand_bits,
                boost=prog.boost, n_states=prog.n_states, backend=self._kb,
                group=1, prng=ta_prng, lfsr_bits=prng.lfsr_bits,
                seed_refresh=prng.seed_refresh)
        else:
            new_ta, new_inc = kops.ta_update_op(
                prog.ta, lit2, cl2, t1, t2, prog.l_mask, seed=ta_seed,
                p_ta=prog.p_ta, rand_bits=self.rand_bits, boost=prog.boost,
                n_states=prog.n_states, backend=self._kb, emit_include=True,
                prng=ta_prng, lfsr_bits=prng.lfsr_bits,
                seed_refresh=prng.seed_refresh, stream=stream)

        new_w, stats = self._weights_and_stats(
            prog, cl, sel_lab, sel_neg, cls_lab, neg, correct, abs_err)
        new_prog = dataclasses.replace(
            prog, ta=new_ta.astype(prog.ta.dtype), weights=new_w,
            inc=new_inc)
        return new_prog, prng, stats

    def _weights_and_stats(self, prog: DTMProgram, cl, sel_lab, sel_neg,
                           lab, neg, correct, abs_err):
        """Shared training post-stage: Alg-4 weight nudges (one-hot
        scatter-add as two int32 matmuls) + Alg-6 group-skip accounting on
        the engine's y-tile granularity."""
        hr = jnp.arange(self.H, dtype=jnp.int32)
        lab_oh = (lab[:, None] == hr[None, :]).astype(jnp.int32)       # [B,H]
        neg_oh = (neg[:, None] == hr[None, :]).astype(jnp.int32)
        contract_b = (((0,), (0,)), ((), ()))
        d_w = (jax.lax.dot_general(lab_oh, sel_lab * cl, contract_b,
                                   preferred_element_type=jnp.int32)
               - jax.lax.dot_general(neg_oh, sel_neg * cl, contract_b,
                                     preferred_element_type=jnp.int32))
        new_w = jnp.where(prog.w_frozen, prog.weights,
                          jnp.clip(prog.weights + d_w, -prog.w_clip,
                                   prog.w_clip))

        d_sel = (sel_lab + sel_neg).sum(axis=0)                        # [R]
        g = (d_sel > 0).astype(jnp.int32).reshape(-1, self.tile.y).max(-1)
        gmask = prog.cl_mask.reshape(-1, self.tile.y).max(-1)
        stats = {"selected": d_sel.sum(), "active_groups": (g * gmask).sum(),
                 "total_groups": gmask.sum(), "correct": correct,
                 "abs_err": abs_err}
        return new_w, stats

    def train_step(self, prog: DTMProgram, prng: PRNG, lits: jax.Array,
                   labels: jax.Array):
        """lits [B, W] packed (from pad_features/encode) train step."""
        return self._train(prog, prng, lits, labels)

    # ------------------------------------------------------------------ #
    # conv training (Granmo et al. conv feedback around the shared stages)#
    # ------------------------------------------------------------------ #
    def _train_conv_impl(self, prog: DTMProgram, prng: PRNG,
                         plits: jax.Array, labels: jax.Array):
        """One batched Conv-TM train step (:meth:`_conv_step` on one
        device)."""
        return self._conv_step(prog, prng, plits, labels, "train_conv")

    def _conv_step(self, prog: DTMProgram, prng: PRNG, plits: jax.Array,
                   labels: jax.Array, stage: str,
                   axis: Optional[str] = None):
        """One batched Conv-TM train step, on one device or, given the
        mesh ``axis``, as one clause shard's body (``launch/pod.py``).

        Draws: the flat step's (negated class [B], selection [2, B, R],
        a two-word TA seed), then one patch-choice number per
        (datapoint, clause row) [B, R] — at B=32, R=2048 about 26 cycles
        of an 8192-lane LFSR cluster; no random tensor of patch or
        literal width exists.  Pre-stage: per-patch clause eval on the
        shared clause datapath ([B·P, W] packed view).  Post-stages: OR
        over real patches, the ordinary weight-matrix + Alg-3 selection
        machinery, then Type I/II feedback against ONE firing patch per
        (datapoint, clause) (:func:`choose_patch`).  The chosen patches'
        packed literal words [B, R, W] are gathered from the staged
        patches, and the ``ta_update_conv`` kernel updates every TA cell
        from the flat kernel's keyed in-place streams and emits the
        updated include bitplane.

        Sharded: every shard draws the same full-width numbers (the PRNG
        is replicated) and slices its row window, class sums are psum'd
        raw and pinned after, and the TA streams are keyed at GLOBAL rows
        (``row0``) — the shards together are bit-identical to the
        single-device step, with zero cross-shard TA traffic."""
        B, P, W = plits.shape
        row0, r_loc = 0, self.R
        if axis is not None:
            row0, r_loc, shards = self._shard_window(prog, axis)
            self._stage_paths[stage + "_shard"] = f"{axis}:{shards}"
        n_cls = prog.h_mask.sum()

        prng, c_rand = prng.bits((B,))
        prng, sel_rand = prng.bits((2, B, self.R))
        prng, seed_bits = prng.bits((2,))
        prng, patch_rand = prng.bits((B, self.R))
        ta_seed = (seed_bits[0] << jnp.uint32(self.rand_bits)) | seed_bits[1]
        if axis is not None:
            sel_rand = jax.lax.dynamic_slice_in_dim(sel_rand, row0, r_loc,
                                                    axis=2)
            patch_rand = jax.lax.dynamic_slice_in_dim(patch_rand, row0,
                                                      r_loc, axis=1)

        rn = (c_rand % (jnp.maximum(n_cls - 1, 1).astype(jnp.uint32))
              ).astype(jnp.int32)
        neg = jnp.where(rn < labels, rn, rn + 1)                       # [B]

        cl_p = self._clause_outputs(prog, plits.reshape(B * P, W),
                                    eval_mode=False, stage=stage)
        cl_p = cl_p.reshape(B, P, r_loc) * prog.p_mask[None, :, None]
        cl = cl_p.max(axis=1)                                  # [B, r_loc]
        sums = self._class_sums_raw(prog, cl)
        if axis is not None:
            sums = jax.lax.psum(sums, axis)
        sums = self._pin_class_sums(prog, sums)
        correct = (jnp.argmax(sums, -1) == labels).sum()

        # Alg-3 selection (same fixed-point compare as the fused kernel)
        wf = prog.w_frozen.astype(jnp.int32)
        sel_lab = kops.round_select_op(
            sums, labels, 1, sel_rand[0], prog.weights, prog.cl_mask,
            prog.T, wf, rand_bits=self.rand_bits)
        sel_neg = kops.round_select_op(
            sums, neg, 0, sel_rand[1], prog.weights, prog.cl_mask,
            prog.T, wf, rand_bits=self.rand_bits)

        # Type I/II against the chosen patch's literals (Alg 5, gated by
        # the OR-level clause output as Granmo et al. gate it), both
        # rounds stacked as the flat update stacks them
        patch = choose_patch(cl_p, patch_rand)                 # [B, r_loc]
        words = jnp.take_along_axis(plits, patch[:, :, None], axis=1)
        w_lab = jnp.take(prog.weights, labels, axis=0)         # [B, r_loc]
        w_neg = jnp.take(prog.weights, neg, axis=0)
        t1 = jnp.concatenate([sel_lab * (w_lab >= 0), sel_neg * (w_neg < 0)])
        t2 = jnp.concatenate([sel_lab * (w_lab < 0), sel_neg * (w_neg >= 0)])
        self._stage_paths[stage + "_ta"] = self._kb
        ta_prng, _ = self._ta_prng(prng, stage + "_ta", streamable=False)
        new_ta, new_inc = kops.ta_update_conv_op(
            prog.ta, words, jnp.concatenate([cl, cl]), t1, t2, prog.l_mask,
            seed=ta_seed, p_ta=prog.p_ta, rand_bits=self.rand_bits,
            boost=prog.boost, n_states=prog.n_states, backend=self._kb,
            row0=row0, prng=ta_prng, lfsr_bits=prng.lfsr_bits,
            seed_refresh=prng.seed_refresh)

        zero = jnp.asarray(0, jnp.int32)
        if axis is None:
            new_w, stats = self._weights_and_stats(
                prog, cl, sel_lab, sel_neg, labels, neg, correct, zero)
        else:
            new_w, stats = self._weights_and_stats_sharded(
                prog, cl, sel_lab, sel_neg, labels, neg, correct, zero, axis)
        new_prog = dataclasses.replace(
            prog, ta=new_ta.astype(prog.ta.dtype), weights=new_w,
            inc=new_inc)
        return new_prog, prng, stats

    def train_conv(self, prog: DTMProgram, prng: PRNG, plits: jax.Array,
                   labels: jax.Array):
        """plits [B, P, W] packed (from encode) conv train step."""
        return self._train_conv(prog, prng, plits, labels)

    # ------------------------------------------------------------------ #
    # clause-sharded stage bodies (run INSIDE shard_map — launch/pod.py)  #
    # ------------------------------------------------------------------ #
    # One over-VMEM machine spread over a ``clauses`` mesh axis: each
    # shard holds a contiguous row window of the clause-indexed program
    # leaves (ta [r_loc, L], inc [r_loc, W], cl_mask [r_loc], weight
    # COLUMNS [H, r_loc]); everything else is replicated.  Bit-identity
    # with the single-device trace rests on three invariants:
    #   1. every shard draws the same FULL-width PRNG streams as a
    #      single-device step (the PRNG is replicated) and slices its row
    #      window — no stream position ever moves;
    #   2. class sums are psum'd RAW and pinned after (Alg-3 selection is
    #      column-independent given the global sums, so selection runs
    #      shard-local on the sliced randoms/weights);
    #   3. the TA-update stage keys its in-kernel streams at GLOBAL row
    #      numbers via ``row0`` (kernels.ta_update) — zero cross-shard TA
    #      traffic, matching the FPGA's per-slice BRAM locality (Fig 5).

    def _shard_window(self, prog: DTMProgram, axis: str):
        """(row0, r_loc, shards) of this shard's clause-row window."""
        r_loc = prog.ta.shape[0]
        shards = self.R // r_loc
        row0 = jax.lax.axis_index(axis) * r_loc
        return row0, r_loc, shards

    def _infer_sharded_impl(self, prog: DTMProgram, plits: jax.Array,
                            axis: str = "clauses",
                            stage: str = "infer_sharded"):
        """Clause-sharded inference body: local clause eval, one [B, H]
        psum, Fig-6d pinning after the all-reduce.  Returns (global sums
        [B, H] replicated, LOCAL clause columns [B, r_loc])."""
        _, _, shards = self._shard_window(prog, axis)
        cl = self._clause_outputs(prog, plits, eval_mode=True, stage=stage)
        sums = jax.lax.psum(self._class_sums_raw(prog, cl), axis)
        self._stage_paths[stage + "_shard"] = f"{axis}:{shards}"
        return self._pin_class_sums(prog, sums), cl

    def _infer_conv_sharded_impl(self, prog: DTMProgram, plits: jax.Array,
                                 axis: str = "clauses",
                                 stage: str = "infer_conv_sharded"):
        B, P, W = plits.shape
        _, r_loc, shards = self._shard_window(prog, axis)
        cl_p = self._clause_outputs(prog, plits.reshape(B * P, W),
                                    eval_mode=True, stage=stage)
        cl_p = cl_p.reshape(B, P, r_loc) * prog.p_mask[None, :, None]
        cl = cl_p.max(axis=1)                                  # [B, r_loc]
        sums = jax.lax.psum(self._class_sums_raw(prog, cl), axis)
        self._stage_paths[stage + "_shard"] = f"{axis}:{shards}"
        return self._pin_class_sums(prog, sums), cl

    def _train_sharded_impl(self, prog: DTMProgram, prng: PRNG,
                            plits: jax.Array, labels: jax.Array,
                            axis: str = "clauses",
                            stage: str = "train_sharded"):
        """Clause-sharded train-step body (flat programs).

        Mirrors :meth:`_train_impl` stage for stage; the only collectives
        are the [B, H] class-sum psum, the [B] vote psum (regression
        programs) and the tiny stat gathers — TA/include/weight updates
        stay entirely shard-local."""
        B = plits.shape[0]
        row0, r_loc, shards = self._shard_window(prog, axis)
        lits = unpack_literals(plits, self.L)                      # [B, L]
        n_cls = prog.h_mask.sum()
        reg = prog.regression

        # full-width draws, identical on every shard (invariant 1)
        prng, c_rand = prng.bits((B,))
        prng, sel_rand_full = prng.bits((2, B, self.R))
        prng, seed_bits = prng.bits((2,))
        ta_seed = ((seed_bits[0] << jnp.uint32(self.rand_bits))
                   | seed_bits[1])
        sel_rand = jax.lax.dynamic_slice_in_dim(sel_rand_full, row0,
                                                r_loc, axis=2)

        cls_lab = jnp.where(reg, 0, labels)
        rn = (c_rand % (jnp.maximum(n_cls - 1, 1).astype(jnp.uint32))
              ).astype(jnp.int32)
        neg = jnp.where(rn < cls_lab, rn, rn + 1)                  # [B]

        # front half: local clause eval -> psum raw sums -> pin -> local
        # Alg-3 selection on the sliced randoms/weight columns
        cl = self._clause_outputs(prog, plits, eval_mode=False, stage=stage)
        sums_m = self._pin_class_sums(
            prog, jax.lax.psum(self._class_sums_raw(prog, cl), axis))
        wf = prog.w_frozen.astype(jnp.int32)
        sel_lab = kops.round_select_op(
            sums_m, cls_lab, 1, sel_rand[0], prog.weights, prog.cl_mask,
            prog.T, wf, rand_bits=self.rand_bits)
        sel_neg = kops.round_select_op(
            sums_m, neg, 0, sel_rand[1], prog.weights, prog.cl_mask,
            prog.T, wf, rand_bits=self.rand_bits)
        correct = jnp.where(reg, 0,
                            (jnp.argmax(sums_m, -1) == labels).sum())

        # regression: global clipped vote count needs one [B] psum
        votes = jnp.clip(jax.lax.psum(cl.sum(axis=-1), axis), 0, prog.T)
        err = labels - votes
        sel_reg = ((sel_rand[0].astype(jnp.int32) * (2 * prog.T))
                   < (jnp.abs(err)[:, None] << self.rand_bits))
        sel_reg = sel_reg.astype(jnp.int32) * prog.cl_mask[None, :]
        abs_err = jnp.abs(err).sum()

        w_lab = jnp.take(prog.weights, cls_lab, axis=0)        # [B, r_loc]
        w_neg = jnp.take(prog.weights, neg, axis=0)
        zero = jnp.zeros_like(sel_lab)
        t1_lab = jnp.where(reg, sel_reg * (err > 0)[:, None],
                           sel_lab * (w_lab >= 0))
        t2_lab = jnp.where(reg, sel_reg * (err < 0)[:, None],
                           sel_lab * (w_lab < 0))
        t1_neg = jnp.where(reg, zero, sel_neg * (w_neg < 0))
        t2_neg = jnp.where(reg, zero, sel_neg * (w_neg >= 0))
        sel_lab = jnp.where(reg, sel_reg, sel_lab)
        sel_neg = jnp.where(reg, zero, sel_neg)

        # local TA update with GLOBAL stream keys (invariant 3)
        lit2 = jnp.concatenate([lits, lits], axis=0)
        cl2 = jnp.concatenate([cl, cl], axis=0)
        t1 = jnp.concatenate([t1_lab, t1_neg], axis=0)
        t2 = jnp.concatenate([t2_lab, t2_neg], axis=0)
        ta_path = kops.select_ta_path(1, shape=(self.L, self.R, self.H))
        self._stage_paths[stage + "_ta"] = ta_path
        self._stage_paths[stage + "_shard"] = f"{axis}:{shards}"
        ta_prng, stream = self._ta_prng(prng, stage)
        row0_u = row0.astype(jnp.uint32)
        if ta_path == kops.TA_COMPACT:
            new_ta, new_inc = kops.ta_update_compact_op(
                prog.ta, lit2, cl2, t1, t2, prog.l_mask, prog.inc,
                seed=ta_seed, p_ta=prog.p_ta, rand_bits=self.rand_bits,
                boost=prog.boost, n_states=prog.n_states,
                backend=self._kb, group=1, row0=row0_u, prng=ta_prng,
                lfsr_bits=prng.lfsr_bits, seed_refresh=prng.seed_refresh)
        else:
            new_ta, new_inc = kops.ta_update_op(
                prog.ta, lit2, cl2, t1, t2, prog.l_mask, seed=ta_seed,
                p_ta=prog.p_ta, rand_bits=self.rand_bits, boost=prog.boost,
                n_states=prog.n_states, backend=self._kb,
                emit_include=True, row0=row0_u, prng=ta_prng,
                lfsr_bits=prng.lfsr_bits, seed_refresh=prng.seed_refresh,
                stream=stream)

        new_w, stats = self._weights_and_stats_sharded(
            prog, cl, sel_lab, sel_neg, cls_lab, neg, correct, abs_err,
            axis)
        new_prog = dataclasses.replace(
            prog, ta=new_ta.astype(prog.ta.dtype), weights=new_w,
            inc=new_inc)
        return new_prog, prng, stats

    def _train_conv_sharded_impl(self, prog: DTMProgram, prng: PRNG,
                                 plits: jax.Array, labels: jax.Array,
                                 axis: str = "clauses",
                                 stage: str = "train_conv_sharded"):
        """Clause-sharded Conv-TM train-step body: :meth:`_conv_step`
        over this shard's row window."""
        return self._conv_step(prog, prng, plits, labels, stage, axis=axis)

    def _weights_and_stats_sharded(self, prog: DTMProgram, cl, sel_lab,
                                   sel_neg, lab, neg, correct, abs_err,
                                   axis: str):
        """Sharded mirror of :meth:`_weights_and_stats`: the Alg-4 weight
        nudges act on this shard's weight COLUMNS (local, exact); the
        Alg-6 group-skip accounting needs the GLOBAL [R] selection bitmap
        (r_loc may be smaller than a y-tile, so group occupancy cannot be
        derived per shard) — one tiny [r_loc] all_gather per step."""
        hr = jnp.arange(self.H, dtype=jnp.int32)
        lab_oh = (lab[:, None] == hr[None, :]).astype(jnp.int32)   # [B,H]
        neg_oh = (neg[:, None] == hr[None, :]).astype(jnp.int32)
        contract_b = (((0,), (0,)), ((), ()))
        d_w = (jax.lax.dot_general(lab_oh, sel_lab * cl, contract_b,
                                   preferred_element_type=jnp.int32)
               - jax.lax.dot_general(neg_oh, sel_neg * cl, contract_b,
                                     preferred_element_type=jnp.int32))
        new_w = jnp.where(prog.w_frozen, prog.weights,
                          jnp.clip(prog.weights + d_w, -prog.w_clip,
                                   prog.w_clip))

        d_sel = (sel_lab + sel_neg).sum(axis=0)                # [r_loc]
        d_sel_all = jax.lax.all_gather(d_sel, axis).reshape(-1)    # [R]
        clm_all = jax.lax.all_gather(prog.cl_mask, axis).reshape(-1)
        g = (d_sel_all > 0).astype(jnp.int32).reshape(
            -1, self.tile.y).max(-1)
        gmask = clm_all.reshape(-1, self.tile.y).max(-1)
        stats = {"selected": d_sel_all.sum(),
                 "active_groups": (g * gmask).sum(),
                 "total_groups": gmask.sum(), "correct": correct,
                 "abs_err": abs_err}
        return new_w, stats

    # ------------------------------------------------------------------ #
    # session epoch executables (device-resident scan training)           #
    # ------------------------------------------------------------------ #
    def _scan_epoch(self, step_impl: Callable, prog: DTMProgram, prng: PRNG,
                    lits: jax.Array, labels: jax.Array, idx: jax.Array):
        """One training epoch as a single ``lax.scan`` over pre-staged
        batches.

        ``lits``/``labels`` are the FULL staged dataset (packed literals,
        encoded labels) resident on device; ``idx`` [steps, B] int32 is
        the epoch's shuffled batch index plan.  The scan carries
        (program, PRNG) and emits PER-STEP stats ([steps] int32 per key
        — per-step values fit int32 comfortably; the epoch totals are
        summed host-side in exact integer arithmetic, just like the host
        loop sums per-batch ints, so histories stay bit-identical at any
        scale).  The per-batch step is the SAME ``_train_impl``/
        ``_train_conv_impl`` trace the host loop jits, so the resulting
        program and stats are bit-identical to ``steps`` individual
        dispatches; only the host↔device round trips differ (one per
        epoch instead of one per batch)."""

        def body(carry, ib):
            prog, prng = carry
            prog, prng, stats = step_impl(prog, prng,
                                          jnp.take(lits, ib, axis=0),
                                          jnp.take(labels, ib, axis=0))
            return (prog, prng), {k: stats[k].astype(jnp.int32)
                                  for k in STAT_KEYS}

        (prog, prng), step_stats = jax.lax.scan(body, (prog, prng), idx)
        return prog, prng, step_stats

    def _fit_epoch_impl(self, prog, prng, lits, labels, idx):
        return self._scan_epoch(self._train_impl, prog, prng, lits, labels,
                                idx)

    def _fit_epoch_conv_impl(self, prog, prng, plits, labels, idx):
        return self._scan_epoch(self._train_conv_impl, prog, prng, plits,
                                labels, idx)

    def bind(self, program: DTMProgram, x=None, y=None, *, spec=None,
             prng: Optional[PRNG] = None, seed: int = 0) -> "TMSession":
        """Open a device-resident training session on this engine.

        ``x``/``y`` (optional) are raw model inputs/targets staged ONCE —
        encoded to the packed canonical layout and kept on device;
        ``session.fit_epochs(n)`` then runs each epoch as a single scan
        launch (program + PRNG donated through the carry).  Without
        staged data the session still owns the (program, PRNG) pair and
        serves streaming ``step()`` updates — the estimator's
        ``partial_fit`` path."""
        if prng is None:
            if spec is not None:
                prng = PRNG.create(spec.tm_config(), seed + 1)
            else:
                prng = PRNG("counter", 24, self.rand_bits, False,
                            jnp.uint32(seed + 1 if seed + 1 else 0xC0FFEE))
        session = TMSession(self, program, prng, spec=spec)
        if x is not None:
            with span(spans.FIT_BIND):
                session.stage(x, y)
        return session

    # ------------------------------------------------------------------ #
    # program-bank executables (K stacked programs, one launch)           #
    # ------------------------------------------------------------------ #
    def _infer_bank_impl(self, progs: DTMProgram, lits: jax.Array):
        """Stacked inference: program leaves [K, ...], lits [K, B, W] ->
        (sums [K, B, H], clause [K, B, R]) in ONE launch."""
        lanes = lits.shape[0]
        return jax.vmap(functools.partial(
            self._infer_impl, lanes=lanes, stage="infer_bank"))(progs, lits)

    def _infer_conv_bank_impl(self, progs: DTMProgram, plits: jax.Array):
        """Stacked conv inference: plits [K, B, P, W]."""
        lanes = plits.shape[0]
        return jax.vmap(functools.partial(
            self._infer_conv_impl, lanes=lanes,
            stage="infer_conv_bank"))(progs, plits)

    def _train_bank_impl(self, progs: DTMProgram, prngs: PRNG,
                         lits: jax.Array, labels: jax.Array):
        """Stacked training step: K programs each take one batch
        ([K, B, W] literals, [K, B] labels) in ONE launch — ensembles and
        multi-tenant on-line training without per-program dispatches."""
        lanes = lits.shape[0]
        return jax.vmap(functools.partial(
            self._train_impl, lanes=lanes, stage="train_bank"))(
                progs, prngs, lits, labels)

    def _infer_bank_list_impl(self, progs: DTMProgram, lits_list):
        return self._infer_bank_impl(progs, jnp.stack(lits_list))

    def _infer_conv_bank_list_impl(self, progs: DTMProgram, plits_list):
        return self._infer_conv_bank_impl(progs, jnp.stack(plits_list))

    def _predict_bank_impl(self, progs: DTMProgram, lits: jax.Array):
        """Stacked inference DECODED in-trace: (argmax preds [K, B],
        clipped clause votes [K, B]) — the serving flush fetches two tiny
        int32 planes instead of the [K, B, H] sums + [K, B, R] clause
        matrix (classification reads ``preds``, regression reads
        ``votes`` / T; same values as host-side decode)."""
        sums, cl = self._infer_bank_impl(progs, lits)
        preds = jnp.argmax(sums, axis=-1).astype(jnp.int32)
        votes = jnp.clip(cl.sum(axis=-1), 0, progs.T[:, None])
        return preds, votes.astype(jnp.int32)

    def _predict_bank_list_impl(self, progs: DTMProgram, lits_list):
        return self._predict_bank_impl(progs, jnp.stack(lits_list))

    def _encode_bank(self, feats: jax.Array,
                     n_feats: jax.Array) -> jax.Array:
        """In-trace :meth:`encode` of K flat requests at once.

        ``feats`` [K, L/2, B] int8 {0,1}: slot k is its request's
        ``[B, n_feats[k]]`` rows, feature-major and zero-padded to L/2
        features (feature-major, so a column-major request, as
        ``np.asarray`` of a TPU array gives, is copied in its own memory
        order).  Returns packed [K, B, W], each slot bit-identical to
        ``encode`` of its block: ``_layout`` at the full half width, then
        every column at or past a slot's feature count is zeroed in both
        halves, so one trace serves any mix of widths."""
        live = (jnp.arange(self.L // 2) < n_feats[:, None]).astype(jnp.int8)
        keep = jnp.concatenate([live, live], axis=-1)[:, None, :]
        return pack_literals(self._layout(feats.transpose(0, 2, 1)) * keep)

    def _predict_bank_raw_impl(self, progs: DTMProgram, feats: jax.Array,
                               n_feats: jax.Array):
        return self._predict_bank_impl(progs,
                                       self._encode_bank(feats, n_feats))

    def infer_bank(self, progs: DTMProgram, lits):
        """lits: stacked [K, B, W] array, or a K-tuple of [B, W] arrays
        (stacked in-trace — the cheap path for per-tenant requests)."""
        if isinstance(lits, (list, tuple)):
            return self._infer_bank_list(progs, tuple(lits))
        return self._infer_bank(progs, lits)

    def infer_conv_bank(self, progs: DTMProgram, plits):
        if isinstance(plits, (list, tuple)):
            return self._infer_conv_bank_list(progs, tuple(plits))
        return self._infer_conv_bank(progs, plits)

    def predict_bank(self, progs: DTMProgram, lits):
        """Flat-bank inference with in-trace decode: K-tuple (or stacked
        [K, B, W]) packed literals -> (preds [K, B], votes [K, B])."""
        if not isinstance(lits, (list, tuple)):
            lits = tuple(lits)
        return self._predict_bank_list(progs, tuple(lits))

    def predict_bank_raw(self, progs: DTMProgram, feats: jax.Array,
                         n_feats: jax.Array):
        """Flat-bank inference from raw Boolean features, encoded and
        decoded in ONE launch: ``feats`` [K, L/2, B] int8 (slot k's
        ``[B, n_feats[k]]`` features, feature-major, zero-padded to L/2),
        ``n_feats`` [K] int32 -> (preds [K, B], votes [K, B]), equal to
        :meth:`predict_bank` of the per-slot :meth:`encode` literals.
        For the kinds whose ``TMSpec.raw_is_bool`` holds."""
        return self._predict_bank_raw(progs, feats, n_feats)

    def train_bank(self, progs: DTMProgram, prngs: PRNG, lits: jax.Array,
                   labels: jax.Array):
        return self._train_bank(progs, prngs, lits, labels)

    # spec-driven stage dispatch (one definition for estimator AND server)
    def train_fn(self, spec):
        return (self.train_conv if getattr(spec, "kind", None) == "conv"
                else self.train_step)

    def infer_fn(self, spec):
        return (self.infer_conv if getattr(spec, "kind", None) == "conv"
                else self.infer)

    # convenience: compile-cache introspection for the flexibility tests
    def cache_sizes(self) -> Tuple[int, int]:
        return (self._infer._cache_size(), self._train._cache_size())

    def cache_report(self) -> dict:
        """Jit cache entries per engine stage executable (the paper's
        'no resynthesis' claim: every int value stays <= 1 across
        arbitrary program swaps).

        ``path_per_stage`` maps each traced stage to the kernel path that
        stage actually EXECUTES (recorded inside the taken branch at trace
        time, for the most recent trace) — dispatch == execution is
        asserted in tests, closing the old silent packed_vpu→mxu fallback.
        Train stages additionally record the SKIP dimension under
        ``<stage>_ta``: ``compact`` (Alg-6 clause-skip TA update) or
        ``dense`` (``REPRO_SKIP=0`` / program banks).
        """
        return {
            "infer": self._infer._cache_size(),
            "train": self._train._cache_size(),
            "infer_conv": self._infer_conv._cache_size(),
            "train_conv": self._train_conv._cache_size(),
            "fit_epoch": self._fit_epoch._cache_size(),
            "fit_epoch_conv": self._fit_epoch_conv._cache_size(),
            "infer_bank": self._infer_bank._cache_size(),
            "infer_conv_bank": self._infer_conv_bank._cache_size(),
            "infer_bank_list": self._infer_bank_list._cache_size(),
            "infer_conv_bank_list":
                self._infer_conv_bank_list._cache_size(),
            "predict_bank_list": self._predict_bank_list._cache_size(),
            "predict_bank_raw": self._predict_bank_raw._cache_size(),
            "train_bank": self._train_bank._cache_size(),
            "path_per_stage": dict(self._stage_paths),
        }


class TMSession:
    """A (program, PRNG) pair bound to an engine, with optionally staged
    device-resident training data (paper §IV-D: the datapath plus the RAM
    image it is currently programmed with, mid-training).

    Two execution modes share the session state:

    * ``step(x, y)``      — streaming: encode one batch, one dispatch
      (the estimator's ``partial_fit`` path).
    * ``fit_epochs(n)``   — device-resident: the staged dataset is
      gathered on device per the epoch's shuffled index plan and the
      whole epoch runs as ONE ``lax.scan`` launch (program + PRNG donated
      through the carry, per-step stats summed exactly on the host).
      Bit-identical to the
      host ``fit_loop`` driving ``step`` batch by batch — same PRNG
      stream, same shuffle draws, same integer datapath — with host↔
      device transitions collapsed from one per batch to one per epoch.

    ``dispatches`` counts engine-executable launches — the probe the
    ≤ 1-transition-per-epoch tests assert on.
    """

    def __init__(self, engine: DTMEngine, program: DTMProgram, prng: PRNG,
                 spec=None):
        self.engine = engine
        self.spec = spec
        self.program = program
        self.prng = prng
        self.steps = 0          # train batches consumed
        self.dispatches = 0     # engine-executable launches (the probe)
        self._lits = None       # staged packed literals [N, W] / [N, P, W]
        self._labels = None     # staged encoded labels [N]
        self.n = 0

    # ---- data staging ------------------------------------------------------
    def _encode(self, x) -> jax.Array:
        if self.spec is not None:
            return self.engine.encode(self.spec, jnp.asarray(x))
        return self.engine.pad_features(jnp.asarray(x))

    def _encode_labels(self, y) -> jax.Array:
        if self.spec is not None:
            return self.spec.encode_labels(y)
        return jnp.asarray(y, jnp.int32)

    def stage(self, x, y) -> "TMSession":
        """Encode the full dataset ONCE and pin it on device.

        Row-wise encoding commutes with gathering, so device-side
        ``take`` of staged rows is bit-identical to encoding the gathered
        host batch (the fit_loop order of operations).

        Flat kinds encode in one launch.  Conv input is encoded in chunks
        of :data:`STAGE_CHUNK_ROWS` rows, each one jitted launch that
        writes its
        packed [rows, P, W] literals into the staged [N, P, W] array in
        place; the last chunk ends at row N (re-encoding rows of the one
        before it), so every launch has one shape.  The peak is the packed
        epoch plus one chunk's intermediates — the one-shot encode would
        hold the [N, P, L] int8 layout and its temporaries (≈ 14 GB at
        60,000 MNIST images, for 1.04 GB of packed literals).  Each
        launch is one ``tm.fit.encode`` span (``chunk=i``)."""
        if self.conv:
            self._lits = self._stage_conv(x)
        else:
            with span(spans.FIT_ENCODE, chunk=0):
                self._lits = self._encode(x)
        self._labels = self._encode_labels(y)
        self.n = int(self._lits.shape[0])
        return self

    def _stage_conv(self, x) -> jax.Array:
        eng = self.engine
        x = x if isinstance(x, jax.Array) else np.asarray(x)
        n = x.shape[0]
        c = min(STAGE_CHUNK_ROWS, n)
        starts = list(range(0, n - c + 1, c))
        if starts[-1] + c < n:
            starts.append(n - c)
        out = jnp.zeros((n, eng.P, eng.W), jnp.uint32)
        for i, s in enumerate(starts):
            with span(spans.FIT_ENCODE, chunk=i):
                out = eng._encode_into(self.spec, out, x[s:s + c], s)
        return out

    @property
    def conv(self) -> bool:
        return getattr(self.spec, "kind", None) == "conv"

    # ---- streaming mode ----------------------------------------------------
    def step(self, x, y) -> dict:
        """One engine train step on a fresh (unstaged) batch."""
        lits, lab = self._encode(x), self._encode_labels(y)
        fn = self.engine.train_fn(self.spec)
        self.program, self.prng, stats = fn(self.program, self.prng, lits,
                                            lab)
        self.steps += 1
        self.dispatches += 1
        return stats

    # ---- device-resident mode ----------------------------------------------
    def fit_epochs(self, epochs: int, batch: int = 32,
                   rng: Optional[np.random.Generator] = None,
                   log_every: int = 0, score_fn: Optional[Callable] = None,
                   x_test=None, y_test=None,
                   extra_metrics: Optional[Callable] = None) -> list:
        """Run ``epochs`` training epochs, ONE scan launch per epoch.

        Returns the same per-epoch records as
        :func:`repro.core.evaluate.fit_loop` (``epoch_record`` is shared),
        with identical shuffle-RNG consumption — one
        ``rng.permutation(n)`` per epoch."""
        assert self._lits is not None, "bind data first: engine.bind(p, x, y)"
        rng = rng or np.random.default_rng(0)
        n = self.n - self.n % batch
        steps = n // batch
        fit = (self.engine._fit_epoch_conv if self.conv
               else self.engine._fit_epoch)
        history = []
        for ep in range(epochs):
            with span(spans.FIT_PLAN, epoch=ep):
                idx = rng.permutation(self.n)[:n].astype(np.int32)
                # the epoch's ONE host->device transition, made explicit
                # so the whole loop runs under
                # jax.transfer_guard("disallow") (analysis/trace_audit.py)
                # — an implicit transfer sneaking into the scan launch
                # would fail the audit
                plan = jax.device_put(idx.reshape(steps, batch))
            with span(spans.FIT_EPOCH, epoch=ep):
                self.program, self.prng, step_stats = fit(
                    self.program, self.prng, self._lits, self._labels, plan)
            self.dispatches += 1
            self.steps += steps
            # exact integer epoch totals from the per-step stats — the
            # same arithmetic fit_loop does with per-batch Python ints
            # (an in-carry int32 sum could wrap at paper scale); the
            # device_get is the epoch's one explicit device->host read
            with span(spans.FIT_FETCH, epoch=ep):
                step_stats = jax.device_get(step_stats)
                agg = {k: int(np.asarray(v).sum(dtype=np.int64))
                       for k, v in step_stats.items()}
                rec = epoch_record(ep, agg, n, extra_metrics)
            if score_fn is not None and x_test is not None:
                rec["test_acc"] = score_fn(x_test, y_test)
            history.append(rec)
            if log_every and ep % log_every == 0:
                print(rec)
        return history

    # ---- state hand-back ---------------------------------------------------
    def state(self) -> Tuple[DTMProgram, PRNG]:
        """Current (program, PRNG) — live view, safe to read any time."""
        return self.program, self.prng

    def unbind(self) -> Tuple[DTMProgram, PRNG]:
        """Close the session: release staged data, return final state."""
        self._lits = self._labels = None
        return self.program, self.prng
