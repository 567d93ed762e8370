"""Multi-tenant DTM serving: one resident engine, hot program swaps.

The FPGA story (paper §IV-A, Table II) as an API: the accelerator is
synthesised ONCE; switching the hosted model is a RAM rewrite, not a
resynthesis.  Here the engine's jitted stage executables are the
synthesised datapath and a :class:`repro.core.dtm.DTMProgram` is the RAM
image — so a server can host any number of TM models (any mix of the five
spec kinds) and swap them *between requests* at memory-bandwidth cost.

Requests are padded to a fixed batch-slot size so every tenant hits the
same compiled executable (jit cache stays at one entry per stage — the
``cache_report()`` assert at the bottom of the benchmark is the claim).

Program-major stacked serving (ISSUE 4): beyond swap-per-request, the
server can coalesce pending requests across tenants into ONE stacked
launch — tenant programs live in a resident :class:`repro.api.ProgramBank`
(one per stage family: flat / conv) and ``enqueue(...)`` + ``flush()``
run all K tenants through the engine's vmapped bank executable in a
single dispatch.  Hot-swap semantics survive: training a tenant updates
its own program and marks the bank slot dirty; the next flush scatters
the fresh program back into the bank (``swap_in`` — a device-side row
write, the per-tenant RAM rewrite of the paper at bank granularity).

Programs are stored and swapped in the engine's bit-packed canonical
layout (uint8 TA states 4-per-word + the uint32 include bitplane the
train stages maintain incrementally), so the per-tenant RAM image —
reported per tenant as ``program_nbytes`` in :meth:`TMServer.stats` — is
~7× smaller than the int32 TA + re-thresholded include pair it replaced;
literals ship packed 32-per-word from ``engine.encode``.

Raw requests to resident tenants whose ``TMSpec.raw_is_bool`` holds
(vanilla, coalesced, regression) are checked and padded at ``enqueue``
and stay on the host; ``flush_async`` stacks the flat cycle's rows into
one fresh int8 array of K feature-major ``[L/2, B]`` slots, makes one
``device_put`` and one dispatch (``ProgramBank.predict_raw``) that
encodes, evaluates and decodes the whole cycle in-trace.  Pre-encoded literals, head and conv requests,
non-resident tenants, pod mode, and a flat cycle that mixes any of those
with raw rows keep the per-request ``engine.encode``.  Spans and counters:
``tm.server.encode`` times one request at ``enqueue`` (the pad alone for
a deferred raw request); ``tm.server.encode_batch`` times the stack,
transfer and dispatch of a batched cycle inside ``tm.server.launch``;
:meth:`TMServer.stats` counts ``encode_batches``,
``encode_batched_requests`` and ``encode_eager_requests``.

Async serving (ISSUE 7): ``flush`` is split into a launch phase
(:meth:`TMServer.flush_async` — dispatches the stacked bank executables
and returns a :class:`PendingFlush` WITHOUT fetching) and a fetch phase
(:meth:`TMServer.collect`), so a driver can overlap device work with
host-side encode of the next batch (``repro.launch.scheduler`` owns that
loop).  Bank membership is DYNAMIC: :meth:`TMServer.set_resident`
restricts a stage family's bank roster, :meth:`TMServer.swap_resident`
promotes a swapped tenant into a demoted tenant's slot through the
routed ``swap_in``/``swap_out`` path (a pair of device-side row
scatters — no restack), and requests for non-resident tenants fall back
to a per-request single-program launch (the measured "cold path" the
promotion policy exists to avoid).

On-line training requests run the clause-skip TA update (ISSUE 5): as a
tenant's model converges, fewer clause groups receive feedback and its
``train()`` wall-clock falls.  The per-tenant lifetime skip fraction is
surfaced as ``skip_frac`` in :meth:`TMServer.stats` (device-lazy
accumulators — no extra host sync on the train path).

Benchmark (``BENCH_reconfig.json``): measures

* ``engine_compile_s``   — one-time cost of the first request per stage
  (the "synthesis" analogue, paid once per server lifetime);
* ``swap_overhead_us``   — extra latency of a request that *switches*
  tenants vs one that repeats the resident tenant (the paper's
  reconfiguration cost, Fig 5/6: iteration counts + masks);
* ``resynthesis_baseline_s`` — what the swap *would* cost if each model
  needed its own compiled engine (fresh engine + first request), i.e. the
  no-DTM world the paper compares against.

CLI:  PYTHONPATH=src python -m repro.launch.serve_tm --smoke \
          [--backend auto] [--out BENCH_reconfig.json]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.api import ProgramBank, TM, TMSpec
from repro.core.dtm import DTMEngine, DTMProgram
from repro.core.prng import PRNG
from repro.launch import pod as _pod
from repro.runtime import spans
from repro.runtime.spans import span


@dataclasses.dataclass
class _Tenant:
    spec: TMSpec
    program: DTMProgram
    prng: PRNG
    steps: int = 0      # lifetime applied training steps (durable cursor)


@dataclasses.dataclass
class PendingFlush:
    """One in-flight stacked flush: device work dispatched, results not
    yet fetched.  Produced by :meth:`TMServer.flush_async`; resolved by
    :meth:`TMServer.collect` (which is where the only host-device sync
    of the serving path happens).  ``hot`` holds one entry per launched
    stage-family bank (lazy output arrays), ``cold`` one per
    non-resident tenant served through the single-program fallback."""

    n_real: Dict[str, int]                 # tenant -> un-padded batch
    hot: list                              # (conv, names, out_a, out_b)
    cold: list                             # (name, sums, cl)


def _decode_np(spec: TMSpec, sums: np.ndarray, cl: np.ndarray,
               t: int) -> np.ndarray:
    """Host-side mirror of ``TMSpec.decode_output`` (numpy, zero extra
    dispatches) — used on the already-fetched stacked launch outputs."""
    if spec.kind == "regression":
        votes = np.clip(cl.sum(-1), 0, t)
        return votes.astype(np.float32) / t
    return np.argmax(sums, axis=-1)


def _fetch(a) -> np.ndarray:
    """One lazy launch output to host: the serving path's host sync."""
    with span(spans.SERVER_FETCH):
        return np.asarray(a)


class TMServer:
    """One compiled engine, N resident programs, swap-per-request serving.

    ``batch_slot`` is the fixed request batch the executables are traced
    for; incoming batches are padded up to it (and the padding stripped),
    so heterogeneous request sizes never retrace the engine.

    Pod mode (``mesh=`` with > 1 device): the resident banks become
    tenant-parallel :class:`repro.launch.pod.PodBank` s sharded over
    ``tenants_axis`` — D devices each serve a device-local slice of the
    roster in the same stacked launch.  The server owns the global
    tenant → (device, slot) map (:meth:`routing_table`); per-tenant
    hot-swap stays a global-row scatter/gather that XLA routes to the
    owning device (:meth:`swap_in` / :meth:`swap_out`).
    """

    def __init__(self, engine: DTMEngine, batch_slot: int = 32,
                 mesh=None, tenants_axis: str = "tenants"):
        self.engine = engine
        self.batch_slot = batch_slot
        self.mesh = mesh
        self.tenants_axis = tenants_axis
        self.pod_devices = (_pod.mesh_axis_size(mesh, tenants_axis)
                            if mesh is not None else 1)
        self.tenants: Dict[str, _Tenant] = {}
        self.active: Optional[str] = None
        self.swaps = 0
        self.requests = 0
        # stacked (program-major) serving state
        # (tenant, device literals or padded host rows, un-padded rows)
        self._pending: List[Tuple[str, jax.Array | np.ndarray, int]] = []
        self._banks: Dict[bool, Tuple[List[str], ProgramBank]] = {}
        self._groups: Dict[bool, List[str]] = {}
        self._decode_info: Dict[str, Tuple[bool, int]] = {}
        self._dirty: set = set()
        self.stacked_launches = 0
        self.coalesced_requests = 0
        # literal encode: cycles encoded in one dispatch, and the
        # requests served through that path or encoded one at a time
        self.encode_batches = 0
        self.encode_batched_requests = 0
        self.encode_eager_requests = 0
        # dynamic bank membership (scheduler-driven): per stage family,
        # the ordered resident roster — None = every registered tenant
        self._membership: Dict[bool, Optional[List[str]]] = {}
        self.cold_requests = 0
        self.membership_swaps = 0
        # per-tenant Alg-6 skip accounting: device-lazy [active, total]
        # group-count accumulators (summed on the train path with zero
        # extra host syncs; materialised only by stats())
        self._skip_acc: Dict[str, list] = {}

    # ---- tenant management ------------------------------------------------
    def register(self, name: str, spec: TMSpec,
                 program: Optional[DTMProgram] = None, seed: int = 0,
                 prng: Optional[PRNG] = None, steps: int = 0):
        """Admit a model: lower its spec onto the resident engine (or adopt
        an already-lowered/trained program).  ``prng``/``steps`` resume a
        tenant mid-stream (the durable-restore path) — by default a fresh
        PRNG is derived from ``seed`` and the step cursor starts at 0."""
        if program is None:
            program = self.engine.lower(spec, jax.random.PRNGKey(seed))
        if prng is None:
            prng = PRNG.create(spec.tm_config(), seed + 1)
        self.tenants[name] = _Tenant(spec, program, prng, steps=steps)
        self._admitted(name, spec)

    def adopt(self, name: str, tm: TM):
        """Admit a trained ``repro.api.TM`` estimator (must share tile
        geometry with the resident engine)."""
        assert tm.engine.tile == self.engine.tile, "tile geometry mismatch"
        self.tenants[name] = _Tenant(tm.spec, tm.program, tm.prng)
        self._admitted(name, tm.spec)

    def _admitted(self, name: str, spec: TMSpec) -> None:
        # group membership changed — the resident bank must be rebuilt;
        # decode constants are cached off the request hot path
        self._banks.pop(spec.kind == "conv", None)
        self._groups.pop(spec.kind == "conv", None)
        self._decode_info[name] = (spec.kind == "regression",
                                   int(spec.tm_config().T))
        # a (re-)registered tenant is a fresh model: its lifetime skip
        # accounting starts over (skip_frac == None until it trains)
        self._skip_acc.pop(name, None)

    def _swap_to(self, name: str) -> _Tenant:
        tenant = self.tenants[name]
        if self.active != name:
            self.swaps += 1
            self.active = name
        return tenant

    def _pad(self, x: np.ndarray) -> Tuple[np.ndarray, int]:
        n = x.shape[0]
        assert n <= self.batch_slot, (n, self.batch_slot)
        if n < self.batch_slot:
            x = np.concatenate(
                [x, np.repeat(x[-1:], self.batch_slot - n, axis=0)])
        return x, n

    def _encode_request(self, tenant: _Tenant, x, encoded: bool,
                        defer: bool = False
                        ) -> Tuple[jax.Array | np.ndarray, int]:
        """Pad a request to the batch slot and encode it (unless the
        front-end already shipped packed engine literals, or ``defer``
        keeps the padded raw rows on the host for the cycle's batched
        encode in :meth:`flush_async`)."""
        with span(spans.SERVER_ENCODE):
            if encoded:
                # hot path: a full-slot device array passes straight
                # through (no eager jnp ops — they dominate small-request
                # latency)
                if (isinstance(x, jax.Array)
                        and x.shape[0] == self.batch_slot):
                    return x, self.batch_slot
                lits = jnp.asarray(x)
                n = lits.shape[0]
                assert n <= self.batch_slot, (n, self.batch_slot)
                if n < self.batch_slot:
                    pad = jnp.repeat(lits[-1:], self.batch_slot - n, axis=0)
                    lits = jnp.concatenate([lits, pad], axis=0)
                return lits, n
            x = np.asarray(x)
            if defer and (x.ndim != 2 or x.shape[1] > self.engine.L // 2
                          or x.dtype.kind not in "biuf"):
                # refused here, as encode would refuse it: a bad block
                # must fail alone, not the flush that stacks it
                raise ValueError(
                    f"raw request {x.dtype}{list(x.shape)}: expected "
                    f"numeric [rows, features], at most "
                    f"{self.engine.L // 2} features")
            xp, n = self._pad(x)
            if defer:
                return xp, n
            return self.engine.encode(tenant.spec, jnp.asarray(xp)), n

    # ---- request paths ----------------------------------------------------
    def predict(self, name: str, x, encoded: bool = False) -> np.ndarray:
        """Hot-swap to tenant ``name`` and serve an inference request.

        ``encoded=True`` accepts packed engine literals (``[n, W]``
        uint32 from ``engine.encode``) straight from a front-end that
        booleanises client-side — the pure launch path the stacked-mode
        benchmark compares against."""
        tenant = self._swap_to(name)
        self.requests += 1
        lits, n = self._encode_request(tenant, x, encoded)
        sums, cl = self.engine.infer_fn(tenant.spec)(tenant.program, lits)
        if tenant.spec.kind == "regression":
            t = int(tenant.spec.tm_config().T)
            return _decode_np(tenant.spec, None, np.asarray(cl), t)[:n]
        return _decode_np(tenant.spec, np.asarray(sums), None, 0)[:n]

    def train(self, name: str, x, y, encoded: bool = False) -> dict:
        """Hot-swap and apply one on-line training step (on-chip training:
        the same resident datapath updates the tenant's program in place).

        Training requests must FILL the batch slot: padding an inference
        request is free, but padding a training batch would replicate the
        last example's feedback — callers accumulate until a slot is full.

        ``encoded=True`` accepts packed engine literals plus
        engine-encoded labels (``engine.encode`` / ``spec.encode_labels``
        done front-end-side), mirroring ``predict``/``enqueue`` — the
        pure launch path with no eager encode ops on the driver thread
        (what the trace-contract audit drives under
        ``jax.transfer_guard``)."""
        with span(spans.SERVER_TRAIN):
            tenant = self._swap_to(name)
            self.requests += 1
            if encoded:
                lits, lab = x, y
                assert lits.shape[0] == self.batch_slot, (
                    f"encoded training request has {lits.shape[0]} "
                    f"examples; batch_slot is {self.batch_slot}")
            else:
                xp, yp = np.asarray(x), np.asarray(y)
                assert xp.shape[0] == self.batch_slot, (
                    f"training request has {xp.shape[0]} examples; "
                    f"batch_slot is {self.batch_slot} — accumulate to a "
                    "full slot before train()")
                lits = self.engine.encode(tenant.spec, jnp.asarray(xp))
                lab = tenant.spec.encode_labels(yp)
            step = self.engine.train_fn(tenant.spec)
            tenant.program, tenant.prng, stats = step(
                tenant.program, tenant.prng, lits, lab)
            # the tenant's bank slot is stale until the next flush swaps
            # the fresh program back in (hot-swap at bank granularity)
            self._dirty.add(name)
            tenant.steps += 1
            # step stats are device scalars: fetch them ALL in one
            # explicit transfer so (a) the skip accumulator stays a host
            # counter instead of a growing lazy device graph and (b)
            # callers (the scheduler's drift/pause telemetry, the durable
            # writer) get plain host ints with no further syncs
            host = {k: int(v) for k, v in jax.device_get(stats).items()}
            acc = self._skip_acc.setdefault(name, [0, 0])
            acc[0] = acc[0] + host["active_groups"]
            acc[1] = acc[1] + host["total_groups"]
            return host

    # ---- stacked (program-major) serving ----------------------------------
    def _group(self, conv: bool) -> List[str]:
        """:meth:`_group_names`, cached until the roster changes."""
        group = self._groups.get(conv)
        if group is None:
            group = self._groups[conv] = self._group_names(conv)
        return group

    def _group_names(self, conv: bool) -> List[str]:
        member = self._membership.get(conv)
        if member is not None:
            return [n for n in member if n in self.tenants]
        return sorted(n for n, t in self.tenants.items()
                      if (t.spec.kind == "conv") == conv)

    def resident_names(self, conv: Optional[bool] = None) -> List[str]:
        """Tenants eligible for the stacked bank launch (the resident
        roster): the dynamic membership if one was set, otherwise every
        registered tenant of the family."""
        fams = (False, True) if conv is None else (conv,)
        return [n for c in fams for n in self._group_names(c)]

    # ---- dynamic bank membership (promote / demote) ------------------------
    def set_resident(self, names: Sequence[str], conv: bool = False) -> None:
        """Restrict one stage family's bank roster to ``names`` (slot
        order).  Tenants left out stay registered and servable — their
        stacked-flush requests take the per-request cold path until
        :meth:`swap_resident` / :meth:`add_resident` promotes them."""
        names = list(names)
        assert len(set(names)) == len(names), names
        for n in names:
            assert n in self.tenants, n
            assert (self.tenants[n].spec.kind == "conv") == conv, n
        self._membership[conv] = names
        self._banks.pop(conv, None)
        self._groups.pop(conv, None)

    def swap_resident(self, out_name: str, in_name: str):
        """Dynamic bank membership: demote ``out_name`` (its fresh
        program reads back to the tenant record via the routed
        ``swap_out``) and promote ``in_name`` into the freed slot (routed
        ``swap_in``) — two device-side row ops, NO bank restack.  Returns
        the reused :class:`repro.launch.pod.Route` (``None`` when the
        bank was not built yet and only the roster changed)."""
        t_in = self.tenants[in_name]
        conv = t_in.spec.kind == "conv"
        assert (self.tenants[out_name].spec.kind == "conv") == conv, (
            "swap_resident stays within one stage family (flat vs conv)")
        member = self._membership.get(conv)
        assert member is not None, "set_resident() first"
        assert out_name in member and in_name not in member, (out_name,
                                                             in_name)
        self.membership_swaps += 1
        if conv not in self._banks:
            member[member.index(out_name)] = in_name
            self._groups.pop(conv, None)
            return None
        names, bank = self._bank_for(conv)     # applies dirty rescatter
        idx = names.index(out_name)
        self.tenants[out_name].program = bank.swap_out(idx)
        bank.swap_in(idx, t_in.program)
        names[idx] = in_name
        member[member.index(out_name)] = in_name
        self._groups.pop(conv, None)
        self._dirty.discard(in_name)
        spd = len(names) // max(self.pod_devices, 1)
        return _pod.Route(device=idx // spd, slot=idx % spd, index=idx,
                          conv=conv)

    def add_resident(self, in_name: str):
        """Promote ``in_name`` without demoting anyone: fill a pod-mode
        pad slot in place when one exists (routed ``swap_in``), else
        grow the roster (bank restacks on the next flush).  Returns the
        filled :class:`repro.launch.pod.Route` or ``None``."""
        conv = self.tenants[in_name].spec.kind == "conv"
        member = self._membership.get(conv)
        assert member is not None, "set_resident() first"
        assert in_name not in member, in_name
        self.membership_swaps += 1
        if conv in self._banks:
            names, bank = self._bank_for(conv)
            pad = _pod.first_pad_slot(names)
            if pad is not None:
                bank.swap_in(pad, self.tenants[in_name].program)
                names[pad] = in_name
                member.append(in_name)
                self._groups.pop(conv, None)
                self._dirty.discard(in_name)
                spd = len(names) // max(self.pod_devices, 1)
                return _pod.Route(device=pad // spd, slot=pad % spd,
                                  index=pad, conv=conv)
        member.append(in_name)
        self._banks.pop(conv, None)
        self._groups.pop(conv, None)
        return None

    def _bank_for(self, conv: bool) -> Tuple[List[str], ProgramBank]:
        """Resident ProgramBank over ALL tenants of a stage family (flat
        vs conv), built once per roster; per-tenant updates are scattered
        in via ``swap_in`` rather than restacking.  Pod mode instead
        builds a tenant-sharded :class:`repro.launch.pod.PodBank` (the
        roster padded to a multiple of the device count — pad slots
        replay slot 0's program and their outputs are dropped)."""
        if conv not in self._banks:
            names = self._group_names(conv)
            if self.mesh is not None and self.pod_devices > 1:
                padded = _pod.pad_roster(names, self.pod_devices)
                progs = [self.tenants[n].program if n is not None
                         else self.tenants[names[0]].program
                         for n in padded]
                bank = _pod.pod_stack(progs, self.engine, self.mesh,
                                      axis=self.tenants_axis, conv=conv)
                names = padded
            else:
                bank = api.stack([self.tenants[n].program for n in names],
                                 self.engine, conv=conv)
            self._banks[conv] = (names, bank)
            self._dirty -= set(n for n in names if n is not None)
        names, bank = self._banks[conv]
        if self._dirty:
            for n in list(self._dirty):
                if n in names:
                    bank.swap_in(names.index(n), self.tenants[n].program)
                    self._dirty.discard(n)
        return names, bank

    def bank(self, conv: bool = False) -> ProgramBank:
        """The resident bank of one stage family (built on first use) —
        a :class:`repro.launch.pod.PodBank` in pod mode."""
        return self._bank_for(conv)[1]

    def enqueue(self, name: str, x, encoded: bool = False) -> None:
        """Queue an inference request for the next stacked flush.

        A raw request to a resident flat tenant whose spec needs no
        booleanizing (``TMSpec.raw_is_bool``), outside pod mode, is only
        checked and padded and stays on the host: :meth:`flush_async`
        encodes the whole cycle in one transfer and one dispatch.  Every
        other request is encoded here, one request at a time."""
        tenant = self.tenants[name]
        defer = (not encoded and tenant.spec.raw_is_bool
                 and self.pod_devices == 1 and name in self._group(False))
        lits, n = self._encode_request(tenant, x, encoded, defer)
        self._pending.append((name, lits, n))

    def abandon_pending(self) -> int:
        """Drop every enqueued-but-unlaunched request (fault recovery:
        the scheduler failed the corresponding futures and must not let
        the stale literals ride the next cycle's flush).  Returns the
        number dropped."""
        n = len(self._pending)
        self._pending = []
        return n

    def flush_async(self) -> Optional[PendingFlush]:
        """Launch phase of :meth:`flush`: dispatch ONE stacked launch per
        stage family with pending requests (plus one single-program
        launch per pending NON-resident tenant — the cold path) and
        return a :class:`PendingFlush` WITHOUT fetching any result, so a
        driver can overlap the device work with host encode of the next
        batch.  When every pending request of the flat bank is a host
        block (see :meth:`enqueue`), that bank's launch encodes them all
        in-trace from one transfer.  An empty queue is a cheap no-op
        (``None``): no bank build, no launch, no device sync — the
        background flush loop calls this on a timer.  The queue is
        cleared only once every launch is dispatched, so a launch that
        raises leaves it whole for a retry."""
        if not self._pending:
            return None
        with span(spans.SERVER_LAUNCH):
            by_name: Dict[str, tuple] = {}
            for name, x, n in self._pending:
                by_name[name] = (x, n)
            hot, cold, claimed = [], [], set()
            launches = batched = 0
            for conv in (False, True):
                req_names = [n for n in self._group(conv) if n in by_name]
                if not req_names:
                    continue
                claimed.update(req_names)
                names, bank = self._bank_for(conv)
                launches += 1
                if not conv and all(isinstance(by_name[n][0], np.ndarray)
                                    for n in req_names):
                    # the whole cycle in ONE transfer and ONE launch
                    # that encodes and decodes in-trace
                    with span(spans.SERVER_ENCODE_BATCH):
                        out = bank.predict_raw(*jax.device_put(
                            self._stack_raw(names, by_name)))
                    batched = len(req_names)
                    hot.append((False, list(names)) + tuple(out))
                    continue
                # idle slots replay a pending tenant's literals — their
                # outputs are dropped, so the filler's values are
                # irrelevant and no eager zeros/stack ops run (stacking
                # happens in-trace via the tuple-taking bank executables)
                lits = {n: self._lits(n, by_name[n][0]) for n in req_names}
                filler = lits[req_names[0]]
                lits = tuple(lits.get(n, filler) for n in names)
                if not conv:
                    # flat banks decode IN-TRACE: two tiny [K, B] planes,
                    # no host argmax, no clause-matrix round trip
                    hot.append((False, list(names))
                               + tuple(bank.predict(lits)))
                else:
                    hot.append((True, list(names)) + tuple(bank.infer(lits)))
            for name, (x, _) in by_name.items():
                # requests for tenants OUTSIDE the resident roster
                # (dynamic bank membership demoted them) fall back to a
                # per-request single-program launch — the measured cold
                # path
                if name in claimed:
                    continue
                tenant = self.tenants[name]
                sums, cl = self.engine.infer_fn(tenant.spec)(
                    tenant.program, self._lits(name, x))
                cold.append((name, sums, cl))
            self.requests += len(self._pending)
            self._pending = []
            self.stacked_launches += launches
            self.coalesced_requests += len(claimed)
            self.cold_requests += len(cold)
            self.encode_batches += int(batched > 0)
            self.encode_batched_requests += batched
            self.encode_eager_requests += len(by_name) - batched
            return PendingFlush(
                n_real={n: v[1] for n, v in by_name.items()},
                hot=hot, cold=cold)

    def _lits(self, name: str, x) -> jax.Array:
        """Device literals of a queued request.  A host block whose
        cycle cannot be encoded in one launch (its tenant left the
        roster, or its bank's cycle holds other requests) is encoded
        here, as :meth:`enqueue` would have encoded it."""
        if isinstance(x, np.ndarray):
            return self.engine.encode(self.tenants[name].spec,
                                      jnp.asarray(x))
        return x

    def _stack_raw(self, names: List[str], by_name: Dict[str, tuple]
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """One flat cycle of host blocks as the raw bank launch takes
        them: a fresh [K, L/2, B] int8 array and the [K] int32 feature
        counts.  Slot k holds its request's rows, feature-major,
        zero-padded from their feature count to L/2; idle slots are all
        zero and their outputs dropped.  Allocated per cycle, never
        reused while a transfer may still read it."""
        feats = np.zeros((len(names), self.engine.L // 2, self.batch_slot),
                         np.int8)
        n_feats = np.zeros(len(names), np.int32)
        for k, name in enumerate(names):
            if name in by_name:
                x = by_name[name][0]
                n_feats[k] = x.shape[1]
                feats[k, :x.shape[1]] = x.T
        return feats, n_feats

    def collect(self, pf: Optional[PendingFlush]) -> Dict[str, np.ndarray]:
        """Fetch phase of :meth:`flush`: materialise a
        :class:`PendingFlush`'s lazy outputs and decode per tenant.
        Returns {tenant: prediction}."""
        if pf is None:
            return {}
        with span(spans.SERVER_COLLECT):
            out: Dict[str, np.ndarray] = {}
            for conv, names, a, b in pf.hot:
                if not conv:
                    preds_np = _fetch(a)
                    votes_np = (_fetch(b) if any(
                        self._decode_info[n][0] for n in names
                        if n in pf.n_real) else None)
                    for k, name in enumerate(names):
                        if name not in pf.n_real:
                            continue
                        is_reg, t = self._decode_info[name]
                        n_real = pf.n_real[name]
                        if is_reg:
                            out[name] = (votes_np[k][:n_real]
                                         .astype(np.float32) / t)
                        else:
                            out[name] = preds_np[k][:n_real]
                    continue
                preds = np.argmax(_fetch(a), axis=-1)
                for k, name in enumerate(names):
                    if name in pf.n_real:
                        out[name] = preds[k][:pf.n_real[name]]
            for name, sums, cl in pf.cold:
                is_reg, t = self._decode_info[name]
                n_real = pf.n_real[name]
                if is_reg:
                    votes = np.clip(_fetch(cl).sum(-1), 0, t)
                    out[name] = votes[:n_real].astype(np.float32) / t
                else:
                    out[name] = np.argmax(_fetch(sums), axis=-1)[:n_real]
            return out

    def flush(self) -> Dict[str, np.ndarray]:
        """Serve every pending request in ONE stacked launch per stage
        family: the full tenant bank executes (vmapped over the program
        axis); tenants without a pending request run their last/zero
        slot and their outputs are dropped.  Returns {tenant: prediction}
        (last request wins if a tenant queued twice).  Equivalent to
        ``collect(flush_async())`` — the synchronous convenience path."""
        return self.collect(self.flush_async())

    def unstack(self, conv: bool = False) -> Dict[str, DTMProgram]:
        """Swap every bank slot back out to its tenant (and return the
        per-tenant programs) — proves the stacked round trip is lossless."""
        names, bank = self._bank_for(conv)
        progs = {}
        for k, name in enumerate(names):
            if name is None:          # pod-mode roster pad slot
                continue
            progs[name] = bank.swap_out(k)
            self.tenants[name].program = progs[name]
        return progs

    # ---- pod routing (tenant -> device, slot) ------------------------------
    def routing_table(self) -> Dict[str, "_pod.Route"]:
        """Global tenant → (device, slot) map over BOTH stage-family
        banks (flat + conv), rebuilt-on-demand alongside the banks.  The
        slot index is the stacked program row; with the bank's leading
        axis laid out ``P(tenants)``, contiguous row blocks of size
        ``len(roster)/D`` live per device — single-device servers route
        everything to device 0."""
        table: Dict[str, _pod.Route] = {}
        for conv in (False, True):
            if not self._group_names(conv):
                continue
            names, _ = self._bank_for(conv)
            table.update(_pod.routing_table(names, self.pod_devices, conv))
        return table

    def swap_in(self, name: str, program: DTMProgram) -> "_pod.Route":
        """Hot-swap a tenant's program THROUGH the routing table: update
        the tenant record and scatter the new program into its bank slot
        on the owning device.  Returns the route it resolved to."""
        route = self.routing_table()[name]
        self.tenants[name].program = program
        _, bank = self._bank_for(route.conv)
        bank.swap_in(route.index, program)
        self._dirty.discard(name)
        return route

    def swap_out(self, name: str) -> DTMProgram:
        """Read a tenant's program back out of its routed bank slot."""
        route = self.routing_table()[name]
        prog = self._bank_for(route.conv)[1].swap_out(route.index)
        self.tenants[name].program = prog
        return prog

    def program_nbytes(self, name: str) -> int:
        """Hot-swap payload of one tenant: total bytes of its DTMProgram
        leaves.  The bit-packed canonical layout (uint8 TA 4-per-word +
        uint32 include bitplane instead of an int32 [R, L] pair) is what
        keeps this — the per-swap RAM image — small."""
        return sum(leaf.nbytes
                   for leaf in jax.tree.leaves(self.tenants[name].program))

    def skip_frac(self, name: str) -> Optional[float]:
        """Lifetime Alg-6 clause-skip fraction of one tenant's on-line
        training (share of clause groups whose TA tiles the compacted
        update skipped); ``None`` before the tenant ever trained."""
        acc = self._skip_acc.get(name)
        if acc is None or int(acc[1]) == 0:
            return None
        return 1.0 - int(acc[0]) / int(acc[1])

    def stats(self) -> dict:
        resident = self.resident_names()
        return {"tenants": sorted(self.tenants), "requests": self.requests,
                "swaps": self.swaps, "cache": self.engine.cache_report(),
                "pod_devices": self.pod_devices,
                "stacked_launches": self.stacked_launches,
                "coalesced_requests": self.coalesced_requests,
                "encode_batches": self.encode_batches,
                "encode_batched_requests": self.encode_batched_requests,
                "encode_eager_requests": self.encode_eager_requests,
                # operator visibility (ISSUE 7): backlog + bank membership
                "queue_depth": len(self._pending),
                "resident_tenants": len(resident),
                "swapped_tenants": len(self.tenants) - len(resident),
                "resident": sorted(resident),
                "cold_requests": self.cold_requests,
                "membership_swaps": self.membership_swaps,
                "program_nbytes": {n: self.program_nbytes(n)
                                   for n in sorted(self.tenants)},
                "skip_frac": {n: self.skip_frac(n)
                              for n in sorted(self.tenants)}}


# ---------------------------------------------------------------------------
# reconfiguration-latency benchmark
# ---------------------------------------------------------------------------

def _block(x):
    # benchmark timing fence, not the serving hot path
    jax.block_until_ready(x)           # dtmlint: disable=DTM003
    return x


def demo_specs(small: bool = True) -> Dict[str, TMSpec]:
    """One spec per TM kind — the five-variant multi-tenant roster."""
    rng = np.random.default_rng(0)
    f, c = (32, 24) if small else (256, 128)
    calib = rng.standard_normal((64, 8)).astype(np.float32)
    return {
        "cotm": TMSpec.coalesced(features=f, classes=4, clauses=c, T=16,
                                 s=4.0),
        "vanilla": TMSpec.vanilla(features=f, classes=4, clauses=max(c // 4,
                                                                     4),
                                  T=16, s=4.0),
        "conv": TMSpec.conv(img_h=8, img_w=8, patch=3, classes=3,
                            clauses=c, T=12, s=3.0),
        "regression": TMSpec.regression(features=f, clauses=c, T=64, s=3.0),
        "head": TMSpec.head(calib, classes=3, therm_bits=4,
                            clauses=c, T=16, s=4.0),
    }


def demo_batch(spec: TMSpec, batch: int, seed: int = 1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if spec.kind == "conv":
        return (rng.random((batch, spec.img_h, spec.img_w)) < 0.3
                ).astype(np.int8)
    if spec.kind == "head":
        return rng.standard_normal(
            (batch, spec.thresholds.shape[0])).astype(np.float32)
    return (rng.random((batch, spec.features)) < 0.5).astype(np.int8)


def reconfig_benchmark(backend: str = "auto", batch_slot: int = 32,
                       rounds: int = 8, small: bool = True,
                       out: str = "BENCH_reconfig.json") -> dict:
    """Serve all five TM kinds round-robin off one engine and time it."""
    specs = demo_specs(small)
    tile = api.tile_for(*specs.values())
    engine = api.compile(tile, backend=backend)
    server = TMServer(engine, batch_slot=batch_slot)
    for name, spec in specs.items():
        server.register(name, spec)
    batches = {n: demo_batch(s, batch_slot) for n, s in specs.items()}
    names = sorted(specs)

    # one-time "synthesis": first request per tenant compiles each stage
    compile_s = {}
    for name in names:
        t0 = time.perf_counter()
        _block(server.predict(name, batches[name]))
        compile_s[name] = time.perf_counter() - t0

    # steady state, no swap: repeat the resident tenant
    steady_us = {}
    for name in names:
        _block(server.predict(name, batches[name]))            # make resident
        t0 = time.perf_counter()
        for _ in range(rounds):
            _block(server.predict(name, batches[name]))
        steady_us[name] = (time.perf_counter() - t0) / rounds * 1e6

    # swap every request: round-robin through all five kinds
    t0 = time.perf_counter()
    for _ in range(rounds):
        for name in names:
            _block(server.predict(name, batches[name]))
    swap_us = (time.perf_counter() - t0) / (rounds * len(names)) * 1e6

    # training requests also hot-swap (on-chip training between tenants);
    # first warm each train stage executable UNTIMED — its one-time jit
    # compile belongs with engine_compile_s, not the swap latency
    labels = {n: (np.zeros(batch_slot, np.float32)
                  if specs[n].kind == "regression"
                  else np.zeros(batch_slot, np.int32)) for n in names}
    train_compile_s = {}
    for name in names:
        t0 = time.perf_counter()
        jax.tree.map(_block, server.train(name, batches[name], labels[name]))
        train_compile_s[name] = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(rounds):
        for name in names:
            jax.tree.map(_block,
                         server.train(name, batches[name], labels[name]))
    train_swap_us = (time.perf_counter() - t0) / (rounds * len(names)) * 1e6

    # the no-DTM baseline: a fresh engine ("resynthesis") per model switch
    spec0 = specs["cotm"]
    t0 = time.perf_counter()
    fresh = api.compile(tile, backend=backend)
    prog = fresh.lower(spec0, jax.random.PRNGKey(0))
    _block(fresh.infer(prog, fresh.encode(spec0, jnp.asarray(
        batches["cotm"]))))
    resynthesis_s = time.perf_counter() - t0

    cache = engine.cache_report()
    assert all(v <= 1 for v in cache.values()
               if isinstance(v, int)), cache
    mean_steady = float(np.mean(list(steady_us.values())))
    report = {
        "backend": engine.backend,
        "tile": dataclasses.asdict(tile),
        "batch_slot": batch_slot,
        "n_models": len(names),
        "rounds": rounds,
        "engine_compile_s": compile_s,
        "train_compile_s": train_compile_s,
        "steady_us": steady_us,
        "swap_us": swap_us,
        "swap_overhead_us": swap_us - mean_steady,
        "train_swap_us": train_swap_us,
        "resynthesis_baseline_s": resynthesis_s,
        "speedup_vs_resynthesis": resynthesis_s * 1e6 / max(swap_us, 1e-9),
        "server": server.stats(),
    }
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny models + few rounds (CI artifact run)")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "kernel", "ref"))
    ap.add_argument("--batch-slot", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--out", default="BENCH_reconfig.json")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    rounds = args.rounds if args.rounds is not None else (
        4 if args.smoke else 16)
    rep = reconfig_benchmark(backend=args.backend,
                             batch_slot=args.batch_slot, rounds=rounds,
                             small=args.smoke, out=args.out)
    print(f"engine backend={rep['backend']}  tenants={rep['n_models']}  "
          f"requests={rep['server']['requests']}  "
          f"swaps={rep['server']['swaps']}")
    print(f"steady latency      : {np.mean(list(rep['steady_us'].values())):10.1f} us/req")
    print(f"swap-every-request  : {rep['swap_us']:10.1f} us/req "
          f"(overhead {rep['swap_overhead_us']:+.1f} us)")
    print(f"resynthesis baseline: {rep['resynthesis_baseline_s'] * 1e6:10.1f} us "
          f"({rep['speedup_vs_resynthesis']:.0f}x slower than a hot swap)")
    print(f"cache entries       : {rep['server']['cache']} "
          f"(all <= 1: no recompilation across swaps)")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
