"""Production mesh construction + TPU v5e hardware model.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model) — pod is pure
DP over the (slower) inter-pod links.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Whatever this host has (tests / examples): (n//m, m)."""
    n = len(jax.devices())
    return jax.make_mesh((max(n // model_axis, 1), model_axis),
                         ("data", "model"))


def make_tenant_mesh(n: int | None = None):
    """1-D serving mesh over a ``tenants`` axis (launch/pod.py: D devices
    each hosting a device-local slice of a stacked ProgramBank), built on
    the first ``n`` devices (default: all)."""
    return _mesh_1d("tenants", n)


def make_clause_mesh(n: int | None = None):
    """1-D mesh over a ``clauses`` axis (launch/pod.py: one over-VMEM TM's
    clause rows spread across D devices), on the first ``n`` devices."""
    return _mesh_1d("clauses", n)


def _mesh_1d(axis: str, n: int | None):
    """A 1-D mesh on the first ``n`` devices with an ``Auto`` axis: XLA
    places data between device-local and sharded arrays itself (a
    one-device tenant program scattered into a sharded bank slot), where
    ``make_mesh``'s default ``Explicit`` axes would demand a reshard at
    every such boundary."""
    devices = jax.devices()[:n]
    return jax.make_mesh((len(devices),), (axis,),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devices)


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """Per-chip peaks of one accelerator kind (a row of :data:`PEAKS`)."""

    name: str                              # jax Device.device_kind
    source: str
    peak_flops_bf16: float                 # FLOP/s per chip
    peak_int8_ops: float                   # OP/s per chip
    hbm_bw: float                          # B/s per chip
    hbm_bytes: float
    ici_link_bw: float                     # B/s per link
    ici_links_per_chip: int
    vmem_bytes: float                      # per-core VMEM (pod planner
    #                                        budget: a program whose RAM
    #                                        image exceeds it clause-shards)
    vpu_word_ops: float                    # 32-bit VPU ops/s (estimate)

    def collective_bw(self) -> float:
        """Aggregate per-chip ICI bandwidth available to a collective."""
        return self.ici_link_bw * self.ici_links_per_chip


V5E = HardwareModel(
    name="TPU v5 lite",
    source=("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
            "393 TOP/s int8, 16 GB HBM at 819 GB/s, 1600 Gbit/s ICI over "
            "4 links; VMEM 128 MiB; VPU rate = 8x128 lanes x ~0.94 GHz "
            "(estimate, not published)"),
    peak_flops_bf16=197e12, peak_int8_ops=393e12, hbm_bw=819e9,
    hbm_bytes=16e9, ici_link_bw=50e9, ici_links_per_chip=4,
    vmem_bytes=128e6, vpu_word_ops=1.0e12)

# Peaks keyed by ``jax.Device.device_kind``.  A kind missing here is an
# error, never a default.
PEAKS = {V5E.name: V5E}


def hardware_model(device=None) -> HardwareModel:
    """Peaks of ``device`` (default: the first JAX device).

    CPU runs — the tests and interpret-mode rehearsals — take the v5e row
    explicitly, so every roofline-driven dispatch decision is rehearsed
    for the chip.  An accelerator whose kind has no row raises."""
    device = jax.devices()[0] if device is None else device
    if device.platform == "cpu":
        return PEAKS["TPU v5 lite"]
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device kind {device.device_kind!r}; add its row "
            "to repro.launch.mesh.PEAKS") from None


def mesh_chips(mesh) -> int:
    return int(np.prod(mesh.devices.shape))


def data_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
