"""The master-slave LFSR cluster (paper Fig. 8), shared by the plain
references of every kind (``kinds/<kind>_ref.py``).

It imports nothing of the program under test.  A cluster is one master
(xorshift32) and ``n`` slave lanes (maximal-length Galois LFSRs of
``bits`` bits, seeded from the master by splitmix32 and the lane's id);
after ``2^bits - 1`` cycles the master steps and reseeds every lane.  A
draw emits one lane value per lane per cycle, cut or widened to
``rand_bits``.
"""
from __future__ import annotations

import jax.numpy as jnp

_U = jnp.uint32

# maximal-length Galois LFSR taps (polynomial without x^0), by width
TAPS = {8: 0b10111000, 12: 0b111000001000, 16: 0b1101000000001000,
        20: 0b10010000000000000000, 24: 0b111000010000000000000000,
        32: 0b10000000001000000000000000000110}


def splitmix32(x):
    x = (x.astype(_U) + _U(0x9E3779B9)).astype(_U)
    x = (x ^ (x >> 16)) * _U(0x21F0AAAD)
    x = (x ^ (x >> 15)) * _U(0x735A2D97)
    return (x ^ (x >> 15)).astype(_U)


def xorshift32(x):
    x = x ^ (x << 13)
    x = x ^ (x >> 17)
    return (x ^ (x << 5)).astype(_U)


def lfsr_seed(master, ids, bits: int):
    s = splitmix32(master ^ ids) & _U((1 << bits) - 1)
    return jnp.where(s == 0, _U(1), s)


def lfsr_shift(v, bits: int):
    return jnp.where((v & _U(1)) == 1, (v >> 1) ^ _U(TAPS[bits]), v >> 1)


def emit(v, bits: int, rand_bits: int):
    if bits > rand_bits:
        v = v >> (bits - rand_bits)
    elif bits < rand_bits:
        v = v << (rand_bits - bits)
    return v & _U((1 << rand_bits) - 1)


def lfsr_cycle(lanes, master, cycles, ids, bits: int, refresh: bool):
    """One cycle of a master-slave cluster: every lane shifts; after
    2^bits - 1 cycles the master steps and reseeds every lane."""
    lanes = lfsr_shift(lanes, bits)
    cycles = cycles + _U(1)
    if refresh:
        due = cycles >= _U((1 << bits) - 1)
        master = jnp.where(due, xorshift32(master), master)
        lanes = jnp.where(due, lfsr_seed(master, ids, bits), lanes)
        cycles = jnp.where(due, _U(0), cycles)
    return lanes, master, cycles


def cluster(seed: int, n_lanes: int, bits: int):
    """The per-model draw cluster: (lanes, master, cycles)."""
    master = _U(seed if seed else 0xDEADBEEF)
    ids = jnp.arange(n_lanes, dtype=_U)
    return lfsr_seed(master, ids, bits), master, _U(0)


def draw(st, size: int, bits: int, refresh: bool, rand_bits: int):
    """``size`` numbers: one lane value per lane per cycle, lanes in order,
    as many cycles as needed."""
    lanes, master, cycles = st
    ids = jnp.arange(lanes.shape[0], dtype=_U)
    out = []
    for _ in range(-(-size // lanes.shape[0])):
        lanes, master, cycles = lfsr_cycle(lanes, master, cycles, ids, bits,
                                           refresh)
        out.append(emit(lanes, bits, rand_bits))
    return (lanes, master, cycles), jnp.concatenate(out)[:size]
