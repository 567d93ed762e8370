"""Seeds of a run's inputs and tenant programs, made from ``--seed``.

Everything the program under test receives is made by the kind module of
the cell's configuration (``kinds/<kind>.py``: ``motifs``, ``rows``,
``program``) from these seeds; the program receives only the arrays it
returns.
"""
from __future__ import annotations

import jax
import numpy as np


def seed32(seed: int, *salt: int) -> int:
    """A 32-bit seed derived from ``seed`` (any size) and a salt."""
    return int(np.random.SeedSequence([int(seed), *salt]).generate_state(1)[0])


def key(seed: int, *salt: int) -> jax.Array:
    return jax.random.PRNGKey(seed32(seed, *salt))


def tenant_seed(seed: int, tenant: int) -> int:
    """The seed the program derives a tenant's random stream from (kept
    below 2^30 so every seed argument of the program takes it)."""
    return seed32(seed, 3, tenant) >> 2
