"""Weights made from the seed, shared by the program's side and the reference.

Every tensor is a pure function of ``(seed, role, layer)``: the role is
the reference's own name for the tensor ("q_proj", "s2b1_3x3", ...), so
the reference regenerates any layer's weights on its own, without
touching what the program holds. Values are small integers times a power
of two, so a bf16 value and its float32 copy are the same number.

An N:M weight is drawn directly in compressed form: ``vals`` of shape
``(K*n/m, N)`` and ``idx`` (int8 in-block positions, ascending within each
block of ``m`` rows). :func:`expand` is the reference's own expansion
to the dense ``(K, N)`` matrix.
"""
from __future__ import annotations

import itertools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

LEVELS = 127  # values are integers in [-LEVELS, LEVELS] times 2**-e
_UNIFORM_STD = LEVELS / math.sqrt(3.0)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (PRNGKey keeps only 32)."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def role_id(role: str) -> int:
    """The number a role's name folds into the key."""
    return zlib.crc32(role.encode())


def role_key(key: jax.Array, role, layer=0) -> jax.Array:
    """Key of one tensor: ``role`` is its name or that name's ``role_id``,
    which may be traced, as ``layer`` may (inside ``lax.map``)."""
    rid = role_id(role) if isinstance(role, str) else role
    return jax.random.fold_in(jax.random.fold_in(key, rid), layer)


def pow2_scale(std: float) -> float:
    """The power of two that gives the integer grid a std near ``std``."""
    return 2.0 ** round(math.log2(std / _UNIFORM_STD))


def uniform_ints(key: jax.Array, shape, lo: int, hi: int) -> jax.Array:
    """int32 in [lo, hi): 32 random bits mod the count, which compiles to
    far less code on a TPU than ``jax.random.randint`` (the bias, under
    2**-24 here, is of no account for weights)."""
    return lo + (jax.random.bits(key, shape) % (hi - lo)).astype(jnp.int32)


def int_grid(key: jax.Array, shape, std: float, dtype) -> jax.Array:
    """Integers in [-LEVELS, LEVELS] times a power of two near ``std``."""
    q = uniform_ints(key, shape, -LEVELS, LEVELS + 1)
    return (q.astype(jnp.float32) * pow2_scale(std)).astype(dtype)


def uniform_around_one(key: jax.Array, shape, dtype) -> jax.Array:
    """Norm scales: 1 + k/128 for integer k in [-64, 64]."""
    q = uniform_ints(key, shape, -64, 65)
    return (1.0 + q.astype(jnp.float32) / 128.0).astype(dtype)


def _pairs(n: int, m: int) -> np.ndarray:
    """Every ascending choice of n positions among m, one per row."""
    return np.asarray(list(itertools.combinations(range(m), n)), np.int8)


def nm_weight(key: jax.Array, k: int, n_out: int, n: int, m: int,
              std: float, dtype):
    """(vals, idx) of a random N:M weight compressed along K."""
    if k % m:
        raise ValueError(f"K={k} is not a multiple of m={m}")
    kv, ki = jax.random.split(key)
    pairs = _pairs(n, m)
    choice = uniform_ints(ki, (k // m, n_out), 0, len(pairs))
    slots = [sum(jnp.where(choice == p, int(pairs[p, j]), 0)
                 for p in range(len(pairs))) for j in range(n)]
    idx = jnp.stack(slots, axis=1).reshape(k // m * n, n_out)  # (K/m, n, N)
    vals = int_grid(kv, (k // m * n, n_out), std, dtype)
    return vals, idx.astype(jnp.int8)


def expand(vals: jax.Array, idx: jax.Array, n: int, m: int) -> jax.Array:
    """Dense (K, N) float32 matrix of a compressed (vals, idx) pair."""
    kc, n_out = vals.shape
    blocks = kc // n
    v = vals.astype(jnp.float32).reshape(blocks, n, n_out)
    i = idx.astype(jnp.int32).reshape(blocks, n, n_out)
    pos = jnp.arange(m, dtype=jnp.int32)[None, None, :, None]
    dense = jnp.sum(jnp.where(i[:, :, None, :] == pos, v[:, :, None, :], 0.0),
                    axis=1)                               # (blocks, m, N)
    return dense.reshape(blocks * m, n_out)
