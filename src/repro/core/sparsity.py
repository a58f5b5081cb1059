"""N:M structured sparsity primitives.

The paper's data format (Fig. 1b): within every block of M consecutive
elements along a row of the sparse matrix A, at most N are non-zero. The
compressed representation stores, per block, exactly N ``values`` and N
``col_idx`` entries (zero-padded when fewer than N non-zeros exist). The
indices are *bounded*: ``col_idx in [0, M)`` relative to the block — the
property that makes register-file (here: VMEM) residency of the dense
operand possible.

Orientation note: the paper compresses A along its rows (the contraction
dimension k of C = A @ B). For transformer weights we use y = x @ W with W
sparse along K (its rows), i.e. per *output column* of W each K-block of M
holds at most N non-zeros. ``axis`` selects the compressed axis.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "NMConfig",
    "value_bytes_of",
    "prune_mask_nm",
    "apply_mask",
    "compress_nm",
    "decompress_nm",
    "check_nm_pattern",
    "random_nm_matrix",
    "pad_compressed_kn",
]


@dataclasses.dataclass(frozen=True)
class NMConfig:
    """N:M structured sparsity configuration.

    n: max non-zeros per block.
    m: block size (consecutive elements along the compressed axis).
    """

    n: int = 2
    m: int = 4

    def __post_init__(self):
        if not (1 <= self.n < self.m):
            raise ValueError(f"need 1 <= n < m, got {self.n}:{self.m}")

    @property
    def density(self) -> float:
        return self.n / self.m

    @property
    def tag(self) -> str:
        return f"{self.n}:{self.m}"

    def byte_ratio(self, value_bytes: int, dense_value_bytes: int = 2) -> float:
        """Compressed-bytes ratio vs a dense bf16 weight.

        ``value_bytes`` is the *stored* dtype of the kept values (2 for
        bf16, 1 for int8, 4 for f32) — explicit, because the old 2-byte
        default silently mis-accounted quantized weights. Each kept
        value also carries one int8 index byte; ``dense_value_bytes`` is
        the dense baseline's dtype (bf16 by default). Per-output-channel
        scale bytes are O(N) and amortize to ~0 per weight — use
        :func:`repro.core.cost_model.tpu_indexmac_cost` when they
        matter.
        """
        return (self.n * (value_bytes + 1)) / (self.m * dense_value_bytes)


def value_bytes_of(dtype) -> int:
    """Bytes per stored value for a weight dtype — the explicit argument
    every byte-accounting caller threads instead of assuming bf16."""
    return int(jnp.dtype(dtype).itemsize)


def _move_axis_last(x: jax.Array, axis: int) -> jax.Array:
    return jnp.moveaxis(x, axis, -1)


def _block_slots(w: jax.Array, m: int, axis: int) -> list[jax.Array]:
    """The m positions of every M-block along ``axis`` as m arrays:
    ``out[j] = w[..., j::m, ...]``. Working on these instead of a
    ``(..., blocks, m)`` view keeps M off the minor axis, which a TPU
    would pad to 128 lanes (32x the bytes for M = 4)."""
    return [jax.lax.slice_in_dim(w, j, None, stride=m, axis=axis)
            for j in range(m)]


def _interleave(parts: Sequence[jax.Array], axis: int) -> jax.Array:
    """Inverse of :func:`_block_slots` for k parts: position j of every
    k-block along ``axis`` comes from ``parts[j]``."""
    out = jnp.stack(parts, axis=axis + 1)
    shape = list(parts[0].shape)
    shape[axis] *= len(parts)
    return out.reshape(shape)


def _check_axis(w, cfg: NMConfig, axis: int) -> int:
    axis = axis % w.ndim
    if w.shape[axis] % cfg.m != 0:
        raise ValueError(
            f"axis {axis} size {w.shape[axis]} not divisible by M={cfg.m}"
        )
    return axis


def prune_mask_nm(w: jax.Array, cfg: NMConfig, axis: int = 0) -> jax.Array:
    """Magnitude-based N:M mask: keep the top-``n`` |w| in every ``m``-block.

    Returns a boolean mask with w's shape. Deterministic: ties are broken
    by position (the lower position wins), as a stable sort on
    (-|w|, position) would rank them.
    """
    axis = _check_axis(w, cfg, axis)
    a = [jnp.abs(x) for x in _block_slots(w, cfg.m, axis)]
    keep = []
    for j in range(cfg.m):
        # rank of position j in its block: larger magnitudes, and equal
        # magnitudes at lower positions, come first
        rank = sum(((a[i] > a[j]) | (a[i] == a[j]) if i < j
                    else a[i] > a[j]).astype(jnp.int32)
                   for i in range(cfg.m) if i != j)
        keep.append(rank < cfg.n)
    return _interleave(keep, axis)


def apply_mask(w: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.where(mask, w, jnp.zeros_like(w))


def compress_nm(w: jax.Array, cfg: NMConfig, axis: int = 0):
    """Compress an (already N:M-sparse) matrix.

    Returns (values, idx):
      values: same dtype as w, shape = w.shape with ``axis`` shrunk by n/m.
      idx:    int8, same shape as values, entries in [0, m).

    Within each block the kept entries are ordered by ascending position
    (paper Fig. 1b stores them left-to-right). Blocks with fewer than n
    non-zeros fill the remaining slots with their zero entries, again by
    ascending position (a zero value makes the index a don't-care).
    """
    axis = _check_axis(w, cfg, axis)
    slots = _block_slots(w, cfg.m, axis)
    nz = [x != 0 for x in slots]
    nnz = sum(z.astype(jnp.int32) for z in nz)
    # slot of position j: non-zeros first by position, then zeros
    slot_of = []
    nz_before = jnp.zeros_like(nnz)
    for j in range(cfg.m):
        slot_of.append(jnp.where(nz[j], nz_before, nnz + j - nz_before))
        nz_before = nz_before + nz[j].astype(jnp.int32)
    values, idx = [], []
    for s in range(cfg.n):
        v = jnp.zeros_like(slots[0])
        i = jnp.zeros(slots[0].shape, jnp.int8)
        for j in range(cfg.m):
            v = jnp.where(slot_of[j] == s, slots[j], v)
            i = jnp.where(slot_of[j] == s, jnp.int8(j), i)
        values.append(v)
        idx.append(i)
    return _interleave(values, axis), _interleave(idx, axis)


def decompress_nm(
    values: jax.Array, idx: jax.Array, cfg: NMConfig, axis: int = 0
) -> jax.Array:
    """Inverse of :func:`compress_nm` (zero-padded positions stay zero)."""
    vl = _move_axis_last(values, axis)
    il = _move_axis_last(idx, axis)
    lead = vl.shape[:-1]
    nblocks = vl.shape[-1] // cfg.n
    v = vl.reshape(*lead, nblocks, cfg.n)
    i = il.reshape(*lead, nblocks, cfg.n).astype(jnp.int32)
    # one-hot expand: out[..., b, j] = sum_n v[..., b, n] * (i[..., b, n]==j)
    onehot = jax.nn.one_hot(i, cfg.m, dtype=v.dtype)  # (..., b, n, m)
    dense = jnp.einsum("...bn,...bnm->...bm", v, onehot)
    dense = dense.reshape(*lead, nblocks * cfg.m)
    return jnp.moveaxis(dense, -1, axis)


def pad_compressed_kn(
    values: jax.Array, idx: jax.Array, *, kc_pad: int, n_pad: int
):
    """Zero-pad a compressed (Kc, N) pair to (kc_pad, n_pad).

    Appended rows are whole zero blocks (callers pad K by multiples of M,
    so Kc grows by multiples of N) and appended columns are zero output
    channels; a zero value makes its index a don't-care, so the padded
    pair decompresses to the original W bordered by zeros.
    """
    kc, nn = values.shape
    if kc_pad < kc or n_pad < nn:
        raise ValueError(
            f"pad target ({kc_pad}, {n_pad}) smaller than ({kc}, {nn})"
        )
    if (kc_pad, n_pad) == (kc, nn):
        return values, idx
    pad = ((0, kc_pad - kc), (0, n_pad - nn))
    return jnp.pad(values, pad), jnp.pad(idx, pad)


def check_nm_pattern(w: jax.Array | np.ndarray, cfg: NMConfig, axis: int = 0) -> bool:
    """True iff every M-block along ``axis`` has at most N non-zeros."""
    w = np.asarray(w)
    wl = np.moveaxis(w, axis, -1)
    blocks = wl.reshape(*wl.shape[:-1], wl.shape[-1] // cfg.m, cfg.m)
    return bool(((blocks != 0).sum(-1) <= cfg.n).all())


def random_nm_matrix(
    key: jax.Array,
    shape: Sequence[int],
    cfg: NMConfig,
    axis: int = 0,
    dtype=jnp.float32,
) -> jax.Array:
    """Random dense-valued matrix that satisfies the N:M pattern exactly
    (every block has exactly N non-zeros) — used by tests and benchmarks."""
    k1, _ = jax.random.split(key)
    w = jax.random.normal(k1, tuple(shape), dtype=jnp.float32)
    # Avoid exact zeros so "exactly N per block" holds post-masking.
    w = jnp.where(w == 0, 1e-3, w)
    mask = prune_mask_nm(w, cfg, axis=axis)
    return apply_mask(w, mask).astype(dtype)
