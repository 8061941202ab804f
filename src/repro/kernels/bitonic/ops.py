"""jit'd public wrappers for the bitonic Pallas kernel.

Handles padding to a power-of-two lane width (≥128), row batching, the
single-tile / multi-tile split (tiles sorted in-kernel, then merged with
rank merges), and CPU fallback to ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.types import sentinel_for

from . import kernel

#: widest single-tile sort: (8 rows, 16384 lanes) f32 = 1 MB VMEM blocks.
MAX_WIDTH = 16384
_SUPPORTED = (jnp.int32, jnp.uint32, jnp.float32, jnp.bfloat16)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pow2_at_least(n: int, floor: int = 128) -> int:
    w = floor
    while w < n:
        w *= 2
    return w


def supports(x: jnp.ndarray) -> bool:
    return x.ndim in (1, 2) and x.dtype in [jnp.dtype(d) for d in _SUPPORTED]


@jax.jit
def sort(x: jnp.ndarray) -> jnp.ndarray:
    """Sort along the last axis via the in-VMEM bitonic network.

    Widths ≤ MAX_WIDTH sort in one tile; larger rows are split into
    MAX_WIDTH tiles, kernel-sorted, and combined by a rank-merge tree.
    """
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None, :]
    rows, n = x.shape
    sent = sentinel_for(x.dtype)
    if n <= MAX_WIDTH:
        w = _pow2_at_least(n)
        xp = jnp.pad(x, ((0, 0), (0, w - n)), constant_values=sent)
        out = kernel.bitonic_sort_tiles(xp, interpret=_interpret())[:, :n]
        return out[0] if squeeze else out

    # multi-tile: sort MAX_WIDTH tiles in-kernel, then merge pairs.
    w = _pow2_at_least(n, MAX_WIDTH)
    xp = jnp.pad(x, ((0, 0), (0, w - n)), constant_values=sent)
    t = w // MAX_WIDTH
    tiles = kernel.bitonic_sort_tiles(
        xp.reshape(rows * t, MAX_WIDTH), interpret=_interpret()
    ).reshape(rows, t, MAX_WIDTH)
    while tiles.shape[1] > 1:
        a, b = tiles[:, 0::2], tiles[:, 1::2]
        tiles = _rank_merge(a, b)
    out = tiles[:, 0, :n]
    return out[0] if squeeze else out


def _rank_merge(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Merge sorted runs pairwise: out position = own idx + rank in other."""
    *lead, m = a.shape
    ra = jax.vmap(jnp.searchsorted, (0, 0))(b.reshape(-1, m), a.reshape(-1, m))
    rb = jax.vmap(functools.partial(jnp.searchsorted, side="right"), (0, 0))(
        a.reshape(-1, m), b.reshape(-1, m)
    )
    i = jnp.arange(m)
    pos_a, pos_b = i + ra, i + rb
    flat = a.shape[0] * a.shape[1] if a.ndim == 3 else a.shape[0]
    out = jnp.zeros((flat, 2 * m), a.dtype)
    out = out.at[jnp.arange(flat)[:, None], pos_a].set(a.reshape(-1, m))
    out = out.at[jnp.arange(flat)[:, None], pos_b].set(b.reshape(-1, m))
    return out.reshape(*lead, 2 * m)


@jax.jit
def sort_kv(keys: jnp.ndarray, vals: jnp.ndarray):
    """Key-value sort along the last axis (single-tile widths only)."""
    squeeze = keys.ndim == 1
    if squeeze:
        keys, vals = keys[None, :], vals[None, :]
    rows, n = keys.shape
    if n > MAX_WIDTH:
        order = jnp.argsort(keys, axis=-1, stable=True)  # fallback
        out = jnp.take_along_axis(keys, order, -1), jnp.take_along_axis(vals, order, -1)
    else:
        w = _pow2_at_least(n)
        sent = sentinel_for(keys.dtype)
        kp = jnp.pad(keys, ((0, 0), (0, w - n)), constant_values=sent)
        vp = jnp.pad(vals, ((0, 0), (0, w - n)))
        ko, vo = kernel.bitonic_sort_kv_tiles(kp, vp, interpret=_interpret())
        out = ko[:, :n], vo[:, :n]
    return (out[0][0], out[1][0]) if squeeze else out
