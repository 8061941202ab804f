"""In-VMEM Batcher bitonic sorting network — Pallas TPU kernel.

The paper's Ph2 hot loop (85-90% of T3D runtime) is a scalar quicksort. The
TPU-native analogue is a *sorting network over full vector registers*: every
compare-exchange stage is two lane rotations + `jnp.where` on an
(rows, width) VMEM tile, so the VPU processes 8×128 lanes per cycle with
zero data-dependent control flow. Work is Θ(n lg² n) vs quicksort's Θ(n lg n) — the standard TPU
trade (DESIGN.md §7): the lg(n)/2 work inflation is paid for by lane
parallelism and the absence of branches.

Layout: width must be a power of two (callers pad with the dtype sentinel —
`ops.py` handles this) and ≥ 128 so the lane dimension stays MXU/VPU aligned.
The row dimension batches independent sorts (grid over row blocks).

The compare-exchange pairing `i ↔ i^j` is realized *without gathers* and
without reshaping the lane axis (Mosaic cannot split it): each lane fetches
its partner with two lane rotations, ``x[i + j]`` where the lane index has
bit j clear ("low" half of its pair) and ``x[i - j]`` where it is set. The
direction bit ``(i & k) == 0`` comes from the same lane iota. These network
functions use ``pltpu.roll`` and so run only inside a Pallas kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _stage(lane, k: int, j, keys, *vals):
    """One compare-exchange substage (partner = lane XOR j, region size k).

    Swaps exactly when the (lower, upper) pair is out of order for its
    region's direction — strict compares, so equal keys (and NaNs) stay put.
    Every array in ``vals`` follows the key swaps. ``j`` may be traced.
    """
    w = keys.shape[-1]
    low = (lane & j) == 0

    def partner(a):
        return jnp.where(low, pltpu.roll(a, w - j, 1), pltpu.roll(a, j, 1))

    other = partner(keys)
    # lower element of a descending pair, or upper element of an ascending one
    flip = low ^ ((lane & k) == 0)
    # boolean algebra, not a select: Mosaic cannot select between i1 vectors
    swap = (flip & (keys < other)) | (~flip & (keys > other))
    return (
        jnp.where(swap, other, keys),
        *(jnp.where(swap, partner(v), v) for v in vals),
    )


def _merge_steps(lane, k: int, h: int, arrays):
    """Substages j = h, h/2, ..., 1 of region size k, as one loop.

    A loop with a traced ``j`` instead of lg(h) unrolled stages keeps the
    compile time and the kernel's live VMEM temporaries to one stage's worth.
    """

    def body(u, arrays):
        return _stage(lane, k, jnp.int32(h) >> u, *arrays)

    return jax.lax.fori_loop(0, h.bit_length(), body, arrays)


def _sort_arrays(arrays):
    keys = arrays[0]
    lane, w = _lanes(keys), keys.shape[-1]
    k = 2
    while k <= w:
        arrays = _merge_steps(lane, k, k // 2, arrays)
        k *= 2
    return arrays


def _lanes(x):
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)


def sort_network(x: jnp.ndarray) -> jnp.ndarray:
    """Full bitonic sort along the last axis (width = power of two)."""
    return _sort_arrays((x,))[0]


def merge_network(x: jnp.ndarray) -> jnp.ndarray:
    """Bitonic *merge* of a bitonic row (ascending run ++ descending run)."""
    w = x.shape[-1]
    # k > w ⇒ every region ascending
    return _merge_steps(_lanes(x), 2 * w, w // 2, (x,))[0]


def sort_network_kv(keys: jnp.ndarray, vals: jnp.ndarray):
    return _sort_arrays((keys, vals))


# ------------------------------------------------------------- pallas_call
def _sort_kernel(x_ref, o_ref):
    o_ref[...] = sort_network(x_ref[...])


def _sort_kv_kernel(k_ref, v_ref, ko_ref, vo_ref):
    ko, vo = sort_network_kv(k_ref[...], v_ref[...])
    ko_ref[...] = ko
    vo_ref[...] = vo


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bitonic_sort_tiles(
    x: jnp.ndarray, *, block_rows: int = 8, interpret: bool = False
) -> jnp.ndarray:
    """Sort each row of (rows, width) independently; width a power of two.

    VMEM working set per grid step = 2 · block_rows · width · itemsize;
    the default (8, ≤16384) f32 tile is 1 MB — comfortably inside the
    ~16 MB/core v5e VMEM while leaving room for double buffering.
    """
    rows, width = x.shape
    assert width & (width - 1) == 0, "width must be a power of two"
    grid = (pl.cdiv(rows, block_rows),)
    return pl.pallas_call(
        _sort_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, width), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, width), x.dtype),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bitonic_sort_kv_tiles(
    keys: jnp.ndarray,
    vals: jnp.ndarray,
    *,
    block_rows: int = 8,
    interpret: bool = False,
):
    rows, width = keys.shape
    assert width & (width - 1) == 0, "width must be a power of two"
    grid = (pl.cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, width), lambda i: (i, 0))
    return pl.pallas_call(
        _sort_kv_kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct((rows, width), keys.dtype),
            jax.ShapeDtypeStruct((rows, width), vals.dtype),
        ),
        interpret=interpret,
    )(keys, vals)
