"""Merge of two sorted runs — Pallas TPU kernel (the paper's Ph6 hot loop).

Two sorted rows a, b of width W are merged by the *bitonic merge network*:
``concat(a, reverse(b))`` is a bitonic sequence, so lg(2W) compare-exchange
substages produce the sorted 2W row. Each substage is one full-width
`jnp.where` on the VMEM tile — no gathers, no branches. The reversal and the
concatenation happen in the XLA wrapper (Mosaic has no lane reversal), so
the kernel body is the merge network alone.

This replaces the GPU "merge path" diagonal-partition idea (which needs
per-thread binary searches — a scalar-unit pattern) with the TPU-idiomatic
network formulation: same O(W lg W) work per pair, all lane-parallel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitonic.kernel import merge_network


def _merge_kernel(x_ref, o_ref):
    o_ref[...] = merge_network(x_ref[...])


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def merge_sorted_tiles(
    a: jnp.ndarray, b: jnp.ndarray, *, block_rows: int = 8, interpret: bool = False
) -> jnp.ndarray:
    """Pairwise-merge rows of two (rows, width) sorted arrays.

    VMEM per grid step = 4 · block_rows · width · itemsize (one double-width
    bitonic input, one double-width output); width must be a power of two
    ≥ 128.
    """
    rows, width = a.shape
    assert a.shape == b.shape
    assert width & (width - 1) == 0, "width must be a power of two"
    x = jnp.concatenate([a, b[:, ::-1]], axis=-1)  # bitonic rows
    grid = (pl.cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, 2 * width), lambda i: (i, 0))
    return pl.pallas_call(
        _merge_kernel,
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, 2 * width), a.dtype),
        interpret=interpret,
    )(x)
