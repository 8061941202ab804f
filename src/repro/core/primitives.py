"""SPMD primitive operations (paper §4) mapped onto JAX collectives.

The paper's Lemmas 4.1/4.2 build pipelined t-ary broadcast / parallel-prefix
trees because on a torus a naive broadcast costs g·n·lg p. XLA's collectives
already lower to bandwidth-optimal ICI ring/tree algorithms, so the BSP
*primitives* map to single calls here; their BSP *cost accounting* lives in
``core/bsp.py`` so the model-validation benchmarks can still price them.

All functions run inside an ``axis_name`` region — under ``jax.vmap``
(simulated processors) or ``jax.shard_map`` (real devices) interchangeably.

:func:`superstep` names a BSP superstep on the device: every op a decorated
stage emits carries the superstep's name in its ``op_name`` metadata.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

#: the superstep scopes, in pipeline order (``radix_count`` is the radix
#: route's counting pass, which takes the place of Ph3/Ph4)
SUPERSTEPS = (
    "ph2_local_sort",
    "ph3_splitters",
    "radix_count",
    "ph4_partition",
    "ph5_exchange",
    "ph6_merge",
)


def superstep(name: str) -> Callable[[Callable], Callable]:
    """Run the decorated stage inside ``jax.named_scope(name)``.

    Metadata only: the ops, their fusion and the result are unchanged, so
    the device trace can split time by superstep at no cost. Both runners
    call the same stage functions, so ``vmap`` and ``shard_map`` programs
    carry the same names (``jit(run)/vmap(ph5_exchange)/...``). A fresh
    scope is entered per call, so concurrent tracing threads never share
    one.
    """
    assert name in SUPERSTEPS, name

    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return scoped

    return wrap


def proc_id(axis: str) -> jnp.ndarray:
    return lax.axis_index(axis)


def broadcast_from(x: jnp.ndarray, src: int, axis: str) -> jnp.ndarray:
    """Lemma 4.1 analogue: one-superstep broadcast of ``x`` from proc ``src``."""
    contrib = jnp.where(proc_id(axis) == src, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis)


def exclusive_cumsum(x: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    c = jnp.cumsum(x, axis=axis)
    return c - x


def prefix_counts(local_counts: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Lemma 4.2 analogue: p independent parallel prefixes over the proc axis.

    ``local_counts``: (m,) per proc. Returns (m,) exclusive prefix over
    processors (sum of counts on lower-ranked procs), via a masked psum —
    one superstep, h = m words.
    """
    me = proc_id(axis)
    gathered = lax.all_gather(local_counts, axis)  # (p, m)
    p = gathered.shape[0]
    mask = (jnp.arange(p) < me)[:, None]
    return jnp.sum(jnp.where(mask, gathered, 0), axis=0)


def ppermute_shift(x, axis: str, shift: int = 1, *, p: int | None = None):
    """Rotate values around the ring by ``shift`` (one superstep).

    ``p`` is the static axis size; callers thread it from their SortConfig
    (the permutation table must be built at trace time).
    """
    p = lax.axis_size(axis) if p is None else p
    perm = [(i, (i + shift) % p) for i in range(p)]
    if isinstance(x, (tuple, list)):
        return type(x)(lax.ppermute(v, axis, perm) for v in x)
    return lax.ppermute(x, axis, perm)


def exchange_with(x, partner_xor: int, axis: str, *, p: int | None = None):
    """Pairwise exchange with the XOR partner (bitonic compare-split step)."""
    p = lax.axis_size(axis) if p is None else p
    perm = [(i, i ^ partner_xor) for i in range(p)]
    if isinstance(x, (tuple, list)):
        return type(x)(lax.ppermute(v, axis, perm) for v in x)
    return lax.ppermute(x, axis, perm)


def lex_sort(operands: Sequence[jnp.ndarray], num_keys: int) -> tuple:
    """Stable lexicographic sort on multiple operands (§5.1.1 tagged compare)."""
    return lax.sort(tuple(operands), num_keys=num_keys, is_stable=True)


def lex_less(ka, pa, ia, kb, pb, ib):
    """(key, proc, idx) lexicographic strict less-than — §5.1.1's comparator."""
    return (ka < kb) | ((ka == kb) & ((pa < pb) | ((pa == pb) & (ia < ib))))
