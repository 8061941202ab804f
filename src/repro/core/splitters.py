"""Sampling, parallel sample-sort and splitter machinery (Fig. 1 steps 4-9).

Implements:

* deterministic *regular oversampling* — rp-1 evenly spaced keys + local max
  (paper Fig. 1 step 4, Lemma 5.1 padding analysis);
* randomized oversampling — s uniform positions per proc (Fig. 3 step 4);
* transparent duplicate tagging (§5.1.1): ONLY sample/splitter records carry
  explicit ``(processor, index)`` tags; local keys use their implicit
  position, so memory/comm overhead is o(n);
* parallel sample sort: ``gather`` (all_gather + fused stable lexicographic
  sort — optimal when p·s fits one core) or ``bitonic`` (distributed Batcher
  compare-split over the proc axis — the paper's scheme);
* ``searchsorted_tagged`` — vectorized binary search of tagged splitters into
  the local sorted run under the (key, proc, idx) order; monotone because the
  local run is sorted and local indices ascend.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import primitives as prim
from .types import SortConfig


Tagged = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]  # (keys, proc, idx)


def regular_sample(x_sorted: jnp.ndarray, cfg: SortConfig, axis: str) -> Tagged:
    """Deterministic regular oversampling: s evenly spaced keys (+ local max).

    Pads the local run to ``s·x`` with the max key (Lemma 5.1 proof) and takes
    segment right-boundaries; the tag index of a padded slot saturates at
    ``n_p - 1`` which reproduces "append the maximum" transparently.
    """
    n_p = x_sorted.shape[0]
    s, x = cfg.s, cfg.segment_len
    pos = (jnp.arange(1, s + 1) * x) - 1  # right boundary of each segment
    idx = jnp.minimum(pos, n_p - 1).astype(jnp.int32)
    keys = x_sorted[idx]
    me = prim.proc_id(axis).astype(jnp.int32)
    return keys, jnp.full((s,), me, jnp.int32), idx


def random_sample(
    x_sorted: jnp.ndarray, cfg: SortConfig, axis: str, rng: jax.Array
) -> Tagged:
    """Randomized oversampling: s uniform local positions, tagged, locally
    sorted (the run is sorted, so sorting the positions sorts the sample)."""
    n_p = x_sorted.shape[0]
    me = prim.proc_id(axis)
    k = jax.random.fold_in(rng, me)
    idx = jnp.sort(jax.random.randint(k, (cfg.s,), 0, n_p)).astype(jnp.int32)
    keys = x_sorted[idx]
    return keys, jnp.full((cfg.s,), me, jnp.int32), idx


# --------------------------------------------------------------- sample sort
def _merge_split_tagged(a: Tagged, b: Tagged, keep_low: jnp.ndarray) -> Tagged:
    """Bitonic compare-split: merge two sorted tagged runs, keep one half."""
    m = a[0].shape[0]
    cat = tuple(jnp.concatenate([ai, bi]) for ai, bi in zip(a, b))
    sk, sp, si = prim.lex_sort(cat, num_keys=3)
    low = (sk[:m], sp[:m], si[:m])
    high = (sk[m:], sp[m:], si[m:])
    return tuple(jnp.where(keep_low, lo, hi) for lo, hi in zip(low, high))


def sample_sort_bitonic(sample: Tagged, p: int, axis: str) -> Tagged:
    """Distributed Batcher bitonic sort of the tagged sample over the proc
    axis (Fig. 1 step 5 / [BSI]); local runs must already be sorted.

    lg p · (lg p + 1)/2 compare-split supersteps; each is one ppermute of the
    s-word sample plus an s·lg s local merge — matching the paper's
    2s(lg²p+lg p)/2 computation and (lg²p+lg p)(L+gs)/2 communication charge.
    """
    lgp = int(math.log2(p))
    me = prim.proc_id(axis)
    cur = sample
    for i in range(lgp):
        for j in range(i, -1, -1):
            partner = 1 << j
            other = prim.exchange_with(cur, partner, axis, p=p)
            up = ((me >> (i + 1)) & 1) == 0
            lower_half = ((me >> j) & 1) == 0
            keep_low = jnp.equal(up, lower_half)
            cur = _merge_split_tagged(cur, other, keep_low)
    return cur


def sample_sort_gather(sample: Tagged, axis: str) -> Tagged:
    """All-gather the o(n) sample and sort it with one fused stable
    lexicographic sort — the sequential-sample-sort choice the paper blesses
    for architectures where p·s fits one node (§5, final remark)."""
    gathered = tuple(lax.all_gather(a, axis).reshape(-1) for a in sample)
    return prim.lex_sort(gathered, num_keys=3)


def select_splitters(cfg: SortConfig, sample: Tagged, axis: str, mode: str) -> Tagged:
    """Fig. 1 step 6: p-1 evenly spaced splitters from the sorted sample.

    ``gather`` mode: the sorted sample is replicated; take positions i·s-1.
    ``bitonic`` mode: splitter i is the *last* sample key held by proc i-1;
    one all_gather of a single record per proc broadcasts all splitters
    (Fig. 1 step 7's broadcast, one superstep of h = O(p)).
    """
    p, s = cfg.p, cfg.s
    if mode == "gather":
        pos = jnp.arange(1, p) * s - 1
        return tuple(a[pos] for a in sample)
    # bitonic mode: local run of s sorted records per proc.
    last = tuple(a[-1] for a in sample)
    allp = tuple(lax.all_gather(a, axis) for a in last)  # (p,) each
    return tuple(a[:-1] for a in allp)


# ---------------------------------------------------- tagged binary search
@prim.superstep("ph4_partition")
def searchsorted_tagged(
    x_sorted: jnp.ndarray,
    splitters: Tagged,
    axis: str,
) -> jnp.ndarray:
    """Partition boundaries of the local run induced by tagged splitters.

    Returns ``b`` of shape (p+1,) with b[0]=0, b[p]=n_p; bucket i is
    x[b[i]:b[i+1]]. Local element j on proc ``me`` belongs left of splitter
    (ks, ps, is) iff (x[j], me, j) < (ks, ps, is) lexicographically — the
    §5.1.1 comparator. Count via vectorized binary search (monotone predicate
    since the run is sorted and j ascends), ⌈lg(n_p+1)⌉ steps.
    """
    n_p = x_sorted.shape[0]
    sk, sp, si = splitters
    me = prim.proc_id(axis).astype(jnp.int32)
    nq = sk.shape[0]
    lo = jnp.zeros((nq,), jnp.int32)
    hi = jnp.full((nq,), n_p, jnp.int32)
    steps = max(1, math.ceil(math.log2(n_p + 1)))

    def body(_, lohi):
        lo, hi = lohi
        active = lo < hi  # converged lanes must not move (mid==hi is OOB)
        mid = (lo + hi) // 2
        xm = x_sorted[jnp.clip(mid, 0, n_p - 1)]
        less = prim.lex_less(xm, me, mid, sk, sp, si)
        lo = jnp.where(active & less, mid + 1, lo)
        hi = jnp.where(active & ~less, mid, hi)
        return lo, hi

    lo, hi = lax.fori_loop(0, steps, body, (lo, hi))
    b = jnp.concatenate([jnp.zeros((1,), jnp.int32), lo, jnp.full((1,), n_p, jnp.int32)])
    return b


def splitters_from_sorted_sample(
    cfg: SortConfig, sample: Tagged, axis: str
) -> Tagged:
    """Convenience: run the configured sample sort + splitter selection."""
    if cfg.sample_sort == "gather":
        sorted_sample = sample_sort_gather(sample, axis)
        return select_splitters(cfg, sorted_sample, axis, "gather")
    sorted_sample = sample_sort_bitonic(sample, cfg.p, axis)
    return select_splitters(cfg, sorted_sample, axis, "bitonic")


@prim.superstep("ph3_splitters")
def splitter_stage(
    x_sorted: jnp.ndarray, cfg: SortConfig, axis: str, rng: jax.Array | None = None
) -> Tagged:
    """Full Ph3 for ``cfg.algorithm``: sampling + sample sort + selection.

    The single splitter pipeline shared by the sort bodies, the resumable
    route stage and the phase-decomposed benchmark callables. ``det`` is
    deterministic (and hence capacity-tier-invariant — it runs in the
    prepare stage); ``iran`` draws its sample from ``rng``, so the route
    stage re-enters here with a per-tier folded key.
    """
    if cfg.algorithm == "det":
        sample = regular_sample(x_sorted, cfg, axis)
    else:
        sample = random_sample(x_sorted, cfg, axis, rng)
    return splitters_from_sorted_sample(cfg, sample, axis)
