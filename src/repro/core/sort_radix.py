"""Radix h-relation — count-then-distribute routing for integer keys.

For integer keys, sampling is pure overhead (*A study of integer sorting on
multicores*, Gerbessiotis): exact bucket boundaries are computable in ONE
counting pass over the locally sorted run, so the splitter superstep (Ph3)
disappears, there is no oversampling parameter, and — decisively for the
capacity ladder — the per-destination counts are known *before any data
moves*. The (p,)-word count superstep of the fused h-relation (routing.py)
already communicates them; the launch driver additionally host-reads the
prepared boundaries and sizes the single rung to the true maxima, so a
``route="radix"`` sort retries zero times by construction.

Destination function
--------------------
Keys are mapped through :func:`radix._to_unsigned_order_preserving` (the
sign-bit bias that makes unsigned compare agree with signed order — the same
map every LSD pass of ``radix_argsort`` uses), then bucketed over the
*observed global key range*::

    lo, hi = pmin(u_local_min), pmax(u_local_max)   # two scalar collectives
    width  = (hi - lo) // p + 1
    dest   = (u - lo) // width                      # in [0, p-1]

Range-normalising instead of taking raw top bits is what makes the flagship
workloads work: small dense domains (expert ids, segment-tag composites)
share all their high bits, and a static MSB split would aim every key at one
processor. ``dest`` is monotone in key order, so bucket i's keys are all ≤
bucket i+1's (the concatenated output is globally sorted) and equal keys
share a destination (stability is preserved through the source-ordered
exchange). The boundaries of the sorted run are then a vectorised
``searchsorted`` — exactly the Ph4 shape the shared Ph5/Ph6 tail
(:func:`routing.route_and_merge`) consumes, so the radix route rides the
same fused word-packed ``a2a_dense`` exchange and merge tail as the sample
route. Radix buckets arrive *disjoint* in key range, so the merge tail only
ever interleaves equal-bucket runs — per-bucket local passes, never a
global fix-up.

Both collectives live in ``prepare``: they are tier-invariant, deterministic
(no rng), and their result is carried host-readably in
``PreparedSort.splits`` for the exact-capacity launch path.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import routing
from .local_sort import local_sort
from .radix import _to_unsigned_order_preserving
from .types import PreparedSort, SortConfig


def radix_boundaries(
    xs: jnp.ndarray, p: int, axis: str
) -> jnp.ndarray:
    """Counted (p+1,) bucket boundaries of the locally sorted run ``xs``.

    b[0] = 0, b[p] = n_p; destination i receives ``xs[b[i]:b[i+1]]``. Costs
    two scalar collectives (global min/max of the bias-mapped keys) plus one
    vectorised binary search — no sample, no splitter sort.
    """
    u = _to_unsigned_order_preserving(xs)
    lo = lax.pmin(u[0], axis)  # xs is sorted: u[0]/u[-1] are local extremes
    hi = lax.pmax(u[-1], axis)
    width = (hi - lo) // u.dtype.type(p) + u.dtype.type(1)
    dest = ((u - lo) // width).astype(jnp.int32)  # monotone, in [0, p-1]
    return jnp.searchsorted(
        dest, jnp.arange(p + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)


def host_send_counts(bounds) -> np.ndarray:
    """(p, p) per-(src, dst) send counts from the counted boundaries.

    Host-side companion of :func:`radix_boundaries`: ``bounds`` is the
    prepared ``splits[0]`` — (p, p+1) under the global layout, one row per
    source — and differencing each row yields the exact h-relation count
    matrix. Shared by the launch driver's single-rung capacity sizing and
    the tracer's per-(src, dst) byte-volume record; reading it is the radix
    launch path's only host sync.
    """
    return np.diff(np.asarray(bounds), axis=1)


def prepare_radix_spmd(
    x: jnp.ndarray,
    cfg: SortConfig,
    axis: str,
    values: Sequence[jnp.ndarray] = (),
    rng: jax.Array | None = None,  # unused: the radix route draws no sample
) -> PreparedSort:
    """Tier-invariant stage: Ph2 stable local sort + the counting pass.

    Unlike the sample route, the boundary computation is tier-invariant too
    (capacity never enters it), so it belongs here — and carrying it in
    ``splits`` lets the launch driver host-read the exact counts and size
    the single capacity rung before dispatching the route stage.
    """
    del rng
    xs, vals = local_sort(x, cfg.local_sort, values)  # Ph2
    bounds = radix_boundaries(xs, cfg.p, axis)
    return PreparedSort(xs=xs, vals=tuple(vals), splits=(bounds,))


def route_radix_spmd(
    prep: PreparedSort,
    cfg: SortConfig,
    axis: str,
    rng: jax.Array | None = None,  # unused: nothing random to redraw
) -> Tuple[jnp.ndarray, List[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    """Tier-dependent stages: Ph5 fused h-relation + Ph6 merge tail.

    Ph3/Ph4 are already done — the counted boundaries ride in from
    ``prep.splits``. The shared tail keeps its overflow detection, but with
    a host-counted capacity rung the flag is statically false.
    """
    del rng
    return routing.route_and_merge(
        prep.xs, prep.splits[0], cfg, axis, list(prep.vals)
    )


def sort_radix_spmd(
    x: jnp.ndarray,
    cfg: SortConfig,
    axis: str,
    values: Sequence[jnp.ndarray] = (),
    rng: jax.Array | None = None,
) -> Tuple[jnp.ndarray, List[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    return route_radix_spmd(prepare_radix_spmd(x, cfg, axis, values), cfg, axis, rng)
