"""SORT_RAN_BSP (Fig. 2) — classic one-round randomized sample sort.

The traditional pattern the paper *departs from*: sample & splitter-select
first, route, then local sort. Kept as the comparison baseline (the paper
implements IRAN instead, §5.2: step-9 set formation costs D·n/p with a large
constant, and sample sorting is sequential on processor 0).

Step 9's "integer sort by destination" is realized as a stable argsort of the
destination ids — exactly the set-formation operation the paper prices at
D·n/p.

Pipeline split: *nothing* here is tier-invariant — the sample is drawn from
the raw run with the per-tier rng, and the full local sort happens after
routing (step 12). :func:`prepare_ran_spmd` therefore just wraps the input;
escalation still profits from the shared executor (compiled-callable reuse)
and from the uniform prepare/route execution model.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import merge as merge_mod
from . import routing
from .types import PreparedSort, SortConfig


def prepare_ran_spmd(
    x: jnp.ndarray,
    cfg: SortConfig,
    axis: str,
    values: Sequence[jnp.ndarray] = (),
    rng: jax.Array | None = None,
) -> PreparedSort:
    """No tier-invariant work: classic sample sort local-sorts *last*."""
    del rng
    return PreparedSort(xs=x, vals=tuple(values), splits=None)


def route_ran_spmd(
    prep: PreparedSort,
    cfg: SortConfig,
    axis: str,
    rng: jax.Array | None = None,
) -> Tuple[jnp.ndarray, List[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    if rng is None:
        rng = jax.random.key(cfg.seed)
    x, values = prep.xs, list(prep.vals)
    n_p = x.shape[0]
    p = cfg.p
    me = lax.axis_index(axis)

    # Fig. 2 steps 2-5: random sample, gathered and sorted "at processor 0"
    # (deterministically replicated here — same result, one superstep).
    with jax.named_scope("ph3_splitters"):
        k = jax.random.fold_in(rng, me)
        pos = jax.random.randint(k, (cfg.s,), 0, n_p)
        local_sample = x[pos]
        gathered = lax.all_gather(local_sample, axis).reshape(-1)
        ybar = jnp.sort(gathered)
        # Step 6: p-1 evenly spaced splitters.
        splits = ybar[jnp.arange(1, p) * cfg.s - 1]

    # Step 9: destination of every (unsorted) key + set formation (stable
    # integer sort by destination — the D·n/p operation).
    with jax.named_scope("ph4_partition"):
        dest = jnp.searchsorted(splits, x, side="right").astype(jnp.int32)
        order = jnp.argsort(dest, stable=True)
        xg = x[order]
        vals = [v[order] for v in values]
        bounds = jnp.searchsorted(
            dest[order], jnp.arange(p + 1, dtype=jnp.int32), side="left"
        ).astype(jnp.int32)

    # Steps 10-11: routing; Step 12: full local sort (not a merge).
    buf, vbufs, count, overflow = routing.route(xg, bounds, cfg, axis, vals)
    merged, mvals = merge_mod.merge_by_sort(buf, vbufs)
    return merged, mvals, count, overflow


def sort_ran_spmd(
    x: jnp.ndarray,
    cfg: SortConfig,
    axis: str,
    values: Sequence[jnp.ndarray] = (),
    rng: jax.Array | None = None,
) -> Tuple[jnp.ndarray, List[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    return route_ran_spmd(prepare_ran_spmd(x, cfg, axis, values), cfg, axis, rng)
