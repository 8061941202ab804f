"""Ph6 — stable multi-way merging of the routed buckets (Fig. 1 step 12).

Knuth's heap-based p-way merge (the paper's n_max·lg p charge) is scalar and
branchy; the TPU-native counterparts are:

* ``sort``  — one stable re-sort of the capacity buffer. The routed buffer is
  already ordered by (source proc, local idx), so a *stable* key sort yields
  exactly the paper's stable merge semantics; under XLA this is one fused
  O(n_max lg² n_max) sorting network, usually fastest in practice. 1-D
  payloads ride the same network as extra ``lax.sort`` operands (one fused
  multi-operand sort); only multi-dim payloads pay the argsort+gather
  permutation path.
* ``tree``  — lg p rounds of pairwise *rank merges*: each element's output
  position is ``own_idx + rank_in_other`` (searchsorted), stability by taking
  left-run elements first on ties. Work O(n_max·lg n_max·?) per round but
  each round is a fully vectorized gather/scatter — this honours the paper's
  merge-not-sort structure (Robust/Practical Massively Parallel Sorting:
  *merge* the received sorted runs, don't re-sort them). Rank positions are
  computed ONCE on the keys and the scatter applied to every payload array,
  so the tree tail is payload-generic: key-value callers (MoE dispatch,
  segmented SortService composites) skip the compact+re-sort path entirely.

``merge_backend="pallas"`` routes the tree tail through the Pallas kernel
packages (interpret mode on CPU CI, real kernels on TPU): rank computation
through ``kernels/searchsorted`` (masked-count ranks) and key-only pairwise
merges through ``kernels/merge_path`` (merge-path partitioned network merge).
Both are value-identical to the XLA path.

Both tails keep pads (key == sentinel) at the tail by construction.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .primitives import superstep
from .types import sentinel_for


@superstep("ph6_merge")
def merge_by_sort(
    buf: jnp.ndarray, values: Sequence[jnp.ndarray] = ()
) -> Tuple[jnp.ndarray, List[jnp.ndarray]]:
    """Stable re-sort of the (cap,) buffer (+ payload), pads stay at tail."""
    if not values:
        out = lax.sort((buf,), num_keys=1, is_stable=True)
        return out[0], []
    if all(v.ndim == 1 for v in values):
        # equal-shape 1-D payloads ride the one fused sorting network
        out = lax.sort((buf, *values), num_keys=1, is_stable=True)
        return out[0], list(out[1:])
    # lax.sort wants equal-shape operands along the sort dim; multi-dim
    # payloads are sorted via gathered permutation instead.
    perm = jnp.argsort(buf, stable=True)
    return buf[perm], [v[perm] for v in values]


def _rank(data: jnp.ndarray, queries: jnp.ndarray, side: str, backend: str):
    """searchsorted ranks of ``queries`` in the sorted ``data`` run."""
    if backend == "pallas":
        from repro.kernels.searchsorted import ops as ss_ops  # lazy: optional layer

        return ss_ops.rank_in(data, queries, side=side)
    return jnp.searchsorted(data, queries, side=side)


def _rank_merge_two(
    ka: jnp.ndarray,
    ca: jnp.ndarray,
    kb: jnp.ndarray,
    cb: jnp.ndarray,
    sent: jnp.ndarray,
    va: Sequence[jnp.ndarray] = (),
    vb: Sequence[jnp.ndarray] = (),
    backend: str = "xla",
    w_out: int | None = None,
) -> Tuple[jnp.ndarray, List[jnp.ndarray], jnp.ndarray]:
    """Stable merge of two sorted padded runs -> ((w_out,) run, payloads, count).

    pos_a(i) = i + #{j < cb : b_j < a_i}   (left run first on ties); pos_a
    is strictly increasing over the valid prefix, so the *inverse*
    permutation is itself a binary search: output slot o holds a-element
    ``A(o)-1`` if ``pos_a[A(o)-1] == o`` (where ``A(o) = #{pos_a <= o}``)
    and b-element ``o - A(o)`` otherwise. Everything is ranks + gathers —
    no scatter (whose vmapped lowering is the slow path on every backend we
    measured) and only ONE rank computation per pair. The ``take``
    permutation is computed once on the keys; every payload array rides the
    same gather, which is what makes the tree tail payload-generic.

    ``w_out`` (default 2w) truncates the output run: a caller that knows a
    global bound on the VALID total (the routing receive bound ``n_max``)
    caps every round's width at it, so only pad slots are dropped and the
    per-round work tracks the valid volume, not the padded capacity.
    """
    wa, wb = ka.shape[0], kb.shape[0]
    w2 = wa + wb
    w_out = w2 if w_out is None else min(w_out, w2)
    if wa == 0 or wb == 0:
        # degenerate span (Δ=0 folds, one-run-empty merge-tree lanes): the
        # general path would gather from a width-0 ``pos_a``, which XLA
        # rejects — pass the populated run through, re-masking pads so a
        # truncated w_out still leaves only valid keys followed by sentinel
        ks, cs, vs = (ka, ca, va) if wb == 0 else (kb, cb, vb)
        o = jnp.arange(w_out)
        valid = o < cs
        out = jnp.where(valid, ks[:w_out], sent)
        vout = []
        for v in vs:
            m = valid.reshape((w_out,) + (1,) * (v.ndim - 1))
            vout.append(jnp.where(m, v[:w_out], jnp.zeros((), v.dtype)))
        return out, vout, jnp.minimum(cs, w_out)
    ra = jnp.minimum(_rank(kb, ka, "left", backend), cb)
    ia = jnp.arange(wa)
    # invalid (padded) a-entries park past every output slot, keeping pos_a
    # strictly increasing so the inverse search below stays well-defined
    pos_a = jnp.where(ia < ca, ia + ra, w2 + ia)
    o = jnp.arange(w_out)
    A = _rank(pos_a, o, "right", backend)  # a-elements at output slots <= o
    from_a = jnp.where(A > 0, pos_a[jnp.maximum(A - 1, 0)] == o, False)
    take = jnp.where(
        from_a, jnp.maximum(A - 1, 0), jnp.minimum(wa + o - A, w2 - 1)
    )
    valid = o < ca + cb
    out = jnp.where(valid, jnp.concatenate([ka, kb])[take], sent)
    vout = []
    for a_v, b_v in zip(va, vb):
        m = valid.reshape((w_out,) + (1,) * (a_v.ndim - 1))
        cat = jnp.concatenate([a_v, b_v])
        vout.append(jnp.where(m, cat[take], jnp.zeros((), a_v.dtype)))
    return out, vout, jnp.minimum(ca + cb, w_out)


@superstep("ph6_merge")
def merge_tree(
    runs: jnp.ndarray,
    counts: jnp.ndarray,
    values: Sequence[jnp.ndarray] = (),
    backend: str = "xla",
    cap: int | None = None,
) -> Tuple[jnp.ndarray, List[jnp.ndarray], jnp.ndarray]:
    """Merge (m, w) sorted padded runs (m a power of two) into one run.

    lg m rounds of vmapped pairwise rank merges; payload arrays (m, w, ...)
    follow the key positions through every round. Returns
    ``((min(m·w, cap),) run, [payloads], count)``. ``cap`` is the caller's
    bound on the total VALID element count (the routing receive bound
    ``n_max``): every round's output width is clipped to it, so the padded
    capacity of oversized tiers (``exact``'s p·n/p send layout) never
    inflates the merge work — only pad slots are ever dropped.
    ``backend="pallas"`` takes the kernel substrate: key-only pairs go
    through the merge-path partitioned network merge, key-value pairs
    through the masked-count rank kernel.
    """
    sent = sentinel_for(runs.dtype)
    m = runs.shape[0]
    assert m & (m - 1) == 0, "run count must be a power of two"
    vals = list(values)
    while m > 1:
        a, b = runs[0::2], runs[1::2]
        ca, cb = counts[0::2], counts[1::2]
        if backend == "pallas" and not vals:
            from repro.kernels.merge_path import ops as mp_ops  # lazy

            merged = mp_ops.merge_partitioned(a, b)
            if cap is not None and merged.shape[1] > cap:
                merged = merged[:, :cap]
            runs, counts = merged, jnp.minimum(ca + cb, merged.shape[1])
        else:
            w_out = None if cap is None else min(cap, 2 * runs.shape[1])
            va = tuple(v[0::2] for v in vals)
            vb = tuple(v[1::2] for v in vals)
            runs, vals, counts = jax.vmap(
                lambda ka, ca_, kb, cb_, va_, vb_: _rank_merge_two(
                    ka, ca_, kb, cb_, sent, va_, vb_, backend=backend,
                    w_out=w_out,
                )
            )(a, ca, b, cb, va, vb)
        m //= 2
    return runs[0], [v[0] for v in vals], counts[0]
