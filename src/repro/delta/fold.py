"""Fold machinery — rank-merging a Δ batch into a standing sorted run.

The paper's §5.1.1 tag trick makes every comparison a total order by
lifting keys to composites whose low bits carry a position tag. The same
lift turns *incremental* sorting into a closed-form fold: for a stream
that is already sorted except for Δ out-of-place keys,

    comp = (key + 2^31) << 31 | original_position        (int64)

is strictly increasing over the in-place subsequence, distinct everywhere,
and ordered exactly like the stable sort of the raw stream — so merging
the kept run with the Δ run on composites reproduces the full stable
argsort *by construction* (the low bits of the merged sequence ARE the
argsort). No tie-breaking argument is needed and byte-identity with a cold
``bsp_sort_safe`` of the whole stream is structural, not probabilistic.

Three pieces live here:

* :func:`split_sorted_run` — O(n) host scan extracting the out-of-place Δ
  from a near-sorted stream. Two vectorized passes: drop elements that
  break order with a neighbour (catches scattered updates *before* they
  can poison a running max), then a record-high filter on the remainder
  (catches rotated blocks / appended tails). The kept subsequence is
  non-decreasing by construction; the split quality only affects *cost*
  (a pessimal split degenerates to Δ ≈ n and the fold still sorts
  correctly), never correctness.
* :func:`merge_sorted_runs` — the jitted rank-merge tail
  (:func:`repro.core.merge._rank_merge_two` — one ``rank_in`` + gathers,
  payload-generic). Runs are padded to power-of-two widths so the compile
  cache stays O(log n) across arbitrary view growth; the output width is
  quantized in eighth-of-run steps so pad slots, not valid keys, absorb
  the rounding.
* :func:`near_sorted_sort_launch` — the planner-routed request path: split
  the stream, route ONLY the Δ composites through the fused h-relation (a
  ``(p, Δ/p)`` layout — every capacity rung is Δ-sized by construction,
  and the exact pair capacity makes retries impossible), then one rank
  merge against the kept run. Work: O(n) host scan + O(Δ log Δ) device
  sort + O((n+Δ) log n) rank merge — versus the cold ladder's full
  O(n log n) device sort plus its collectives.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import TierStats
from repro.core.api import (
    InFlightSort,
    SortExecutor,
    bsp_sort_safe_launch,
    gathered_output,
)
from repro.core.merge import _rank_merge_two
from repro.core.segmented import SegmentedResult, _pow2_n_per_proc, contiguous_lane_sizes
from repro.core.types import SortConfig, round_up, sentinel_for

#: zero-argument scope factory for the int64 composites' device entries
enable_x64 = functools.partial(jax.enable_x64, True)

#: low bits of the fold composite holding the original position; the
#: (biased) int32 key sits above. 31 + 32 bits keeps the composite inside
#: positive int64 with the int64-max sentinel strictly past every real
#: composite (positions are bounded by the int32 index space anyway).
POS_BITS = 31
POS_MASK = (np.int64(1) << POS_BITS) - 1
_KEY_BIAS = np.int64(1) << 31


def lift_positions(keys: np.ndarray, pos: np.ndarray) -> np.ndarray:
    """(int32 key, position) -> order-preserving int64 fold composites.

    Strictly increasing in lexicographic (key, pos) — i.e. ordered exactly
    like the stable sort of ``keys`` — and all distinct, so a merge of two
    lifted runs needs no tie-break rule at all.
    """
    k = np.asarray(keys, np.int64)
    p = np.asarray(pos, np.int64)
    assert p.size == 0 or int(p.max()) < (1 << POS_BITS) - 1
    return ((k + _KEY_BIAS) << POS_BITS) | p


def drop_positions(comp: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Invert :func:`lift_positions`: composites -> (int32 keys, positions)."""
    comp = np.asarray(comp, np.int64)
    keys = ((comp >> POS_BITS) - _KEY_BIAS).astype(np.int32)
    return keys, (comp & POS_MASK).astype(np.int32)


def split_sorted_run(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Index split of a stream into (kept sorted run, out-of-place Δ).

    Pass 1 drops every element that violates order with a neighbour —
    scattered in-place updates (a huge value planted early) are removed
    *here*, before they can become the running max and condemn everything
    after them. Pass 2 keeps the record highs of the remainder, which
    handles the patterns pass 1 is blind to (a rotated leading block is
    locally sorted but globally displaced). ``keys[kept]`` is always
    non-decreasing; for the appended/scattered/rotated families the Δ side
    is O(true Δ) (at most ~2 extractions per displaced key).
    """
    k = np.asarray(keys).reshape(-1)
    n = k.shape[0]
    if n == 0:
        e = np.zeros(0, np.int64)
        return e, e.copy()
    prev_ok = np.empty(n, bool)
    prev_ok[0] = True
    np.greater_equal(k[1:], k[:-1], out=prev_ok[1:])
    next_ok = np.empty(n, bool)
    next_ok[-1] = True
    np.less_equal(k[:-1], k[1:], out=next_ok[:-1])
    idx = np.flatnonzero(prev_ok & next_ok)
    sub = k[idx]
    if sub.size:
        kept_idx = idx[sub >= np.maximum.accumulate(sub)]  # record highs
    else:
        kept_idx = idx
    mask = np.zeros(n, bool)
    mask[kept_idx] = True
    return kept_idx.astype(np.int64), np.flatnonzero(~mask).astype(np.int64)


def _pow2_width(n: int, floor: int = 8) -> int:
    return max(floor, 1 << max(0, int(n) - 1).bit_length())


@functools.lru_cache(maxsize=None)
def _merge_fn(wa: int, wb: int, w_out: int, nv: int, backend: str):
    """Jitted two-run rank merge at fixed padded widths (+ nv payloads)."""

    def f(ka, ca, kb, cb, *vals):
        sent = sentinel_for(ka.dtype)
        out, vout, cnt = _rank_merge_two(
            ka, ca, kb, cb, sent, vals[:nv], vals[nv:], backend=backend,
            w_out=w_out,
        )
        return (out, *vout, cnt)

    return jax.jit(f)


def merge_sorted_runs(
    a_keys: np.ndarray,
    b_keys: np.ndarray,
    a_vals: Sequence[np.ndarray] = (),
    b_vals: Sequence[np.ndarray] = (),
    *,
    backend: str = "xla",
) -> Tuple[np.ndarray, List[np.ndarray]]:
    """Stable merge of two sorted host runs (a first on ties) + payloads.

    One jitted rank computation on the keys; every payload rides the same
    gather (`core/merge` semantics). Inputs are padded to pow2 widths and
    the output width is quantized (eighth-of-run steps), so the jit cache
    holds O(log n) programs however the view grows. int64 runs (the fold
    composites) enter under ``enable_x64`` — the repo otherwise runs
    32-bit, and an unscoped transfer would truncate them.
    """
    a = np.asarray(a_keys).reshape(-1)
    b = np.asarray(b_keys).reshape(-1)
    assert a.dtype == b.dtype and len(a_vals) == len(b_vals)
    na, nb = int(a.size), int(b.size)
    if nb == 0:
        return a.copy(), [np.asarray(v).copy() for v in a_vals]
    if na == 0:
        return b.copy(), [np.asarray(v).copy() for v in b_vals]
    wa, wb = _pow2_width(na), _pow2_width(nb)
    step = max(64, wa // 8)
    w_out = min(wa + wb, round_up(na + nb, step))
    # host-side pad value; the jnp sentinel_for would truncate int64 when
    # built outside the enable_x64 scope
    if np.issubdtype(a.dtype, np.integer):
        sent = np.iinfo(a.dtype).max
    else:
        sent = np.inf
    ka = np.full(wa, sent, a.dtype)
    ka[:na] = a
    kb = np.full(wb, sent, b.dtype)
    kb[:nb] = b
    vals = []
    for av, bv in zip(a_vals, b_vals):
        av, bv = np.asarray(av), np.asarray(bv)
        pa = np.zeros((wa,) + av.shape[1:], av.dtype)
        pa[:na] = av
        pb = np.zeros((wb,) + bv.shape[1:], bv.dtype)
        pb[:nb] = bv
        vals += [pa, pb]
    # interleaved (a, b) pairs -> (a..., b...) argument order
    va, vb = vals[0::2], vals[1::2]
    fn = _merge_fn(wa, wb, w_out, len(va), backend)
    scope = enable_x64 if a.dtype == np.int64 else contextlib.nullcontext
    with scope():
        out = fn(
            jnp.asarray(ka), np.int32(na), jnp.asarray(kb), np.int32(nb),
            *[jnp.asarray(v) for v in va], *[jnp.asarray(v) for v in vb],
        )
    merged = np.asarray(out[0])[: na + nb]
    vout = [np.asarray(v)[: na + nb] for v in out[1:-1]]
    return merged, vout


def sort_delta_comps_launch(
    comp: np.ndarray,
    p: int,
    *,
    min_n_per_proc: int = 8,
    executor: Optional[SortExecutor] = None,
    stats: Optional[TierStats] = None,
    obs_handle=None,
) -> Tuple[Optional[InFlightSort], int]:
    """Launch the Δ composites through the fused h-relation (non-blocking).

    The Δ batch gets its own ``(p, n_p)`` layout sized to Δ — every
    capacity rung of the launched sort is Δ-bounded, not n-bounded — and
    runs at the *exact* pair capacity, so the fold can never retry (the
    bench table's zero-retry identity column). Pads are the int64
    sentinel; the composites are distinct and strictly below it, so the
    valid Δ prefix of the gathered output is exact. Returns
    ``(flight | None, n_per_proc)``.
    """
    dn = int(comp.size)
    if dn == 0:
        return None, min_n_per_proc
    n_p = _pow2_n_per_proc(dn, p, min_n_per_proc)
    rows = np.full((p, n_p), np.iinfo(np.int64).max, np.int64)
    off = 0
    for k, c in enumerate(contiguous_lane_sizes(dn, p)):
        rows[k, :c] = comp[off : off + c]
        off += c
    cfg = SortConfig(
        p=p, n_per_proc=n_p, algorithm="iran", pair_capacity="exact",
        obs=obs_handle,
    )
    with enable_x64():
        x = jnp.asarray(rows)
    flight = bsp_sort_safe_launch(
        x, cfg, stats=stats, executor=executor, scope=enable_x64
    )
    return flight, n_p


@dataclasses.dataclass
class InFlightDeltaSort:
    """A dispatched near-sorted request awaiting its Δ sort + fold.

    The host split is done and the Δ composites' (Δ-sized) sort is in the
    device queue; :meth:`wait` syncs on it, rank-merges the Δ run into the
    kept run, and unlifts composites back to (keys, stable argsort). API-
    compatible with ``InFlightSegmentedSort`` from the dispatcher's side.
    """

    comp_kept: np.ndarray  # lifted kept run, strictly increasing
    n_delta: int
    flight: Optional[InFlightSort]  # None when the stream was fully sorted
    stats: TierStats
    n_per_proc: int  # the Δ sort's pow2 bucket
    tracer: Optional[object] = None
    t_launched: float = 0.0
    backend: str = "xla"

    def done(self) -> bool:
        return self.flight is None or self.flight.done()

    def wait(self) -> SegmentedResult:
        if self.flight is not None:
            res, _, _ = self.flight.wait()
            d = gathered_output(res)[: self.n_delta]
        else:
            d = np.zeros(0, np.int64)
        merged, _ = merge_sorted_runs(
            self.comp_kept, d, backend=self.backend
        )
        keys, order = drop_positions(merged)
        if self.tracer is not None:
            n = keys.size
            self.tracer.add_span(
                "fold",
                self.t_launched,
                cat="delta",
                tid="main",
                delta_n=self.n_delta,
                view_n=n - self.n_delta,
                share=round(self.n_delta / max(n, 1), 4),
                route="delta",
            )
        return SegmentedResult(
            keys=[keys],
            order=[order],
            stats=self.stats,
            tier="delta",
            n_per_proc=self.n_per_proc,
        )


def near_sorted_sort_launch(
    keys: np.ndarray,
    p: int,
    *,
    min_n_per_proc: int = 8,
    executor: Optional[SortExecutor] = None,
    stats: Optional[TierStats] = None,
    obs_handle=None,
    backend: str = "xla",
) -> InFlightDeltaSort:
    """Launch the delta route for one near-sorted request (non-blocking).

    Split → lift → Δ-sized fused sort of the out-of-place composites →
    (at :meth:`InFlightDeltaSort.wait`) one rank merge. The result is
    byte-identical — keys AND stable argsort — to the cold ladder's,
    whatever the split extracted: the composites are distinct and ordered
    like the stable sort, so identity is structural.
    """
    arr = np.asarray(keys, np.int32).reshape(-1)
    stats = stats if stats is not None else TierStats()
    kept_idx, delta_idx = split_sorted_run(arr)
    comp_kept = lift_positions(arr[kept_idx], kept_idx)
    comp_delta = lift_positions(arr[delta_idx], delta_idx)
    tracer = obs.resolve_tracer(obs_handle)
    flight, n_p = sort_delta_comps_launch(
        comp_delta, p, min_n_per_proc=min_n_per_proc, executor=executor,
        stats=stats, obs_handle=obs_handle,
    )
    return InFlightDeltaSort(
        comp_kept=comp_kept,
        n_delta=int(delta_idx.size),
        flight=flight,
        stats=stats,
        n_per_proc=n_p,
        tracer=tracer,
        t_launched=tracer.now() if tracer is not None else 0.0,
        backend=backend,
    )


def near_sorted_sort(keys: np.ndarray, p: int, **kw) -> SegmentedResult:
    """Blocking wrapper over :func:`near_sorted_sort_launch`."""
    return near_sorted_sort_launch(keys, p, **kw).wait()
