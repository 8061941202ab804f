"""Segmented BSP sort — many independent sorts fused into ONE tagged sort.

The paper's transparent duplicate handling (§5.1.1) works by *tagging*:
sample/splitter records carry explicit ``(processor, index)`` tags so the
comparator is a total order even when every key is equal, and splitter
selection stays balanced without doubling communication. The same mechanism
generalizes to *segment* tags. A batch of R independent sort requests
("segments") is fused into one BSP sort by lifting every key to the
composite

    comp = segment_id * 2^32 + (key + 2^31)        (int64, order-preserving)

i.e. the pair ``(segment_id, key)`` compared lexicographically. One balanced
sort of the composites returns every segment contiguous *and* sorted — the
segment tag rides in the key's high bits exactly like the §5.1.1 duplicate
tag rides in the comparator, and splitters drawn from the shared oversample
of the composites automatically land inside each segment in proportion to
its size, so a batch of many small/skewed requests is load-balanced as one
n-key sort instead of R degenerate p-lane sorts (the regime where naive
per-request sample sort collapses — Axtmann & Sanders 2016).

Everything rides the existing machinery unchanged: the composite sort goes
through :func:`repro.core.api.bsp_sort_safe`, so it inherits the resumable
prepare/route phase pipeline, the capacity-tier escalation ladder
(whp → whp×2 → exact → allgather) and the :class:`SortExecutor` compile
cache — one compiled program per ``(p, n_per_proc)`` shape serves every
batch that packs to that shape. That includes the fused single-collective
exchange and, via ``merge="tree"``, the payload-generic rank-merge tail:
the int64 composites and their ``pos`` payload ride the lg p rank merges
instead of a full re-sort (``ServiceConfig.merge`` exposes the knob one
level up).

Layout: ``pack_segments`` supports two lane layouts.

* ``contiguous`` (the PR 3 default) concatenates the ragged requests in
  submit order, pads the tail up to ``p * n_per_proc`` with composites of
  the past-the-last segment id (they sort after every real key), and deals
  the result row-major onto the ``(p, n_per_proc)`` global layout. Simple,
  but every lane's run is *value-clustered* (it spans only a couple of
  segments and routes almost whole to the destination covering its own
  global position range), which structurally violates any sub-exact
  per-pair routing capacity.
* ``striped`` splits EVERY segment into ``p`` consecutive chunks, chunk k
  appended to lane k (remainder +1s rotated across lanes so lane totals
  differ by at most one). Each lane then holds ~1/p of every segment — a
  value-representative sample of the whole batch — so per-(src,dst)
  routing loads concentrate near ``n/p²`` again and the planner's
  segment-aware w.h.p. pair capacity (``repro.planner.capacity``) applies.
  Stability is preserved: within a segment, chunk k's submit positions all
  precede chunk k+1's, so the pipeline's (source proc, local index) order
  for equal composites is still ascending submit order. Pads get *distinct*
  composites ``(R << 32) | (j·p + k)`` (lane k's j-th pad) interleaving the
  lanes in sorted order, so the pad tail routes evenly instead of aiming
  each lane's constant pad run at one bucket.

A per-key ``pos`` payload (the key's index *within its segment*) rides
along, so the unpacked result carries each segment's stable argsort for
free — both layouts keep equal keys in original within-segment order.

Keys are int32 (the library's key dtype throughout datagen/benchmarks);
segment count is bounded by 2^31 so the composite stays inside int64.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .api import (
    InFlightSort,
    SortExecutor,
    TierStats,
    bsp_sort_safe_launch,
    gathered_output,
)
from .types import SortConfig

#: bits of the composite holding the (biased) key; segment id sits above.
SEG_SHIFT = 32
_KEY_BIAS = np.int64(1) << 31  # maps int32 -> [0, 2^32): order-preserving
_KEY_MASK = (np.int64(1) << SEG_SHIFT) - 1


def pack_keys(seg_ids: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """Lift (segment_id, int32 key) pairs to order-preserving int64 composites."""
    seg = np.asarray(seg_ids, np.int64)
    k = np.asarray(keys, np.int64)
    return (seg << SEG_SHIFT) | (k + _KEY_BIAS)


def unpack_keys(comp: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Invert :func:`pack_keys`: composites -> (segment ids, int32 keys)."""
    comp = np.asarray(comp, np.int64)
    seg = (comp >> SEG_SHIFT).astype(np.int32)
    keys = ((comp & _KEY_MASK) - _KEY_BIAS).astype(np.int32)
    return seg, keys


def _pow2_n_per_proc(total: int, p: int, min_n_per_proc: int) -> int:
    """Power-of-two per-proc run length covering ``total`` packed keys.

    Each distinct n_per_proc is a distinct XLA compile of the whole tier
    ladder; rounding to the next power of two bounds the compiled-program
    count to O(log n) across arbitrary traffic (same rationale as the
    serve-side cache_len bucketing).
    """
    per = max(1, -(-total // p))
    return max(min_n_per_proc, 1 << (per - 1).bit_length())


@dataclasses.dataclass
class PackedSegments:
    """A batch of ragged requests packed onto the (p, n_per_proc) layout.

    Arrays stay host-side (numpy): device transfer happens inside the
    sort's ``enable_x64`` scope — an eager ``jnp.asarray`` under the repo's
    default 32-bit mode would truncate the int64 composites.

    Single-segment batches (the serve-admission / data-bucketing hot path)
    skip the composite lift entirely: a segment tag carries zero
    information for R = 1, so ``comp`` holds the raw int32 keys (pads =
    int32 max, which may collide with real keys — the unpack therefore
    filters by the pos payload, not by value) and the sort runs in the
    repo's native 32-bit mode at half the key bytes.
    """

    comp: np.ndarray  # (p, n_p) keys: int64 composites (R>1) / int32 (R=1)
    pos: np.ndarray  # (p, n_p) int32 within-segment index (pads: -1)
    sizes: Tuple[int, ...]  # true per-segment lengths, submit order
    p: int
    n_per_proc: int
    layout: str = "contiguous"  # lane layout this batch was packed with

    @property
    def n_keys(self) -> int:
        return int(sum(self.sizes))


def contiguous_lane_sizes(total: int, p: int) -> np.ndarray:
    """(p,) real-key counts of the contiguous even-share lane deal.

    The single source of truth for the contiguous packing geometry — used
    by :func:`pack_segments` to fill lanes and by the planner's
    fingerprint (``repro.planner.fingerprint.lane_spread``) to reason
    about which segments each lane would span.
    """
    q, rem = divmod(int(total), p)
    out = np.full(p, q, np.int64)
    out[:rem] += 1
    return out


def striped_chunk_sizes(sizes: Sequence[int], p: int) -> np.ndarray:
    """(R, p) per-lane chunk lengths for the striped layout.

    Segment s contributes ``floor(m_s/p)`` keys to every lane plus a +1 to
    ``m_s mod p`` lanes; the +1 windows are rotated (laid head-to-tail
    around the lane circle) so final lane totals differ by at most one —
    which is what keeps the packed batch inside ``n_p = ceil(total/p)``.
    Deterministic, so the capacity planner can bound per-lane loads from
    the sizes alone.
    """
    out = np.zeros((len(sizes), p), np.int64)
    start = 0
    for i, m in enumerate(sizes):
        q, r = divmod(int(m), p)
        out[i, :] = q
        if r:
            out[i, (start + np.arange(r)) % p] += 1
            start += r
    return out


def pack_segments(
    arrays: Sequence[np.ndarray],
    p: int,
    *,
    n_per_proc: Optional[int] = None,
    min_n_per_proc: int = 8,
    layout: str = "contiguous",
) -> PackedSegments:
    """Pack ragged int32 request arrays into one tagged (p, n_p) sort input.

    ``n_per_proc`` defaults to the power-of-two bucket covering the batch
    (see :func:`_pow2_n_per_proc`); passing it explicitly lets a batch
    former pin the bucket. Pads carry segment id ``len(arrays)`` — strictly
    above every real composite — so they sort to the global tail and the
    valid prefix decodes exactly.

    ``layout="contiguous"`` deals the submit-order concatenation row-major:
    each lane gets an *even share* of the real keys (submit-contiguous, so
    stability still reads in submit order) with its own tail pads, rather
    than all pads piling onto the last lanes: an all-pad lane is a constant
    run aimed at one routing bucket, which would structurally fault the whp
    pair capacity even for a single benign segment.

    ``layout="striped"`` splits every segment into ``p`` consecutive chunks
    (chunk k → lane k, remainders rotated; :func:`striped_chunk_sizes`), so
    each lane holds a value-representative ~1/p of every segment and the
    planner's segment-aware sub-exact pair capacity applies. Single-segment
    batches ignore the distinction: the contiguous even-share deal IS the
    one-segment stripe, and they keep the raw-int32 fast path.
    """
    if layout not in ("contiguous", "striped"):
        raise ValueError(f"unknown layout {layout!r}")
    arrays = [np.asarray(a, np.int32).reshape(-1) for a in arrays]
    sizes = tuple(int(a.shape[0]) for a in arrays)
    total = sum(sizes)
    n_p = n_per_proc or _pow2_n_per_proc(total, p, min_n_per_proc)
    if p * n_p < total:
        raise ValueError(f"batch of {total} keys exceeds p*n_per_proc={p * n_p}")
    keys = (
        np.concatenate(arrays) if arrays else np.zeros((0,), np.int32)
    )
    pos = np.concatenate(
        [np.arange(s, dtype=np.int32) for s in sizes]
        or [np.zeros((0,), np.int32)]
    )
    if len(arrays) <= 1:  # hot path: no tag needed, sort raw int32 keys
        layout = "contiguous"
        comp = keys
        pad_comp = np.iinfo(np.int32).max
        comp_rows = np.full((p, n_p), pad_comp, np.int32)
    else:
        seg = np.repeat(np.arange(len(arrays), dtype=np.int64), sizes)
        comp = pack_keys(seg, keys)
        pad_comp = np.int64(len(arrays)) << SEG_SHIFT
        comp_rows = np.full((p, n_p), pad_comp, np.int64)
    pos_rows = np.full((p, n_p), -1, np.int32)

    if layout == "striped":
        chunks = striped_chunk_sizes(sizes, p)
        seg_starts = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
        # chunk offsets within each segment: lane k's slice of segment s is
        # [offs[s, k], offs[s, k + 1]) of that segment's submit order
        offs = np.concatenate(
            [np.zeros((len(sizes), 1), np.int64), np.cumsum(chunks, axis=1)],
            axis=1,
        )
        for k in range(p):
            sel = np.concatenate(
                [
                    np.arange(seg_starts[s] + offs[s, k], seg_starts[s] + offs[s, k + 1])
                    for s in range(len(sizes))
                ]
                or [np.zeros((0,), np.int64)]
            )
            c = sel.shape[0]
            comp_rows[k, :c] = comp[sel]
            pos_rows[k, :c] = pos[sel]
            # distinct interleaved pad composites: lane k's j-th pad sorts
            # between other lanes' pads (value j·p + k), so the pad tail
            # routes evenly instead of one constant per-lane run
            comp_rows[k, c:] = pad_comp | (
                np.arange(n_p - c, dtype=np.int64) * p + k
            )
    else:
        off = 0
        for k, c in enumerate(contiguous_lane_sizes(total, p)):
            comp_rows[k, :c] = comp[off : off + c]
            pos_rows[k, :c] = pos[off : off + c]
            off += c
    return PackedSegments(
        comp=comp_rows,
        pos=pos_rows,
        sizes=sizes,
        p=p,
        n_per_proc=n_p,
        layout=layout,
    )


@dataclasses.dataclass
class SegmentedResult:
    """Per-segment outputs of one fused sort, in submit order."""

    keys: List[np.ndarray]  # segment r's keys, sorted ascending
    order: List[np.ndarray]  # stable argsort: keys[r] == input_r[order[r]]
    stats: TierStats  # escalation counters of the fused sort
    tier: Optional[str]  # capacity tier that served the batch
    n_per_proc: int  # the pow2 bucket this batch compiled under


@dataclasses.dataclass
class InFlightSegmentedSort:
    """A dispatched fused batch awaiting completion.

    Host-side packing is done and the sort's first ladder rung is in the
    device queue (:class:`repro.core.api.InFlightSort`); :meth:`wait` is the
    only sync point — it escalates through the remaining capacity rungs if
    the launched rung faulted, then unpacks per segment. The async service
    dispatcher launches batch k+1's packing/planning while batch k sits
    here.
    """

    packed: PackedSegments
    flight: InFlightSort

    def done(self) -> bool:
        return self.flight.done()

    def wait(self) -> SegmentedResult:
        res, vbufs, stats = self.flight.wait()
        return _unpack_result(self.packed, res, vbufs, stats)


def segmented_sort_launch(
    packed: PackedSegments,
    cfg: Optional[SortConfig] = None,
    *,
    rng: Optional[jax.Array] = None,
    stats: Optional[TierStats] = None,
    executor: Optional[SortExecutor] = None,
    **overrides,
) -> InFlightSegmentedSort:
    """Launch one fused overflow-safe sort without awaiting it.

    The composite keys run through :func:`bsp_sort_safe_launch` (prepare
    once, re-enter route per capacity-ladder rung), with the within-segment
    index as payload. Default config: randomized oversampling starting at
    the *exact* pair capacity — the safe choice for the default *contiguous*
    packing, whose value-clustered lanes structurally violate the whp
    per-pair bound. Batches packed with ``layout="striped"`` can instead
    pass ``pair_capacity="planned"`` with the capacity planner's
    segment-aware bound (``repro.planner``) and start sub-exact. The
    receive side is still the Claim 5.1 bound; a batch that overflows it
    (however skewed) escalates to the allgather terminal tier instead of
    dropping keys.

    Int-key fused batches can pass ``route="radix"`` instead (the planner
    does, for balanced key ranges): the segment-tag composite is itself a
    dense-int prefix, so the count-then-distribute route buckets the batch
    by segment runs, sizes its ONE rung from the exact counted totals, and
    never retries — no oversampling parameter, no splitter superstep.
    """
    if cfg is None:
        cfg = SortConfig(
            p=packed.p,
            n_per_proc=packed.n_per_proc,
            **{"algorithm": "iran", "pair_capacity": "exact", **overrides},
        )
    assert (cfg.p, cfg.n_per_proc) == (packed.p, packed.n_per_proc)
    stats = stats if stats is not None else TierStats()
    # Multi-segment composites need all 64 bits; the repo otherwise runs
    # with JAX's default 32-bit mode, so x64 is enabled only around the
    # sort's device entries. Every launch (not just the first trace) must
    # sit inside the scope — input canonicalization is per-call, and a
    # 32-bit call would truncate the segment tags and retrace the
    # executor's cached callables — so the scope *factory* travels with the
    # in-flight sort and is re-entered when ``wait`` escalates. Single-
    # segment batches carry raw int32 keys and stay in native 32-bit mode.
    scope = (
        functools.partial(jax.enable_x64, True)
        if packed.comp.dtype == np.int64
        else contextlib.nullcontext
    )
    with scope():
        x = jnp.asarray(packed.comp)
        pos = jnp.asarray(packed.pos)
    flight = bsp_sort_safe_launch(
        x,
        cfg,
        values=(pos,),
        rng=rng,
        stats=stats,
        executor=executor,
        scope=scope,
    )
    if flight.trace_tid is not None:
        # attach the batch's segment shape to the sort's timeline lane
        from ..obs import resolve_tracer

        tracer = resolve_tracer(cfg.obs)
        sizes = packed.sizes
        tracer.point(
            "segments",
            tid=flight.trace_tid,
            n_segments=len(sizes),
            n_keys=packed.n_keys,
            layout=packed.layout,
            sizes=list(sizes) if len(sizes) <= 256 else None,
            size_max=max(sizes) if sizes else 0,
        )
    return InFlightSegmentedSort(packed=packed, flight=flight)


def segmented_sort_safe(
    packed: PackedSegments,
    cfg: Optional[SortConfig] = None,
    *,
    rng: Optional[jax.Array] = None,
    stats: Optional[TierStats] = None,
    executor: Optional[SortExecutor] = None,
    **overrides,
) -> SegmentedResult:
    """Sort every packed segment in one overflow-safe BSP sort (blocking).

    The launch-then-wait form of :func:`segmented_sort_launch` —
    byte-identical output; see there for capacity semantics.
    """
    return segmented_sort_launch(
        packed, cfg, rng=rng, stats=stats, executor=executor, **overrides
    ).wait()


def _unpack_result(packed: PackedSegments, res, vbufs, stats) -> SegmentedResult:
    """Host-side: slice the fused sorted sequence back into segments."""
    n = packed.n_keys
    cnt = np.asarray(res.count)
    pbuf = np.asarray(vbufs[0])
    pos = np.concatenate([pbuf[k, : cnt[k]] for k in range(packed.p)])
    flat = gathered_output(res)
    if len(packed.sizes) == 1:
        # int32 fast path: pads (= int32 max) may equal real keys and
        # interleave with them among the global maxima, so filter by the
        # pos payload instead of slicing a prefix. Dropping elements from
        # a sorted sequence keeps it sorted, and real equal keys keep
        # their (proc, idx) = submit order.
        mask = pos >= 0
        return SegmentedResult(
            keys=[flat[mask]],
            order=[pos[mask]],
            stats=stats,
            tier=stats.last_tier,
            n_per_proc=packed.n_per_proc,
        )
    flat, pos = flat[:n], pos[:n]  # pad composites (seg = R) hold the tail
    _, keys = unpack_keys(flat)
    bounds = np.concatenate([[0], np.cumsum(packed.sizes)])
    return SegmentedResult(
        keys=[keys[bounds[r] : bounds[r + 1]] for r in range(len(packed.sizes))],
        order=[pos[bounds[r] : bounds[r + 1]] for r in range(len(packed.sizes))],
        stats=stats,
        tier=stats.last_tier,
        n_per_proc=packed.n_per_proc,
    )


def sort_segments(
    arrays: Sequence[np.ndarray],
    p: int = 8,
    *,
    n_per_proc: Optional[int] = None,
    min_n_per_proc: int = 8,
    layout: str = "contiguous",
    stats: Optional[TierStats] = None,
    executor: Optional[SortExecutor] = None,
    rng: Optional[jax.Array] = None,
    **overrides,
) -> SegmentedResult:
    """Convenience: pack + fused-sort + unpack a batch of ragged requests."""
    packed = pack_segments(
        arrays, p, n_per_proc=n_per_proc, min_n_per_proc=min_n_per_proc,
        layout=layout,
    )
    return segmented_sort_safe(
        packed, rng=rng, stats=stats, executor=executor, **overrides
    )
