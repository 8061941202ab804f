"""Key routing (Fig. 1 steps 10-11) — the single balanced h-relation.

On the Cray T3D this superstep is a ragged BSPlib ``bsp_put`` h-relation of
cost g·n_max. XLA collectives are fixed-shape, so we rely on the paper's own
theory to make the port sound: Lemma 5.1 (det) / Claim 5.1 (randomized) bound
the receive side at compile time, giving a static capacity ``cap = n_max``.

Three schedules (DESIGN.md §3):

* ``a2a_dense`` — one ``lax.all_to_all`` over a (p, pair_cap) send buffer.
  ``pair_cap`` is per-(src,dst): ``exact`` mode uses n/p (distribution
  independent — an adversarial input can aim a whole local run at one
  bucket); ``whp`` mode uses the Chernoff-scale (n/p²)(1+1/ω)+ω·p bound that
  holds w.h.p. for the randomized algorithm — overflow is *detected* (pmax of
  counts) and surfaced as a retriable fault, since a sort may not drop keys.
* ``allgather`` — reference schedule; every proc gathers all runs and slices
  its bucket. Volume g·n but one superstep and always exact.
* ``ring`` — p-1 ``ppermute`` supersteps rotating an n/p-word visitor block;
  exact, memory O(n/p), the literal BSP superstep structure.

Fused exchange (``SortConfig.exchange``)
----------------------------------------
The paper's Ph5 is ONE h-relation superstep; a key-value sort must not pay
one collective per array. Under ``exchange="fused"`` (the default) the key
and every payload row are bitcast to uint32 words and concatenated along
the trailing dim into a single send buffer, so each data superstep issues
exactly ONE collective regardless of payload count — one ``all_to_all`` for
``a2a_dense`` (plus the tiny (p,)-word Ph4 count bookkeeping superstep), one
``all_gather`` for ``allgather`` (plus the boundary bookkeeping gather), and
one ``ppermute`` per ring superstep (visitor arrays AND the rotating
boundary vector share the packed buffer). The buffer is unpacked (bitcast
back) after delivery; packing is bit-exact, so the fused path is
byte-identical to ``exchange="per_array"`` (the one-collective-per-array
layout, kept as the measured baseline — see the ``hotpath`` benchmark
table). The pack/unpack helpers (:func:`pack_words` / :func:`unpack_words`)
are shared with the MoE EP dispatch (models/moe.py).

All schedules preserve source order: the receive buffer is compacted by
(source proc, local index), which is what makes the final merge stable and
the §5.1.1 duplicate handling free.

Windowed copies
---------------
Both ends of ``a2a_dense`` move data that is contiguous by construction, so
they copy whole runs, never key by key. The send side
(:func:`_segment_rows`) takes destination i's row as one
``lax.dynamic_slice`` window ``x_sorted[b[i] : b[i] + pair_cap]`` of the
tail-padded local run; the receive side (:func:`compact_rows`) writes each
received row whole at its source's offset, in source order, so each row's
pad tail is overwritten by the next row. Under the vmap runner these lower
to batched gathers of slice size ``pair_cap`` and scatters with unique,
sorted indices (p windows each), which the TPU's compiler turns into loops
of window copies; under ``shard_map`` they stay plain dynamic slices and
updates. Per-key indices cost far more: on a TPU v5e a gather of one key
per index and an unsorted scatter of n_max indices (which it sorts first)
took 90 % of a 2^26-key bulk call.

Capacity-tier ladder & retry semantics
--------------------------------------
A sort may never drop keys, but every fixed-shape schedule above has a
static capacity an adversarial input can exceed. Overflow is therefore
*detected* here (pmax of send/receive counts vs pair_cap / n_max), carried
out of the collective region as the ``overflow`` flag, and treated by the
host-side driver (``api.bsp_sort_safe`` / ``api.bsp_sort_sharded_safe``) as
a retriable fault: the driver re-runs the jitted sort at the next rung of
``SortConfig.tier_ladder()`` —

    whp        Claim 5.1 w.h.p. pair capacity (production default)
    whp2       the same bound Chernoff-scaled ×2
    exact      pair_cap = n/p; Lemma 5.1 receive bound (det: a priori safe)
    allgather  reference schedule, full-size (n) receive buffer — cannot
               overflow for any input, so the ladder always terminates

On a clean flag the partially-filled buffers of the failed attempt are
discarded (nothing was written back), so retries are idempotent; per-tier
attempt counters (``api.TierStats``) surface how often the cheap tier
actually sufficed per workload. A retry re-enters the pipeline *here* (the
route stage), not at Ph2: the driver reuses the tier-invariant
``PreparedSort`` (local sort + det splitters) and only re-runs
Ph3b..Ph6 per rung — see ``api.SortExecutor``.

Values (payload arrays with leading dim n_p) ride along with the keys — this
is the key-value form used by MoE token dispatch (models/moe.py) and the
segmented SortService composites. With ``merge="tree"`` they also ride the
rank-merge tail (:func:`route_and_merge`): rank positions are computed once
on the keys and applied to every payload, so key-value callers skip the
``compact_rows`` compaction + full re-sort entirely.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

import jax.numpy as jnp
from jax import lax

from . import merge as merge_mod
from . import primitives as prim
from .types import SortConfig, sentinel_for


def _pad_value_for(arr: jnp.ndarray) -> jnp.ndarray:
    return jnp.zeros((), arr.dtype)


# ------------------------------------------------------ fused word packing
def _unsigned(a: jnp.ndarray) -> jnp.ndarray:
    """The bits of a sub-word array as the unsigned type of its width."""
    if a.dtype == jnp.bool_:
        return a.astype(jnp.uint8)
    return lax.bitcast_convert_type(a, jnp.dtype(f"uint{8 * a.dtype.itemsize}"))


def _to_words(a: jnp.ndarray, lead: int) -> jnp.ndarray:
    """uint32 words of ``a``: its trailing dims flattened to one word axis.

    A 64-bit array gives its low words, then its high words. A sub-word
    array is cut into ``4 // itemsize`` contiguous chunks of its trailing
    elements (zero-padded), and chunk ``i`` fills bits ``i·8·itemsize`` up
    of each word. No array ever takes a byte view.
    """
    head, s = a.shape[:lead], a.dtype.itemsize
    if s == 4:
        return lax.bitcast_convert_type(a, jnp.uint32).reshape(head + (-1,))
    if s == 8:
        u = lax.bitcast_convert_type(a, jnp.uint64).reshape(head + (-1,))
        return jnp.concatenate(
            [u.astype(jnp.uint32), (u >> 32).astype(jnp.uint32)], axis=-1
        )
    r = 4 // s
    u = _unsigned(a).reshape(head + (-1,)).astype(jnp.uint32)
    c = -(-u.shape[-1] // r)
    u = jnp.pad(u, [(0, 0)] * lead + [(0, c * r - u.shape[-1])])
    w = u[..., :c]
    for i in range(1, r):
        w = w | (u[..., i * c : (i + 1) * c] << (8 * s * i))
    return w


def _from_words(w: jnp.ndarray, dtype, trail: tuple, lead: int) -> jnp.ndarray:
    """Invert :func:`_to_words` for one array."""
    shape, s = w.shape[:lead] + tuple(trail), dtype.itemsize
    if s == 4:
        return lax.bitcast_convert_type(w, dtype).reshape(shape)
    if s == 8:
        lo, hi = jnp.split(w, 2, axis=-1)
        u = lo.astype(jnp.uint64) | (hi.astype(jnp.uint64) << 32)
        return lax.bitcast_convert_type(u, dtype).reshape(shape)
    bits = 8 * s
    u = jnp.concatenate(
        [(w >> (bits * i)) & ((1 << bits) - 1) for i in range(4 // s)], axis=-1
    )
    u = u[..., : int(np.prod(trail, dtype=np.int64))].astype(f"uint{bits}")
    if dtype == jnp.bool_:
        return (u != 0).reshape(shape)
    return lax.bitcast_convert_type(u, dtype).reshape(shape)


def _word_count(dtype, trail) -> int:
    """Words one leading index of a (dtype, trail) array packs into."""
    m, s = int(np.prod(trail, dtype=np.int64)), jnp.dtype(dtype).itemsize
    return m * s // 4 if s >= 4 else -(-m // (4 // s))


def pack_words(
    arrs: Sequence[jnp.ndarray], lead: int
) -> Tuple[jnp.ndarray, tuple]:
    """Pack arrays sharing ``lead`` leading dims into ONE uint32 buffer.

    Each array contributes its trailing dims as one run of uint32 words
    along the last axis (:func:`_to_words`); the concatenation is the single
    send buffer of a fused collective. Callers keep as leading dims only
    the axis the collective splits (``lead=1``: an all_to_all over (p, w)
    rows; ``lead=0``: a gathered or rotated block), so each array's words
    stay one long contiguous run. A byte view, or one word per row, would
    leave a minor axis a few elements wide, which the TPU pads to 128 lanes
    (at 2^25 keys per chip one such ring buffer outgrew the chip's HBM).
    Returns ``(buffer, metas)``, the static recipe :func:`unpack_words`
    inverts bit-exactly.
    """
    parts = [_to_words(a, lead) for a in arrs]
    metas = tuple((a.dtype, a.shape[lead:]) for a in arrs)
    return jnp.concatenate(parts, axis=-1), metas


def unpack_words(
    buf: jnp.ndarray, metas: tuple, lead: int
) -> List[jnp.ndarray]:
    """Invert :func:`pack_words` after delivery (bit-exact). ``lead`` is the
    buffer's leading dims, which a gather may have grown by one."""
    out, off = [], 0
    for dtype, trail in metas:
        dtype = jnp.dtype(dtype)
        k = _word_count(dtype, trail)
        out.append(_from_words(buf[..., off : off + k], dtype, trail, lead))
        off += k
    return out


def send_counts(boundaries: jnp.ndarray) -> jnp.ndarray:
    """(p,) keys this proc sends to each destination."""
    return jnp.diff(boundaries)


# ---------------------------------------------- host-side observability math
def packed_row_bytes(key_dtype, value_dtypes=()) -> int:
    """Bytes one routed row carries in the fused exchange (key + payloads).

    Pure host math for the tracer: the fused Ph5 collective moves
    word-packed (key, payload...) runs, so a traced h-relation's byte
    volume is ``counts × packed_row_bytes`` (up to the padding of a
    sub-word run to whole words) and its BSP h (32-bit words, the paper's
    unit) is that over 4.
    """
    return int(sum(np.dtype(d).itemsize for d in (key_dtype, *value_dtypes)))


def route_supersteps(routing: str, p: int) -> int:
    """Data supersteps one route-stage execution issues under ``routing``.

    The tracer charges each route span ``supersteps × L`` in the (g, L)
    fit: ``a2a_dense`` is the (p,)-word count bookkeeping all_to_all plus
    ONE fused data all_to_all (see :func:`recv_rows`); ``allgather`` is a
    single fused all_gather; ``ring`` is p−1 ppermute visitor supersteps.
    """
    if routing == "a2a_dense":
        return 2
    if routing == "allgather":
        return 1
    if routing == "ring":
        return max(1, p - 1)
    raise ValueError(f"unknown routing {routing!r}")


def recv_counts(counts: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Transpose the (implicit) p×p count matrix: r[j] = counts_on_proc_j[me].

    One all_to_all of p words — the Ph4 prefix bookkeeping superstep.
    """
    return lax.all_to_all(counts.reshape(-1, 1), axis, 0, 0).reshape(-1)


def _segment_rows(
    arrs: Sequence[jnp.ndarray],
    boundaries: jnp.ndarray,
    counts: jnp.ndarray,
    width: int,
    key_sentinel: jnp.ndarray,
) -> List[jnp.ndarray]:
    """Slice the local run into p destination rows of static width.

    Row i is the window ``arr[b[i] : b[i] + width]``, copied whole, with
    slots ``t >= c_i`` set to the pad. Each array is first padded at its
    tail by ``width`` slots, because ``lax.dynamic_slice`` clamps a start
    that would run past the end (which would shift the run); with the pad,
    ``b[i] + width`` never passes the padded length. Under the vmap runner
    each window is a gather of slice size ``width``, not one index per key.
    """
    starts = boundaries[:-1]
    valid = jnp.arange(width)[None, :] < counts[:, None]
    rows = []
    for i, a in enumerate(arrs):
        fill = key_sentinel if i == 0 else _pad_value_for(a)
        tail = a.shape[1:]
        padded = jnp.concatenate([a, jnp.full((width,) + tail, fill, a.dtype)])
        zeros = (0,) * len(tail)
        g = jnp.stack(
            [
                lax.dynamic_slice(padded, (starts[d],) + zeros, (width,) + tail)
                for d in range(starts.shape[0])
            ]
        )  # (p, width, ...)
        mask = valid.reshape(valid.shape + (1,) * len(tail))
        rows.append(jnp.where(mask, g, fill))
    return rows


def _all_to_all_rows(rows: List[jnp.ndarray], cfg: SortConfig, axis: str):
    """Deliver (p, w, ...) rows: ONE fused all_to_all, or one per array."""
    if cfg.exchange == "fused" and len(rows) > 1:
        buf, metas = pack_words(rows, lead=1)
        return unpack_words(lax.all_to_all(buf, axis, 0, 0), metas, lead=1)
    return [lax.all_to_all(r, axis, 0, 0) for r in rows]


@prim.superstep("ph5_exchange")
def recv_rows(
    x_sorted: jnp.ndarray,
    boundaries: jnp.ndarray,
    cfg: SortConfig,
    axis: str,
    values: Sequence[jnp.ndarray] = (),
) -> Tuple[List[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    """Deliver bucket ``me`` of every source as padded rows.

    Returns ``(rows, rcounts, overflow)`` where rows[a] has shape
    (p, width, ...): row j = the run received from source j (sorted, padded),
    rcounts[j] its valid length. Width = pair_cap (a2a_dense) or n_p
    (allgather).
    """
    sent = sentinel_for(x_sorted.dtype)
    counts = send_counts(boundaries)
    arrs = [x_sorted, *values]

    if cfg.routing == "a2a_dense":
        pair_cap = cfg.pair_cap
        rcounts = recv_counts(counts, axis)
        over = (jnp.any(counts > pair_cap) | (rcounts.sum() > cfg.n_max)).astype(
            jnp.int32
        )
        overflow = lax.pmax(over, axis) > 0
        rows = _segment_rows(arrs, boundaries, counts, pair_cap, sent)
        rows = _all_to_all_rows(rows, cfg, axis)
        return rows, rcounts, overflow

    if cfg.routing == "allgather":
        me = prim.proc_id(axis)
        b_all = lax.all_gather(boundaries, axis)  # (p, p+1) — bookkeeping
        starts = b_all[:, me]
        rcounts = b_all[:, me + 1] - starts
        n_p = x_sorted.shape[0]
        t = jnp.arange(n_p)[None, :]
        idx = jnp.clip(starts[:, None] + t, 0, n_p - 1)
        valid = t < rcounts[:, None]
        if cfg.exchange == "fused" and len(arrs) > 1:
            buf, metas = pack_words(arrs, lead=0)
            gathered = unpack_words(lax.all_gather(buf, axis), metas, lead=1)
        else:
            gathered = [lax.all_gather(a, axis) for a in arrs]  # (p, n_p, ...)
        rows = []
        for i, a_all in enumerate(gathered):
            g = jnp.take_along_axis(
                a_all, idx.reshape(idx.shape + (1,) * (a_all.ndim - 2)), axis=1
            )
            fill = sent if i == 0 else _pad_value_for(arrs[i])
            mask = valid.reshape(valid.shape + (1,) * (g.ndim - 2))
            rows.append(jnp.where(mask, g, fill))
        over = (rcounts.sum() > cfg.n_max).astype(jnp.int32)
        overflow = lax.pmax(over, axis) > 0
        return rows, rcounts, overflow

    raise ValueError(f"recv_rows: unsupported routing {cfg.routing!r}")


@prim.superstep("ph5_exchange")
def compact_rows(
    rows: Sequence[jnp.ndarray],
    rcounts: jnp.ndarray,
    cap: int,
    key_sentinel: jnp.ndarray,
) -> List[jnp.ndarray]:
    """Lay (p, w, ...) rows end to end in a (cap, ...) buffer, by source.

    Row j's first r_j entries land at ``offsets[j]`` onward. Each row is
    written whole, as one window, into a (cap + w, ...) buffer of pad, in
    source order: every row is already pad past r_j, so row j+1 overwrites
    row j's pad tail, and the last row's tail is pad. The writes stay p
    ordered updates (one scatter with overlapping windows leaves their
    order undefined). A start past ``cap`` (an overflowing tier, whose
    buffers the overflow-safe sort discards) is clamped to ``cap`` and
    lands in the slack that ``[:cap]`` cuts off. Pads end at the tail.
    """
    offsets = prim.exclusive_cumsum(rcounts)
    p, w = rows[0].shape[:2]
    out = []
    for i, r in enumerate(rows):
        fill = key_sentinel if i == 0 else _pad_value_for(r)
        zeros = (0,) * (r.ndim - 2)
        buf = jnp.full((cap + w,) + r.shape[2:], fill, r.dtype)
        for j in range(p):
            buf = lax.dynamic_update_slice(buf, r[j], (offsets[j],) + zeros)
        out.append(buf[:cap])
    return out


def route(
    x_sorted: jnp.ndarray,
    boundaries: jnp.ndarray,
    cfg: SortConfig,
    axis: str,
    values: Sequence[jnp.ndarray] = (),
) -> Tuple[jnp.ndarray, List[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    """Route bucket i of every proc to proc i, compacted by source.

    Returns ``(buf, value_bufs, count, overflow)``: the (cap,) receive buffer
    ordered by (src, idx), its valid prefix length, and the capacity fault
    flag (retriable — the driver re-runs with the next capacity tier).
    """
    sent = sentinel_for(x_sorted.dtype)
    cap = cfg.n_max

    if cfg.routing == "ring":
        return _route_ring(x_sorted, boundaries, cfg, axis, values, sent)

    rows, rcounts, overflow = recv_rows(x_sorted, boundaries, cfg, axis, values)
    out = compact_rows(rows, rcounts, cap, sent)
    total = jnp.minimum(rcounts.sum(), cap)
    return out[0], out[1:], total, overflow


def _fit(arr: jnp.ndarray, cap: int, fill: jnp.ndarray) -> jnp.ndarray:
    """Slice or pad-extend the merged run to the (cap, ...) result shape.

    The tree tail's run length is p·width, which can undershoot ``n_max``
    for a planner-shrunk pair capacity — pad with ``fill`` so every tier
    returns the same result shape as the sort tail.
    """
    if arr.shape[0] >= cap:
        return arr[:cap]
    pad = jnp.full((cap - arr.shape[0],) + arr.shape[1:], fill, arr.dtype)
    return jnp.concatenate([arr, pad], axis=0)


def route_and_merge(
    x_sorted: jnp.ndarray,
    boundaries: jnp.ndarray,
    cfg: SortConfig,
    axis: str,
    values: Sequence[jnp.ndarray] = (),
) -> Tuple[jnp.ndarray, List[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    """Ph5 + Ph6 tail shared by det/iran: route, then stable merge.

    Requires bucket i of the local run (``x_sorted[b[i]:b[i+1]]``) to be
    sorted, so each received row is a sorted run — which is what makes the
    ``merge=tree`` rank-merge path valid (``ran`` routes dest-grouped, not
    key-sorted, rows and must keep its own sort-based tail). The tree tail
    is payload-generic: received rows (key + payloads) go straight into
    :func:`merge.merge_tree`, skipping the ``compact_rows`` compaction and the
    full O(n_max·lg²n_max) re-sort of the sort tail.
    """
    if cfg.merge == "tree" and cfg.routing != "ring":
        rows, rcounts, overflow = recv_rows(x_sorted, boundaries, cfg, axis, values)
        merged, mvals, count = merge_mod.merge_tree(
            rows[0], rcounts, values=rows[1:], backend=cfg.merge_backend,
            cap=cfg.n_max,
        )
        cap = cfg.n_max
        sent = sentinel_for(x_sorted.dtype)
        merged = _fit(merged, cap, sent)
        mvals = [_fit(v, cap, _pad_value_for(v)) for v in mvals]
        return merged, mvals, jnp.minimum(count, cap), overflow

    buf, vbufs, count, overflow = route(x_sorted, boundaries, cfg, axis, values)
    merged, mvals = merge_mod.merge_by_sort(buf, vbufs)
    return merged, mvals, count, overflow


@prim.superstep("ph5_exchange")
def _route_ring(x_sorted, boundaries, cfg, axis, values, sent):
    """p-1 ppermute supersteps; visitor block = one local run + boundaries.

    Under ``exchange="fused"`` the whole visitor block (keys, payloads AND
    the boundary vector) rotates as one packed word vector — one collective
    per superstep regardless of payload count.
    """
    p, cap = cfg.p, cfg.n_max
    n_p = x_sorted.shape[0]
    me = prim.proc_id(axis)
    arrs = [x_sorted, *values]

    counts = send_counts(boundaries)
    rcounts = recv_counts(counts, axis)
    offsets = prim.exclusive_cumsum(rcounts)
    total = rcounts.sum()
    overflow = lax.pmax((total > cap).astype(jnp.int32), axis) > 0

    bufs = []
    for i, a in enumerate(arrs):
        fill = sent if i == 0 else _pad_value_for(a)
        bufs.append(jnp.full((cap,) + a.shape[1:], fill, a.dtype))

    vis_arrs, vis_b = tuple(arrs), boundaries
    for r in range(p):  # r=0 places the local segment; then p-1 rotations
        src = (me - r) % p
        start = vis_b[me]
        cnt = vis_b[me + 1] - start
        t = jnp.arange(n_p)
        idx = jnp.clip(start + t, 0, n_p - 1)
        valid = t < cnt
        dst = jnp.where(valid, offsets[src] + t, cap)
        bufs = [
            buf.at[dst].set(a[idx], mode="drop") for buf, a in zip(bufs, vis_arrs)
        ]
        if r != p - 1:
            if cfg.exchange == "fused":
                vec, metas = pack_words(list(vis_arrs) + [vis_b], lead=0)
                vec = prim.ppermute_shift(vec, axis, 1, p=p)
                *vis_list, vis_b = unpack_words(vec, metas, lead=0)
                vis_arrs = tuple(vis_list)
            else:
                vis_arrs = prim.ppermute_shift(vis_arrs, axis, 1, p=p)
                vis_b = prim.ppermute_shift(vis_b, axis, 1, p=p)
    return bufs[0], bufs[1:], jnp.minimum(total, cap), overflow
