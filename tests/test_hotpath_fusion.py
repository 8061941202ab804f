"""Fused h-relation + payload-generic rank-merge tail (the hotpath PR).

Covers the three acceptance surfaces:

* the fused exchange is *byte-identical* to the per-array layout (packing is
  a bitcast, so this must hold bit-exactly) across mixes, key-only and
  key-value, on clean runs — and agrees on the overflow flag on faulted ones;
* the payload-generic ``merge="tree"`` tail is byte-identical to the
  ``merge_by_sort`` tail (keys, counts AND payloads), including the int64
  segmented composites and the ``merge_backend="pallas"`` substrate;
* HLO regression: the fused a2a path emits exactly ONE ``all_to_all`` per
  data superstep (+ the (p,)-word count bookkeeping superstep) regardless of
  payload count, counted on the real ``shard_map`` lowering in a subprocess
  with forced host devices (the vmap runner batches collectives away).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SortConfig,
    bsp_sort,
    bsp_sort_safe,
    datagen,
    gathered_output,
)
from repro.core import routing

P, NP = 8, 512
MIXES = ["U", "G", "B", "DD", "zipf"]


def _run_cfg(x, values, **kw):
    res, vb = bsp_sort(x, SortConfig(p=P, n_per_proc=NP, **kw), values=values)
    return (
        bool(res.overflow),
        np.asarray(res.buf),
        np.asarray(res.count),
        [np.asarray(v) for v in vb],
    )


def _assert_same(got, ref, where):
    assert got[0] == ref[0], (where, "overflow flag")
    if got[0]:  # faulted buffers are discarded by the driver: flag-only
        return
    assert np.array_equal(got[1], ref[1]), (where, "buf")
    assert np.array_equal(got[2], ref[2]), (where, "count")
    for a, b in zip(got[3], ref[3]):
        assert np.array_equal(a, b), (where, "values")


@pytest.mark.fast
@pytest.mark.parametrize("kv", [0, 1])
def test_fused_exchange_byte_identical_to_per_array(kv):
    ids = jnp.arange(P * NP, dtype=jnp.int32).reshape(P, NP)
    vals = (ids,) if kv else ()
    for mix in MIXES:
        x = jnp.asarray(datagen.generate(mix, P, NP, seed=7))
        for pc in ("exact", "whp"):
            for merge in ("sort", "tree"):
                ref = _run_cfg(
                    x, vals, algorithm="iran", pair_capacity=pc, merge=merge,
                    exchange="per_array",
                )
                got = _run_cfg(
                    x, vals, algorithm="iran", pair_capacity=pc, merge=merge,
                    exchange="fused",
                )
                _assert_same(got, ref, (mix, pc, merge, kv))


@pytest.mark.fast
@pytest.mark.parametrize("kv", [0, 1])
def test_tree_tail_byte_identical_to_sort_tail(kv):
    """Keys, counts AND payloads of merge="tree" == merge_by_sort, plus the
    safe driver delivering the complete sorted output through the tree tail
    on every mix (DD/zipf escalate past whp at this p)."""
    ids = jnp.arange(P * NP, dtype=jnp.int32).reshape(P, NP)
    vals = (ids,) if kv else ()
    for mix in MIXES:
        x = jnp.asarray(datagen.generate(mix, P, NP, seed=9))
        for pc in ("exact", "whp"):
            ref = _run_cfg(x, vals, algorithm="iran", pair_capacity=pc, merge="sort")
            got = _run_cfg(x, vals, algorithm="iran", pair_capacity=pc, merge="tree")
            _assert_same(got, ref, (mix, pc, kv))
        res, vb, _ = bsp_sort_safe(
            x,
            SortConfig(
                p=P, n_per_proc=NP, algorithm="iran", pair_capacity="whp",
                merge="tree",
            ),
            values=vals,
        )
        assert np.array_equal(
            gathered_output(res), np.sort(np.asarray(x).ravel())
        ), mix
        if kv:
            cnt = np.asarray(res.count)
            vout = np.concatenate(
                [np.asarray(vb[0])[k, : cnt[k]] for k in range(P)]
            )
            assert np.array_equal(
                np.asarray(x).ravel()[vout], gathered_output(res)
            ), mix


@pytest.mark.fast
@pytest.mark.parametrize("kv", [0, 1])
def test_tree_tail_pallas_backend_byte_identical(kv):
    """merge_backend="pallas" (interpret on CPU): same bytes as the XLA tail
    — key-only pairs take the merge-path partitioned network merge, key-value
    pairs the masked-count rank kernel."""
    ids = jnp.arange(P * NP, dtype=jnp.int32).reshape(P, NP)
    vals = (ids,) if kv else ()
    for mix in ("U", "DD"):
        x = jnp.asarray(datagen.generate(mix, P, NP, seed=11))
        ref = _run_cfg(x, vals, algorithm="det", merge="tree")
        got = _run_cfg(
            x, vals, algorithm="det", merge="tree", merge_backend="pallas"
        )
        _assert_same(got, ref, (mix, kv, "pallas"))


@pytest.mark.fast
def test_ring_fused_visitor_block_byte_identical():
    ids = jnp.arange(P * NP, dtype=jnp.int32).reshape(P, NP)
    x = jnp.asarray(datagen.generate("DD", P, NP, seed=13))
    for kv in (0, 1):
        vals = (ids,) if kv else ()
        ref = _run_cfg(
            x, vals, algorithm="det", routing="ring", exchange="per_array"
        )
        got = _run_cfg(x, vals, algorithm="det", routing="ring", exchange="fused")
        _assert_same(got, ref, ("ring", kv))


@pytest.mark.fast
def test_segmented_composites_ride_tree_tail():
    """The int64 (segment, key) composites + pos payload through merge="tree"
    — byte-identical per-segment outputs, at both the service knob and the
    sort_segments override level."""
    from repro.core import sort_segments
    from repro.core.api import SortExecutor
    from repro.service import ServiceConfig, SortService

    sizes = datagen.zipf_sizes(12, 4096, seed=3)
    arrays = [
        datagen.generate(MIXES[i % len(MIXES)], 1, int(s), seed=50 + i)[0]
        for i, s in enumerate(sizes)
    ]
    a = sort_segments(arrays, p=P, merge="sort", executor=SortExecutor())
    b = sort_segments(arrays, p=P, merge="tree", executor=SortExecutor())
    for ka, kb, oa, ob in zip(a.keys, b.keys, a.order, b.order):
        assert np.array_equal(ka, kb)
        assert np.array_equal(oa, ob)

    svc = SortService(ServiceConfig(p=P, merge="tree"), executor=SortExecutor())
    for arr, r in zip(arrays, svc.sort_many(arrays)):
        assert np.array_equal(r.keys, np.sort(arr))
        assert np.array_equal(arr[r.order], r.keys)
    assert svc.stats.retries == 0, svc.stats.as_row()


@pytest.mark.fast
def test_pack_bytes_roundtrip_mixed_dtypes():
    """The fused-exchange packing is a bitcast into uint32 words: bit-exact
    for every dtype and trailing shape the routing/MoE layers ship."""
    rng = np.random.default_rng(0)
    rows = [
        jnp.asarray(rng.integers(-(2**31), 2**31 - 1, (4, 16), dtype=np.int64).astype(np.int32)),
        jnp.asarray(rng.standard_normal((4, 16, 3)).astype(np.float32)),
        jnp.asarray(rng.standard_normal((4, 16)).astype(np.float32)).astype(jnp.bfloat16),
        jnp.asarray(rng.integers(-128, 127, (4, 16, 5), dtype=np.int64).astype(np.int8)),
        jnp.asarray(rng.standard_normal((4, 16, 7)).astype(np.float32)).astype(jnp.bfloat16),
        jnp.asarray(rng.integers(0, 2, (4, 16), dtype=np.int64).astype(bool)),
    ]

    def same(out, ins):
        assert len(out) == len(ins)
        for o, r in zip(out, ins):
            assert o.dtype == r.dtype and o.shape == r.shape
            assert np.array_equal(np.asarray(o), np.asarray(r))

    for lead in (1, 2):  # a2a_dense packs (p, w) rows with lead=1
        buf, metas = routing.pack_words(rows, lead=lead)
        assert buf.dtype == jnp.uint32 and buf.shape[:lead] == (4, 16)[:lead]
        same(routing.unpack_words(buf, metas, lead=lead), rows)

    # allgather: pack the local block flat, unpack the gathered (p, L) buffer
    buf, metas = routing.pack_words([r[0] for r in rows], lead=0)
    stacked = jnp.stack([buf, buf])
    same(routing.unpack_words(stacked, metas, lead=1), [jnp.stack([r[0]] * 2) for r in rows])

    # the ring's flat visitor block: mixed shapes and dtypes, one vector
    flat_in = [rows[0], rows[3], rows[5], jnp.arange(9, dtype=jnp.int32)]
    vec, fmetas = routing.pack_words(flat_in, lead=0)
    assert vec.dtype == jnp.uint32 and vec.ndim == 1
    same(routing.unpack_words(vec, fmetas, lead=0), flat_in)

    # 64-bit composites (the int64 service keys) pack as two words each
    with jax.enable_x64(True):
        wide = jnp.asarray(
            rng.integers(-(2**63), 2**63 - 1, (4, 16), dtype=np.int64)
        )
        flat_in = [wide, rows[2], jnp.arange(9, dtype=jnp.int32)]
        vec, fmetas = routing.pack_words(flat_in, lead=0)
        assert vec.dtype == jnp.uint32 and vec.size == 4 * 16 * 2 + 32 + 9
        same(routing.unpack_words(vec, fmetas, lead=0), flat_in)


@pytest.mark.fast
def test_hlo_exactly_one_all_to_all_per_data_superstep():
    """HLO regression on the real shard_map lowering (8 forced host devices,
    subprocess — the shared benchmarks.common harness, so the ``hotpath``
    table's identity column counts the same way): the fused path lowers to
    exactly 2 all_to_all ops — the (p,)-word Ph4 count bookkeeping plus ONE
    data superstep — independent of payload count, while per-array pays
    2 + R. The allgather schedule gets the same fusion (boundary bookkeeping
    + one data gather). Lowering only; nothing is compiled or executed."""
    from benchmarks.common import sharded_collective_counts

    combos = {
        f"{routing}/{exchange}/{nv}": dict(
            algorithm="iran", pair_capacity="whp", routing=routing,
            exchange=exchange, nv=nv,
        )
        for routing in ("a2a_dense", "allgather")
        for exchange in ("per_array", "fused")
        for nv in (0, 1, 2)
    }
    counts = sharded_collective_counts(combos, p=8)
    for c in counts.values():  # rename for the assertions below
        c["a2a"], c["ag"] = c["all_to_all"], c["all_gather"]
    for nv in (0, 1, 2):
        # per-array: count superstep + one collective per array (key + R)
        assert counts[f"a2a_dense/per_array/{nv}"]["a2a"] == 2 + nv, counts
        # fused: count superstep + exactly ONE data superstep, any R
        assert counts[f"a2a_dense/fused/{nv}"]["a2a"] == 2, counts
        # sanity: the fused payload rows ride the a2a, not a hidden gather
        assert (
            counts[f"a2a_dense/fused/{nv}"]["ag"]
            == counts[f"a2a_dense/per_array/{nv}"]["ag"]
        ), counts
    # allgather routing: the sample-stage gathers + boundary bookkeeping +
    # data gathers. "all_gather" appears a fixed number of times per op in
    # the StableHLO text, so compare *deltas* against the nv=0 graph (where
    # fused == per-array by construction): per-array grows one gather per
    # payload, fused none.
    base = counts["allgather/fused/0"]["ag"]
    assert counts["allgather/per_array/0"]["ag"] == base, counts
    per_op = (counts["allgather/per_array/2"]["ag"] - base) // 2
    assert per_op > 0, counts
    for nv in (1, 2):
        assert (
            counts[f"allgather/per_array/{nv}"]["ag"] == base + per_op * nv
        ), counts
        assert counts[f"allgather/fused/{nv}"]["ag"] == base, counts
