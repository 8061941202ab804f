"""Radix count-then-distribute route: sign/boundary behaviour of the
order-preserving unsigned mapping, the single-rung zero-retry guarantee,
and the segmented composite path."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    SortConfig,
    TierStats,
    bsp_sort_safe,
    datagen,
    gathered_output,
)
from repro.core.radix import radix_argsort
from repro.core.segmented import sort_segments
from repro.core.sort_radix import radix_boundaries

pytestmark = pytest.mark.fast

I32 = np.iinfo(np.int32)
I64 = np.iinfo(np.int64)


# ------------------------------------------------ radix_argsort sign/boundary
def test_radix_argsort_negative_and_boundary_int32():
    x = np.array(
        [5, -1, I32.min, I32.max, 0, -7, I32.min, I32.max, 3, -1], np.int32
    )
    order = np.asarray(radix_argsort(jnp.asarray(x)))
    assert np.array_equal(order, np.argsort(x, kind="stable"))


def test_radix_argsort_int64_extremes():
    with jax.enable_x64(True):
        x = np.array(
            [I64.max, I64.min, 0, -1, 1, I64.min, I64.max, I64.min + 1],
            np.int64,
        )
        order = np.asarray(radix_argsort(jnp.asarray(x), bits=8))
        assert np.array_equal(order, np.argsort(x, kind="stable"))


def test_radix_argsort_zipf_duplicates_stable():
    keys = datagen.generate("zipf", 1, 512, seed=3)[0]
    order = np.asarray(radix_argsort(jnp.asarray(keys)))
    assert np.array_equal(order, np.argsort(keys, kind="stable"))


# ------------------------------------------------- route-level sign/boundary
def _run_route(x, route, n_values=0, **kw):
    p, n_p = x.shape
    cfg = SortConfig(
        p=p, n_per_proc=n_p, routing="a2a_dense", route=route,
        pair_capacity="exact", **kw,
    )
    vals = [
        jnp.asarray(np.arange(x.size, dtype=np.int32).reshape(p, n_p))
        for _ in range(n_values)
    ]
    st = TierStats()
    res, vbufs, st = bsp_sort_safe(jnp.asarray(x), cfg, values=vals, stats=st)
    cnt = np.asarray(res.count)
    flat_vals = [
        np.concatenate([np.asarray(b)[k, : cnt[k]] for k in range(p)])
        for b in vbufs
    ]
    return gathered_output(res), flat_vals, st


def test_radix_route_boundary_keys():
    rng = np.random.default_rng(0)
    x = rng.integers(I32.min, I32.max, (4, 64), dtype=np.int64).astype(np.int32)
    x[0, :4] = (I32.min, I32.max, -1, 0)
    x[3, -2:] = (I32.min, I32.max)
    keys, _, st = _run_route(x, "radix")
    assert np.array_equal(keys, np.sort(x.reshape(-1)))
    assert st.retries == 0 and st.last_tier == "radix"


def test_radix_route_int64_extremes():
    with jax.enable_x64(True):
        rng = np.random.default_rng(1)
        x = rng.integers(I64.min, I64.max, (4, 32), dtype=np.int64)
        x[0, :2] = (I64.min, I64.max)
        keys, _, st = _run_route(x, "radix")
        assert np.array_equal(keys, np.sort(x.reshape(-1)))
        assert st.retries == 0


def test_radix_route_single_rung_zero_retries_on_one_bucket_skew():
    """Every key identical: the whole input lands in one range bucket — the
    worst case for range bucketing — yet the counted capacity fits it on the
    first and only rung. No escalation path exists on this route."""
    x = np.full((8, 256), 123456, np.int32)
    keys, _, st = _run_route(x, "radix")
    assert np.array_equal(keys, np.sort(x.reshape(-1)))
    assert st.retries == 0 and st.last_tier == "radix"
    assert st.attempts == {"radix": 1}, st.as_row()


def test_radix_boundaries_monotone_and_complete():
    """The counted boundary vector is a valid partition of the local run:
    starts at 0, ends at n_p, nondecreasing — and equal keys never straddle
    a destination boundary (stability across the exchange)."""
    p, n_p = 4, 128
    x = np.sort(datagen.dense_int(p, n_p, seed=5, domain=16), axis=1)

    def one(xs):
        return radix_boundaries(jnp.asarray(xs), p, "bsp")

    bounds = np.asarray(jax.vmap(one, axis_name="bsp")(jnp.asarray(x)))
    assert bounds.shape == (p, p + 1)
    for k in range(p):
        b = bounds[k]
        assert b[0] == 0 and b[-1] == n_p
        assert np.all(np.diff(b) >= 0)
        for cut in b[1:-1]:  # equal keys share a destination
            if 0 < cut < n_p:
                assert x[k, cut - 1] != x[k, cut]


# ----------------------------------------------- deterministic parity sweep
# (tests/test_radix_parity.py runs the hypothesis-driven version of this
# when hypothesis is installed; this fixed grid always executes)
@pytest.mark.parametrize("mix", ["U", "B", "DD", "zipf", "dense_int"])
@pytest.mark.parametrize("kv", [0, 1])
def test_radix_route_matches_sample_route(mix, kv):
    p, n_p = 4, 192
    x = (
        datagen.dense_int(p, n_p, seed=7, domain=2 * p)
        if mix == "dense_int"
        else datagen.generate(mix, p, n_p, seed=7)
    )
    k_r, v_r, st_r = _run_route(x, "radix", n_values=kv, algorithm="det")
    k_s, v_s, _ = _run_route(x, "sample", n_values=kv, algorithm="det")
    assert st_r.retries == 0, st_r.as_row()
    assert np.array_equal(k_r, np.sort(x.reshape(-1)))
    assert np.array_equal(k_r, k_s)
    for a, b in zip(v_r, v_s):  # payload parity == stability parity
        assert np.array_equal(a, b)


# -------------------------------------------------- segmented composite path
def test_radix_route_segmented_composite_parity():
    """Int-key fused batches ride the radix route: the segment-tag composite
    is a dense-int prefix, so the counted bucketing splits by segment runs.
    Output must be byte-identical to the sampling route's, with zero
    retries and the radix tier reported."""
    arrays = [
        datagen.dense_int(1, s, seed=10 + i, domain=32)[0]
        for i, s in enumerate((100, 37, 256, 9))
    ]
    r_radix = sort_segments(arrays, p=4, layout="striped", route="radix")
    r_sample = sort_segments(arrays, p=4, layout="striped")
    for a, kr, ks in zip(arrays, r_radix.keys, r_sample.keys):
        assert np.array_equal(kr, np.sort(a))
        assert np.array_equal(kr, ks)
    for or_, os_ in zip(r_radix.order, r_sample.order):
        assert np.array_equal(or_, os_)
    assert r_radix.stats.retries == 0
    assert r_radix.tier == "radix"
