"""Ph5 moves each destination's run as one window.

``routing.recv_rows`` slices each destination's run out of the local run as
one window, and ``routing.compact_rows`` writes each received row whole into
the receive buffer, in source order. Both are checked byte for byte against
a per-key NumPy reference, on the vmap runner and on the ``shard_map``
runner over 4 host devices (a subprocess, so this process keeps one
device). The count layouts cover a destination with count 0, a count equal
to ``pair_cap`` (at ``exact`` the run then ends at ``n_p`` exactly), last
runs whose window passes the end of the local run, and an overflowing tier,
whose buffers must still come out as the reference's and must not fault.

A lowering test holds the route program to the windowed form: no scatter
without ``unique_indices``, and no ``ph5_exchange`` gather of one key at a
time.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SortConfig, routing
from repro.core.types import sentinel_for

AXIS = "procs"
#: keys per processor for each p: multiples of 8, so pair_cap <= n_p
N_P = {2: 1000, 4: 512, 8: 256}
LAYOUTS = ("balanced", "edges", "overflow")
PAYLOADS = ("int32", "int64", "int8")
SHARDED_P = 4
_HERE = os.path.dirname(os.path.abspath(__file__))


def _spread(total: int, k: int) -> np.ndarray:
    """``total`` split into ``k`` near-equal parts, the smallest last."""
    return np.array([total // k + (i < total % k) for i in range(k)], np.int64)


def _counts(layout: str, p: int, n_p: int, pair_cap: int, n_max: int, rng) -> np.ndarray:
    """(src, dst) counts; each source's row sums to n_p."""
    c = np.zeros((p, p), np.int64)
    if layout == "balanced":
        for s in range(p):
            jitter = rng.integers(-(n_p // p) // 4, (n_p // p) // 4 + 1, p)
            row = np.full(p, n_p // p) + jitter - (jitter.sum() // p)
            row[-1] = n_p - row[:-1].sum()
            c[s] = row
    elif layout == "edges":
        # source 0: nothing to destination 0, exactly pair_cap to 1; the
        # others send 1 what it can still take, and source 1 one key to the
        # last destination
        c[0, 1] = pair_cap
        mid = list(range(2, p)) or [0]
        c[0, mid] = _spread(n_p - pair_cap, len(mid))
        for s in range(1, p):
            c[s, 1] = min(pair_cap, (n_max - pair_cap) // (p - 1))
            rest = [0] + list(range(2, p))
            if s == 1 and p > 2:
                c[s, p - 1] = 1
                rest = rest[:-1]
            c[s, rest] = _spread(n_p - c[s].sum(), len(rest))
    elif layout == "overflow":
        c[:, p - 1] = n_p  # every source aims its whole run at one bucket
    assert (c >= 0).all() and (c.sum(1) == n_p).all(), (layout, c)
    return c


def _inputs(p: int, tier: str, payload: str, layout: str, seed: int):
    n_p = N_P[p]
    cfg = SortConfig(p=p, n_per_proc=n_p, algorithm="iran", pair_capacity=tier)
    rng = np.random.default_rng(seed)
    counts = _counts(layout, p, n_p, cfg.pair_cap, cfg.n_max, rng)
    b = np.concatenate([np.zeros((p, 1), np.int64), np.cumsum(counts, 1)], 1)
    x = rng.integers(-(2**31), 2**31 - 1, (p, n_p)).astype(np.int32)
    info = np.iinfo(payload)
    v = rng.integers(info.min, info.max, (p, n_p), dtype=np.int64).astype(payload)
    return cfg, x, b.astype(np.int32), v


def _reference(x, b, v, pair_cap: int, cap: int):
    """Per-key: row j of proc d holds source j's run for d, cut at pair_cap;
    the buffer lays each row's valid prefix at the running offset, and a
    key whose slot reaches cap is dropped."""
    p = x.shape[0]
    counts = np.diff(b.astype(np.int64), axis=1)
    out = {"rcounts": counts.T.astype(np.int32)}
    over = bool((counts > pair_cap).any() or (counts.sum(0) > cap).any())
    for name, a, fill in (("key", x, np.iinfo(x.dtype).max), ("val", v, 0)):
        rows = np.full((p, p, pair_cap), fill, a.dtype)
        buf = np.full((p, cap), fill, a.dtype)
        for d in range(p):
            off = 0
            for s in range(p):
                for t in range(min(counts[s, d], pair_cap)):
                    rows[d, s, t] = a[s, b[s, d] + t]
                    if off + t < cap:
                        buf[d, off + t] = a[s, b[s, d] + t]
                off += counts[s, d]
        out[f"rows_{name}"] = rows
        out[f"buf_{name}"] = buf
    return out, over


def _body(cfg):
    def body(xk, bk, vk):
        rows, rcounts, overflow = routing.recv_rows(xk, bk, cfg, AXIS, [vk])
        bufs = routing.compact_rows(rows, rcounts, cfg.n_max, sentinel_for(xk.dtype))
        return rows[0], rows[1], rcounts, overflow, bufs[0], bufs[1]

    return body


_NAMES = ("rows_key", "rows_val", "rcounts", "overflow", "buf_key", "buf_val")


def _route_vmap(cfg, x, b, v) -> dict:
    out = jax.jit(jax.vmap(_body(cfg), axis_name=AXIS))(x, b, v)
    return {k: np.asarray(a) for k, a in zip(_NAMES, out)}


def _route_sharded(cfg, x, b, v) -> dict:
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    body = _body(cfg)

    def shard(xk, bk, vk):
        return tuple(a[None] for a in body(xk[0], bk[0], vk[0]))

    mesh = Mesh(np.array(jax.devices()[: cfg.p]), (AXIS,))
    fn = jax.shard_map(
        shard, mesh=mesh, in_specs=(P(AXIS),) * 3, out_specs=(P(AXIS),) * 6,
        check_vma=False,
    )
    return {k: np.asarray(a) for k, a in zip(_NAMES, jax.jit(fn)(x, b, v))}


def _cases(p: int):
    for tier in ("whp", "exact"):
        for payload in PAYLOADS:
            for i, layout in enumerate(LAYOUTS):
                yield (tier, payload, layout), _inputs(p, tier, payload, layout, seed=100 * p + i)


def _sharded_main(out_path: str) -> None:
    """Subprocess entry: every case through the shard_map runner."""
    saved = {}
    with jax.enable_x64(True):
        for key, (cfg, x, b, v) in _cases(SHARDED_P):
            for name, a in _route_sharded(cfg, x, b, v).items():
                saved["/".join(key + (name,))] = a
    np.savez(out_path, **saved)


@pytest.fixture(scope="module")
def sharded_outputs(tmp_path_factory):
    out = tmp_path_factory.mktemp("sharded") / "out.npz"
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={SHARDED_P}"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(os.path.dirname(_HERE), "src"), _HERE])
    src = f"import test_routing_windows as t; t._sharded_main({str(out)!r})"
    r = subprocess.run(
        [sys.executable, "-c", src], capture_output=True, text=True, env=env, timeout=600
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    with np.load(out) as f:
        return {k: f[k] for k in f.files}


@pytest.mark.parametrize(
    "runner,p",
    [("vmap", 2), ("vmap", 4), ("vmap", 8), ("sharded", SHARDED_P)],
)
@pytest.mark.parametrize("payload", PAYLOADS)
@pytest.mark.parametrize("tier", ["whp", "exact"])
def test_windowed_rows_match_per_key_reference(runner, p, payload, tier, request):
    """Rows, receive counts, flag and compacted buffers of every layout equal
    the per-key reference bytes, overflowing layouts included."""
    got_all = request.getfixturevalue("sharded_outputs") if runner == "sharded" else None
    clean = []
    with jax.enable_x64(True):
        for i, layout in enumerate(LAYOUTS):
            cfg, x, b, v = _inputs(p, tier, payload, layout, seed=100 * p + i)
            if runner == "vmap":
                got = _route_vmap(cfg, x, b, v)
            else:
                got = {n: got_all["/".join((tier, payload, layout, n))] for n in _NAMES}
            ref, over = _reference(x, b, v, cfg.pair_cap, cfg.n_max)
            assert bool(got["overflow"].any()) == over, layout
            assert got["overflow"].all() == got["overflow"].any(), layout
            for name, want in ref.items():
                have = got[name]
                assert have.dtype == want.dtype, (layout, name)
                assert np.array_equal(have, want), (layout, name)
            if not over:
                clean.append(layout)
    assert clean == ["balanced", "edges"], clean


@pytest.mark.parametrize("algorithm", ["det", "iran", "ran"])
@pytest.mark.parametrize("tier", ["whp", "exact"])
def test_route_program_moves_windows_not_keys(algorithm, tier):
    """Lowering only: in the a2a_dense route program every scatter has
    unique indices, and every ph5_exchange gather takes a whole window of
    pair_cap keys (slice size 1 on the key axis would be one key at a time)."""
    from repro.core.api import SortExecutor

    p, n_p = 8, 4096
    cfg = SortConfig(p=p, n_per_proc=n_p, algorithm=algorithm, pair_capacity=tier)
    tier_cfg = dict(cfg.tier_ladder())[tier]
    assert tier_cfg.routing == "a2a_dense"
    ex = SortExecutor()
    x = jax.ShapeDtypeStruct((p, n_p), jnp.int32)
    prep = jax.eval_shape(ex.prepare_vmap(cfg, 1), x, x)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    module = ex.route_vmap(tier_cfg, 1).lower(prep, rng).compiler_ir("stablehlo")

    ops, stack = [], [module.operation]
    while stack:
        for region in stack.pop().regions:
            for block in region.blocks:
                for op in block.operations:
                    ops.append(op.operation)
                    stack.append(op.operation)

    windows = 0
    for op in ops:
        attrs = op.attributes
        if op.name == "stablehlo.scatter":
            unique = "unique_indices" in attrs and str(attrs["unique_indices"]) == "true"
            assert unique, op.location
        if op.name == "stablehlo.gather" and "ph5_exchange" in str(op.location):
            sizes = str(attrs["slice_sizes"]).split(":")[1].strip(" >").split(",")
            sizes = [int(s) for s in sizes]
            assert sizes[1] == tier_cfg.pair_cap, (sizes, op.location)
            windows += 1
    assert windows > 0
