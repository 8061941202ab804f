"""Distributed (shard_map) paths on 8 host devices (4 for the sharded
sort's check, one per chip of a v5e 2x2) — run in a subprocess so the main
pytest process keeps seeing exactly 1 CPU device (per the brief)."""
import json

import numpy as np
import pytest

from host_devices import run_on_host_devices as _run


def test_sharded_sort_equals_simulated():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core import bsp_sort, bsp_sort_sharded, gathered_output, datagen
        p, n_p = 8, 2048
        mesh = Mesh(np.array(jax.devices()), ("procs",))
        for algo in ["det", "iran", "bitonic"]:
            for dist in ["U", "DD", "WR"]:
                x = jnp.asarray(datagen.generate(dist, p, n_p, seed=7))
                r_sim, _ = bsp_sort(x, algorithm=algo)
                r_shd, _ = bsp_sort_sharded(x, mesh, "procs", algorithm=algo)
                assert np.array_equal(gathered_output(r_sim), gathered_output(r_shd)), (algo, dist)
        print("OK")
    """)
    assert "OK" in out


#: inputs of the overflow-safe sharded sort's check: the paper's [U], [G] and [DD], zipf
#: duplicates, and one input whose every run aims at one bucket (it
#: overflows ``whp`` and must escalate up the ladder)
SHARDED_SAFE_INPUTS = ["U", "G", "DD", "zipf", "adversarial"]


@pytest.fixture(scope="module")
def sharded_safe_runs(tmp_path_factory):
    """``bsp_sort_sharded_safe`` (iran, a2a_dense, whp first) with an int32
    position payload on four host devices, one run per input; each input
    and what came back is saved to ``<name>.npz``."""
    out = tmp_path_factory.mktemp("sharded_safe")
    _run("""
        import dataclasses, json, sys
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        from repro.core import SortConfig, SortExecutor, bsp_sort_sharded_safe, datagen, gathered_output
        names, out = json.loads(sys.argv[1]), sys.argv[2]
        p, n_p = len(jax.devices()), 2048
        mesh = Mesh(np.array(jax.devices()), ("procs",))
        shard = NamedSharding(mesh, PartitionSpec("procs"))
        cfg = SortConfig(p=p, n_per_proc=n_p, algorithm="iran", routing="a2a_dense", pair_capacity="whp")
        pos = jax.device_put(jnp.arange(p * n_p, dtype=jnp.int32).reshape(p, n_p), shard)
        for name in names:
            if name == "adversarial":
                keys = np.repeat((np.arange(p, dtype=np.int32) * 1000)[:, None], n_p, axis=1)
            else:
                keys = datagen.generate(name, p, n_p, seed=11)
            x = jax.device_put(jnp.asarray(keys), shard)
            ex = SortExecutor()
            res, vbufs, st = bsp_sort_sharded_safe(x, mesh, "procs", cfg, values=[pos], executor=ex)
            first = dict(ex.trace_counts)
            # a repeated call with the same mesh and cfg rebuilds no shard_map
            bsp_sort_sharded_safe(x, mesh, "procs", cfg, values=[pos], executor=ex)
            np.savez(
                f"{out}/{name}.npz",
                keys=keys,
                got_keys=gathered_output(res),
                got_payload=gathered_output(dataclasses.replace(res, buf=vbufs[0])),
                retries=st.retries,
                last_tier=st.last_tier,
                traced_once=all(v == 1 for v in first.values()),
                retraced=dict(ex.trace_counts) != first,
                prepare_programs=sum(1 for k in first if k[0] == "prepare"),
            )
        print("OK")
    """, json.dumps(SHARDED_SAFE_INPUTS), str(out), devices=4)
    return out


@pytest.mark.parametrize("name", SHARDED_SAFE_INPUTS)
def test_sharded_safe_sort_matches_the_stable_reference(name, sharded_safe_runs):
    """Keys equal ``np.sort`` and the payload equals the stable argsort,
    exactly; the sort runs one prepare program for every rung it tries
    and re-enters its cached shard_map programs on a repeated call."""
    run = np.load(sharded_safe_runs / f"{name}.npz")
    keys = run["keys"].ravel()
    assert np.array_equal(run["got_keys"], np.sort(keys))
    assert np.array_equal(run["got_payload"], np.argsort(keys, kind="stable"))
    assert run["traced_once"] and not run["retraced"]
    assert run["prepare_programs"] == 1
    if name == "adversarial":
        # escalated past whp and whp2 to the exact rung
        assert run["retries"] >= 1 and str(run["last_tier"]) == "exact"


def test_executor_mesh_keyed_cache_no_cross_mesh_reuse():
    """Two different forced 8-device mesh layouts must get distinct sharded
    cache entries (mesh is part of the key) and re-entry with either mesh
    must hit its own entry — no cross-mesh reuse, no retrace. Scoped-down
    single-host version of the ROADMAP multi-host registry validation."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core import SortConfig, SortExecutor, bsp_sort_sharded, gathered_output, datagen
        p, n_p = 8, 512
        devs = np.array(jax.devices())
        mesh_a = Mesh(devs, ("procs",))
        mesh_b = Mesh(devs[::-1].copy(), ("procs",))  # same devices, other layout
        assert mesh_a != mesh_b
        cfg = SortConfig(p=p, n_per_proc=n_p, algorithm="det")
        ex = SortExecutor()
        x = jnp.asarray(datagen.generate("U", p, n_p, seed=3))
        ra, _ = bsp_sort_sharded(x, mesh_a, "procs", cfg, executor=ex)
        rb, _ = bsp_sort_sharded(x, mesh_b, "procs", cfg, executor=ex)
        keys = list(ex.trace_counts)
        # one ("sort","sharded",cfg,nv,mesh,axis) entry per mesh, each traced once
        assert len(keys) == 2 and all(v == 1 for v in ex.trace_counts.values()), ex.trace_counts
        assert {k[4] for k in keys} == {mesh_a, mesh_b}
        bsp_sort_sharded(x, mesh_a, "procs", cfg, executor=ex)
        bsp_sort_sharded(x, mesh_b, "procs", cfg, executor=ex)
        assert all(v == 1 for v in ex.trace_counts.values())  # cache hits only
        assert np.array_equal(gathered_output(ra), gathered_output(rb))
        print("OK")
    """)
    assert "OK" in out


def test_moe_ep_sharded_matches_dense_reference():
    out = _run("""
        import dataclasses, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_arch
        from repro.models import moe as moe_mod
        mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
        cfg = dataclasses.replace(get_arch("granite-moe-1b-a400m").reduced(),
                                  moe_experts=8, moe_top_k=2, d_model=32, d_ff=16)
        lp = jax.tree.map(lambda a: a[0], moe_mod.init_moe(jax.random.key(0), cfg, 1))
        x = jax.random.normal(jax.random.key(1), (2, 8, 32)).astype(jnp.bfloat16)
        # dense reference (no dispatch)
        x2d = x.reshape(-1, 32)
        probs, experts, _ = moe_mod._router(x2d, lp["router"], 2)
        ref = jnp.zeros_like(x2d)
        for e in range(8):
            w = (probs * (experts == e)).sum(-1).astype(x.dtype)
            ref += w[:, None] * moe_mod._expert_ffn(x2d, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
        ref = ref.reshape(x.shape)
        mi = moe_mod.MoEMeshInfo(mesh=mesh, model_axis="model", data_axes=("data",))
        got, aux = jax.jit(lambda lp, x: moe_mod.moe_ep(lp, x, cfg, mi, capacity_factor=4.0))(lp, x)
        assert not bool(aux["overflow"])
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref, np.float32),
                                   atol=3e-2, rtol=3e-2)
        # decode path (psum dense-eval)
        got2, aux2 = jax.jit(lambda lp, x: moe_mod.moe_ep_decode(lp, x, cfg, mi))(lp, x)
        np.testing.assert_allclose(np.asarray(got2, np.float32), np.asarray(ref, np.float32),
                                   atol=3e-2, rtol=3e-2)
        print("OK")
    """)
    assert "OK" in out


def test_small_mesh_train_step_compiles_and_runs():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.configs import get_arch
        from repro.configs.base import ShapeConfig
        from repro.data import synthetic_batch
        from repro.models import Model
        from repro.optim import OptConfig
        from repro.train import init_all, make_train_step
        mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
        cfg = get_arch("tinyllama-1.1b").reduced()
        model = Model(cfg)
        oc = OptConfig(total_steps=5)
        params, opt = init_all(model, oc, jax.random.key(0))
        step = make_train_step(model, oc, mesh)
        batch = synthetic_batch(cfg, ShapeConfig("t", 32, 4, "train"), 0)
        params, opt, m = step(params, opt, batch)
        assert np.isfinite(float(m["loss"]))
        print("OK", float(m["loss"]))
    """)
    assert "OK" in out
