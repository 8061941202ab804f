"""Bulk sort across chips: ``bsp_sort_sharded_safe`` on a 1-D chip mesh.

One processor per chip: the (p, n_per_proc) keys and their int32 input
positions are sharded one row per device, and the Ph5 exchange is a real
collective over the chips' interconnect. Otherwise as ``bulk_vmap``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import closed_loop, gen


class MeshTarget:
    def __init__(self, config, devices, seed: int) -> None:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from repro.core import SortConfig

        self.p = len(devices)
        self.n = int(config["n_per_chip"]) * self.p
        self.mesh = Mesh(np.array(devices), ("procs",))
        self.sharding = NamedSharding(self.mesh, PartitionSpec("procs"))
        self.cfg = SortConfig(
            p=self.p,
            n_per_proc=self.n // self.p,
            algorithm=config["algorithm"],
            routing=config["routing"],
            pair_capacity=config["pair_capacity"],
        )
        self.rng = jax.random.key(gen.substream(seed, 5) % 2**31)
        self.regions = ()

    def put(self, keys):
        return jax.device_put(keys, self.sharding)

    def payload(self):
        p, n_p = self.p, self.n // self.p
        make = jax.jit(
            lambda: jnp.arange(p * n_p, dtype=jnp.int32).reshape(p, n_p), out_shardings=self.sharding
        )
        return make()

    def launch(self, x, v, call: int):
        from repro.core.api import bsp_sort_sharded_safe

        return bsp_sort_sharded_safe(
            x, self.mesh, "procs", self.cfg, values=[v], rng=jax.random.fold_in(self.rng, call)
        )

    def wait(self, handle) -> None:
        res, vbufs, _ = handle
        jax.block_until_ready((res.buf, res.count, vbufs))

    def tier(self, handle) -> str:
        stats = handle[2]
        return f"{stats.last_tier}/retries={stats.retries}"

    def host_outputs(self, handle):
        from repro.core.api import gathered_output

        res, vbufs, _ = handle
        return gathered_output(res), gathered_output(dataclasses.replace(res, buf=vbufs[0]))

    def warm(self, x, v) -> None:
        self.wait(self.launch(x, v, 0))

    def after_window(self, x, v, ctx) -> None:
        pass

    def release(self) -> None:
        pass


def run(ctx, target=None):
    return closed_loop.run(ctx, target or MeshTarget(ctx.cell.config, ctx.devices, ctx.seed))
