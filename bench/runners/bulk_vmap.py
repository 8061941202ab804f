"""Bulk sort on one chip: ``bsp_sort_safe`` on the vmap runner.

The configuration gives ``p`` (processors simulated as a vmap axis), ``n``
keys in all, the algorithm, route and first capacity tier. Keys are int32
and resident on the chip; the payload is each key's int32 input position.
Every call goes through the whole overflow-safe driver: Ph2 local sort,
splitters, the Ph5 exchange, the Ph6 merge, and escalation on overflow.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib import closed_loop, gen


class VmapTarget:
    def __init__(self, config, devices, seed: int) -> None:
        from repro.core import SortConfig

        self.p = int(config["p"])
        self.n = int(config["n"])
        self.device = devices[0]
        self.cfg = SortConfig(
            p=self.p,
            n_per_proc=self.n // self.p,
            algorithm=config["algorithm"],
            route=config["route"],
            pair_capacity=config["pair_capacity"],
        )
        self.rng = jax.random.key(gen.substream(seed, 5) % 2**31)
        self.regions = ()
        self._lax_sort = None

    def put(self, keys):
        return jax.device_put(keys, self.device)

    def payload(self):
        p, n_p = self.p, self.n // self.p
        return jax.jit(lambda: jnp.arange(p * n_p, dtype=jnp.int32).reshape(p, n_p))()

    def launch(self, x, v, call: int):
        from repro.core.api import bsp_sort_safe

        return bsp_sort_safe(x, self.cfg, values=[v], rng=jax.random.fold_in(self.rng, call))

    def wait(self, handle) -> None:
        res, vbufs, _ = handle
        jax.block_until_ready((res.buf, res.count, vbufs))

    def tier(self, handle) -> str:
        stats = handle[2]
        return f"{stats.last_tier}/retries={stats.retries}"

    def host_outputs(self, handle):
        import dataclasses

        from repro.core.api import gathered_output

        res, vbufs, _ = handle
        return gathered_output(res), gathered_output(dataclasses.replace(res, buf=vbufs[0]))

    def warm(self, x, v) -> None:
        """Compile (or load) the prepare and first-rung programs: one call."""
        self.wait(self.launch(x, v, 0))

    def after_window(self, x, v, ctx) -> None:
        """Traced runs: one plain ``lax.sort`` of the same keys and payload."""
        if not ctx.trace:
            return
        if self._lax_sort is None:
            self._lax_sort = jax.jit(
                lambda k, p: jax.lax.sort((k.reshape(-1), p.reshape(-1)), num_keys=1, is_stable=True)
            )
        jax.block_until_ready(self._lax_sort(x, v))  # compiled outside the span
        with ctx.annotate("lax_sort"):
            jax.block_until_ready(self._lax_sort(x, v))
        self.regions = ("lax_sort",)

    def release(self) -> None:
        self._lax_sort = None


def run(ctx, target=None):
    return closed_loop.run(ctx, target or VmapTarget(ctx.cell.config, ctx.devices, ctx.seed))
