"""The same SPMD sort on REAL devices via shard_map.

This is the exact code path the multi-pod mesh uses; on a TPU host the mesh
axis spans its chips and lax.all_to_all rides the ICI. On the CPU backend the
XLA flag below simulates 8 devices. p is the number of devices found.

    python examples/distributed_sort_multihost.py     # sets its own XLA flag
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import SortConfig, bsp_sort_sharded, gathered_output, datagen

devices = jax.devices()
p = len(devices)
mesh = Mesh(np.array(devices), ("procs",))
n_per_proc = 1 << 15
x = jnp.asarray(datagen.generate("S", p, n_per_proc, seed=3))  # adversarial staggered

for routing in ("a2a_dense", "ring", "allgather"):
    cfg = SortConfig(p=p, n_per_proc=n_per_proc, algorithm="iran", routing=routing)
    res, _ = bsp_sort_sharded(x, mesh, "procs", cfg)
    ok = np.array_equal(gathered_output(res), np.sort(np.asarray(x).ravel()))
    print(f"routing={routing:10s} sorted={ok} overflow={bool(res.overflow)} "
          f"devices={[d.id for d in devices]}")
