"""Ph2 — local sequential sort, dispatching on the configured method.

``lax``    — XLA's stable comparison sort (the [·SQ]/quicksort role).
``radix``  — linear-work counting-split (the [·SR]/radixsort role).
``bitonic``— Pallas in-VMEM sorting network (TPU hot path; interpret mode on
             CPU). Falls back to ``lax`` when the kernel does not support the
             shape/dtype.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax.numpy as jnp
from jax import lax

from .primitives import superstep
from .radix import radix_argsort


@superstep("ph2_local_sort")
def local_sort(
    x: jnp.ndarray, method: str = "lax", values: Sequence[jnp.ndarray] = ()
) -> Tuple[jnp.ndarray, List[jnp.ndarray]]:
    """Stable local sort of (n_p,) keys, carrying optional payload arrays."""
    if method == "radix" and jnp.issubdtype(x.dtype, jnp.integer):
        order = radix_argsort(x)
        return x[order], [v[order] for v in values]
    if method == "bitonic":
        from repro.kernels.bitonic import ops as bitonic_ops  # lazy: optional layer

        if not values and bitonic_ops.supports(x):
            return bitonic_ops.sort(x), []
        # key-value / unsupported shapes: fall through to lax
    if not values:
        (out,) = lax.sort((x,), num_keys=1, is_stable=True)
        return out, []
    if all(v.ndim == 1 for v in values):
        # 1-D payloads ride the one fused sorting network (stable, so the
        # permutation is identical to the argsort+gather path)
        out = lax.sort((x, *values), num_keys=1, is_stable=True)
        return out[0], list(out[1:])
    perm = jnp.argsort(x, stable=True)
    return x[perm], [v[perm] for v in values]
