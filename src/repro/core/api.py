"""Public entry points for the BSP sorting library.

Two runners share one SPMD implementation (verified equivalent in tests):

* :func:`bsp_sort` — *simulated processors*: the global (p, n_per_proc)
  layout is vmapped with an ``axis_name``, so JAX's collective batching rules
  execute the exact same collective pattern on one device. This is how the
  paper's Cray T3D experiments (p = 8..128) are reproduced on CPU.
* :func:`bsp_sort_sharded` — *real devices*: the same SPMD function under
  ``jax.shard_map`` over a mesh axis; used by the multi-pod dry-run, the MoE
  dispatch layer, and the distributed tests.

Execution model — the resumable phase pipeline
-----------------------------------------------
Every algorithm body is an explicit two-stage pipeline:

* ``prepare(x) -> PreparedSort`` — Ph2 local sort plus whatever sampling
  state is *capacity-tier-invariant* (for ``det``, the full Ph3
  sample/splitter computation; for ``iran``/``ran`` nothing random — a retry
  must redraw its sample);
* ``route(prepared, tier_cfg, rng) -> (buf, vals, count, overflow)`` —
  Ph3b/Ph4/Ph5/Ph6, the only stages that depend on the capacity tier.

Because a sort may never drop keys, production callers use the *overflow-safe
drivers* :func:`bsp_sort_safe` / :func:`bsp_sort_sharded_safe`: a host-side
escalation loop that runs ``prepare`` **once**, then re-enters only ``route``
at each rung of the config's capacity-tier ladder (``SortConfig.tier_ladder``:
whp → whp×2 → exact → allgather/full) until the ``overflow`` fault flag is
clean. The rng is folded per tier so a randomized retry is an independent
splitter trial. Re-using the tier-invariant work cuts the retry cost by the
Ph2 share of a tier attempt — ~2× end-to-end for the radix local-sort
variants, measured (not asserted) by the ``capacity`` benchmark table's
``retry_cost`` column. Per-tier attempt counters (:class:`TierStats`) feed
the serving engine and the benchmark tables.

The route stage's Ph5 exchange is *fused* by default
(``SortConfig.exchange="fused"``): key + payload rows are word-packed into
one send buffer so each data superstep issues exactly ONE collective
regardless of payload count, and the Ph6 ``merge="tree"`` tail is
payload-generic — rank positions are computed once on the keys and every
payload rides the same gather, so key-value callers (MoE dispatch, the
segmented service composites) take the lg p rank-merge tail instead of a
full re-sort (see ``core/routing.py`` and the ``hotpath`` benchmark table).

Compiled callables for *both* runners live in a :class:`SortExecutor`
registry keyed by ``(stage, runner, cfg, n_values[, mesh])`` — prepare
callables additionally key on ``SortConfig.prepare_key()`` so every rung of
a ladder shares one compiled prepare, and repeated sharded calls with the
same mesh/config stop rebuilding ``shard_map`` (the registry counts traces,
so tests can assert compile reuse).

Phase-decomposed callables for the paper's Table 4-7 timing methodology are
exposed via :func:`phase_fns`; they are a thin view over the same pipeline
stage functions (``local_sort`` / ``splitters.splitter_stage`` /
``searchsorted_tagged`` / ``routing.route`` / ``merge``), not a parallel
reimplementation.

The service layer — many concurrent sorts as one
------------------------------------------------
Above these drivers sits the *sort service* (``repro.service``), the layer
consumers use when traffic is many small/ragged requests rather than one
big array:

* **segment tagging** (``core/segmented.py``) — a batch of R requests is
  fused into ONE sort by lifting each key to the int64 composite
  ``(segment_id << 32) | biased(key)``: the paper's §5.1.1 duplicate tag
  generalized to a segment tag. One balanced sort returns every segment
  contiguous and sorted, with splitters drawn from the shared oversample
  landing inside each segment in proportion to its size;
* **batch former** (``service/batch.py``) — ragged requests are packed
  greedily (FIFO) into batches quantized to power-of-two
  ``n_per_proc`` buckets, so arbitrary traffic shares O(log n) compiled
  programs through this module's :class:`SortExecutor` registry;
* **escalation per batch** (``service/service.py``) — each fused batch
  runs through :func:`bsp_sort_safe`'s capacity ladder independently, so
  an adversarial request escalates only its own batch, and per-request
  latency plus :class:`TierStats` counters surface as service telemetry;
* **capacity planning** (``repro.planner``) — the batch's starting tier
  and oversampling ratio come from a workload fingerprint + the
  segment-aware whp bound (``pair_capacity="planned"`` over the striped
  packing layout), adapted per fingerprint bucket by observed fault
  rates. The same planner object optionally drives :func:`bsp_sort_safe`
  and ``moe_ep_safe`` ladder starts (``planner=``).

Serve admission ordering (``serve/engine.py``) and data-pipeline length
bucketing (``data/pipeline.py``) are service consumers.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from . import merge as merge_mod
from . import routing, splitters
from .bitonic import sort_bitonic_spmd
from .local_sort import local_sort
from .sort_det import prepare_det_spmd, route_det_spmd, sort_det_spmd
from .sort_iran import prepare_iran_spmd, route_iran_spmd, sort_iran_spmd
from .sort_radix import (
    host_send_counts,
    prepare_radix_spmd,
    route_radix_spmd,
    sort_radix_spmd,
)
from .sort_ran import prepare_ran_spmd, route_ran_spmd, sort_ran_spmd
from .types import AXIS, PreparedSort, SortConfig, SortResult
from ..chaos import resolve_chaos
from ..obs import REGISTRY as _OBS
from ..obs import resolve_tracer

_ALGOS = {
    "det": sort_det_spmd,
    "iran": sort_iran_spmd,
    "ran": sort_ran_spmd,
    "bitonic": sort_bitonic_spmd,
}


def _prepare_bitonic_spmd(x, cfg, axis, values=(), rng=None):
    """[BSI] is perfectly balanced (single-rung ladder): nothing to carry."""
    del rng
    return PreparedSort(xs=x, vals=tuple(values), splits=None)


def _route_bitonic_spmd(prep, cfg, axis, rng=None):
    return sort_bitonic_spmd(prep.xs, cfg, axis, values=list(prep.vals), rng=rng)


#: algorithm -> (prepare, route); sort body == route(prepare(x)).
_PIPELINES = {
    "det": (prepare_det_spmd, route_det_spmd),
    "iran": (prepare_iran_spmd, route_iran_spmd),
    "ran": (prepare_ran_spmd, route_ran_spmd),
    "bitonic": (_prepare_bitonic_spmd, _route_bitonic_spmd),
}


def spmd_sort_fn(cfg: SortConfig) -> Callable:
    """The per-processor SPMD sort body for ``cfg``.

    ``route="radix"`` selects the count-then-distribute pipeline
    (``sort_radix.py``) regardless of ``algorithm`` — the distribution
    route replaces Ph3..Ph4, not the Ph2 local method.
    """
    cfg.validate()
    if cfg.route == "radix":
        return functools.partial(sort_radix_spmd, cfg=cfg)
    return functools.partial(_ALGOS[cfg.algorithm], cfg=cfg)


def spmd_prepare_fn(cfg: SortConfig) -> Callable:
    """The tier-invariant prepare stage for ``cfg``."""
    cfg.validate()
    if cfg.route == "radix":
        return functools.partial(prepare_radix_spmd, cfg=cfg)
    return functools.partial(_PIPELINES[cfg.algorithm][0], cfg=cfg)


def spmd_route_fn(cfg: SortConfig) -> Callable:
    """The tier-dependent route stage for ``cfg``."""
    cfg.validate()
    if cfg.route == "radix":
        return functools.partial(route_radix_spmd, cfg=cfg)
    return functools.partial(_PIPELINES[cfg.algorithm][1], cfg=cfg)


# ------------------------------------------------------------------ runners
def bsp_sort(
    x: jnp.ndarray,
    cfg: Optional[SortConfig] = None,
    *,
    values: Sequence[jnp.ndarray] = (),
    rng: Optional[jax.Array] = None,
    **overrides,
) -> SortResult:
    """Sort a (p, n_per_proc) global array with simulated processors."""
    p, n_p = x.shape
    if cfg is None:
        cfg = SortConfig(p=p, n_per_proc=n_p, **overrides)
    assert (cfg.p, cfg.n_per_proc) == (p, n_p), "config/layout mismatch"
    if rng is None:
        rng = jax.random.key(cfg.seed)
    fn = spmd_sort_fn(cfg)

    def body(xk, vk):
        buf, vbufs, count, overflow = fn(xk, axis=AXIS, values=vk, rng=rng)
        return buf, vbufs, count, overflow

    buf, vbufs, count, overflow = jax.vmap(body, axis_name=AXIS)(x, list(values))
    return SortResult(buf=buf, count=count, overflow=overflow.any()), vbufs


def bsp_sort_sharded(
    x: jnp.ndarray,
    mesh,
    mesh_axis: str,
    cfg: Optional[SortConfig] = None,
    *,
    values: Sequence[jnp.ndarray] = (),
    rng: Optional[jax.Array] = None,
    executor: Optional["SortExecutor"] = None,
    **overrides,
) -> SortResult:
    """Sort a (p, n_per_proc) array sharded over ``mesh_axis`` of ``mesh``.

    The shard-mapped callable comes from the executor registry, so repeated
    calls with the same (mesh, cfg, n_values) reuse one compiled program.
    """
    p, n_p = x.shape
    if cfg is None:
        cfg = SortConfig(p=p, n_per_proc=n_p, **overrides)
    if cfg.obs is not None or cfg.chaos is not None:
        # obs/chaos are hash-excluded, but strip them so executor keys
        # never pin a Tracer or FaultPlan
        cfg = dataclasses.replace(cfg, obs=None, chaos=None)
    if rng is None:
        rng = jax.random.key(cfg.seed)
    ex = executor if executor is not None else _EXECUTOR
    fn = ex.sort_sharded(cfg, mesh, mesh_axis, len(values))
    buf, vbufs, count, overflow = fn(jax.random.key_data(rng), x, *values)
    return SortResult(buf=buf, count=count, overflow=overflow.any()), list(vbufs)


# ------------------------------------------------- overflow-safe drivers
@dataclasses.dataclass
class TierStats:
    """Per-tier attempt counters for the capacity-escalation driver.

    ``attempts[tier]`` counts runs started at that tier, ``successes[tier]``
    the runs whose overflow flag was clean. Accumulates across calls when the
    same instance is passed back in, so a serving engine or benchmark loop
    gets "how often did w.h.p. capacity actually suffice" for free.
    """

    attempts: Dict[str, int] = dataclasses.field(default_factory=dict)
    successes: Dict[str, int] = dataclasses.field(default_factory=dict)
    last_tier: Optional[str] = None
    retries: int = 0  # total re-runs forced by overflow faults

    def record(self, tier: str, ok: bool) -> None:
        # Mirror every attempt into the process-wide metrics registry;
        # merge_from deliberately does NOT re-mirror (the per-batch record
        # already counted each attempt once).
        self.attempts[tier] = self.attempts.get(tier, 0) + 1
        _OBS.counter("sort.tier_attempts", tier=tier).inc()
        if ok:
            self.successes[tier] = self.successes.get(tier, 0) + 1
            self.last_tier = tier
            _OBS.counter("sort.tier_ok", tier=tier).inc()
        else:
            self.retries += 1
            _OBS.counter("sort.retries").inc()

    def merge_from(self, other: "TierStats") -> None:
        """Fold another instance's counters in (per-batch → accumulator).

        Lets a caller observe one dispatch in isolation (e.g. the capacity
        planner's fault feedback) while still accumulating service-wide
        telemetry in a shared instance.
        """
        for t, n in other.attempts.items():
            self.attempts[t] = self.attempts.get(t, 0) + n
        for t, n in other.successes.items():
            self.successes[t] = self.successes.get(t, 0) + n
        self.retries += other.retries
        if other.last_tier is not None:
            self.last_tier = other.last_tier

    def as_row(self) -> Dict[str, int]:
        """Flat counter row: attempts, clean-run counts, total retries.

        Successes are kept per tier (not just ``last_tier``) because one
        accumulating instance spans many calls — ``ok_whp/tier_whp`` is the
        long-run "how often did w.h.p. capacity suffice" rate.
        """
        row = {f"tier_{t}": n for t, n in self.attempts.items()}
        row |= {f"ok_{t}": n for t, n in self.successes.items()}
        row["retries"] = self.retries
        return row


class SortExecutor:
    """Registry of compiled sort callables for both runners.

    One instance (the module-level default) serves the whole process; tests
    may pass a fresh instance to the drivers for isolation. Callables are
    keyed by ``(stage, runner, cfg, n_values[, mesh, mesh_axis])`` where

    * ``prepare`` entries key on ``cfg.prepare_key()`` — every rung of a
      capacity ladder shares one compiled prepare callable and hence one
      :class:`PreparedSort`;
    * ``route``/``sort`` entries key on the full tier config (frozen
      dataclass, hashable — each rung compiles exactly once per process);
    * sharded entries additionally key on ``(mesh, mesh_axis)``, which is
      what stops ``bsp_sort_sharded_safe`` from rebuilding ``shard_map``
      per call (``jax.sharding.Mesh`` hashes by devices + axis names).

    ``trace_counts[key]`` increments every time JAX actually (re)traces the
    callable, so regression tests can assert compile reuse directly.

    All callables take the rng as raw ``jax.random.key_data`` (a (2,) uint32
    array) rather than a typed key: key data passes uniformly through jit
    *and* ``shard_map`` in/out specs.
    """

    def __init__(self) -> None:
        self._fns: Dict[tuple, Callable] = {}
        self.trace_counts: Dict[tuple, int] = {}

    def _get(self, key: tuple, build: Callable[[], Callable]) -> Callable:
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = build()
        return fn

    def _count_trace(self, key: tuple) -> None:
        # Runs at trace time only (it is Python, not jaxpr), so the count is
        # exactly the number of (re)compilations of this callable.
        self.trace_counts[key] = self.trace_counts.get(key, 0) + 1

    # ------------------------------------------------------- vmap runner
    def prepare_vmap(self, cfg: SortConfig, n_values: int) -> Callable:
        pcfg = cfg.prepare_key()
        key = ("prepare", "vmap", pcfg, n_values)

        def build():
            prepare = spmd_prepare_fn(pcfg)

            def run(x, *vals):
                self._count_trace(key)

                def body(xk, vk):
                    return prepare(xk, axis=AXIS, values=vk)

                return jax.vmap(body, axis_name=AXIS)(x, list(vals))

            return jax.jit(run)

        return self._get(key, build)

    def route_vmap(self, tier_cfg: SortConfig, n_values: int) -> Callable:
        key = ("route", "vmap", tier_cfg, n_values)

        def build():
            route = spmd_route_fn(tier_cfg)

            def run(prep, rng_data):
                self._count_trace(key)
                rng = jax.random.wrap_key_data(rng_data)

                def body(prep_k):
                    return route(prep_k, axis=AXIS, rng=rng)

                return jax.vmap(body, axis_name=AXIS)(prep)

            return jax.jit(run)

        return self._get(key, build)

    def sort_vmap(self, cfg: SortConfig, n_values: int) -> Callable:
        """Monolithic prepare∘route in one program (fresh runs, benchmarks)."""
        key = ("sort", "vmap", cfg, n_values)

        def build():
            fn = spmd_sort_fn(cfg)

            def run(x, rng_data, *vals):
                self._count_trace(key)
                rng = jax.random.wrap_key_data(rng_data)

                def body(xk, vk):
                    return fn(xk, axis=AXIS, values=vk, rng=rng)

                return jax.vmap(body, axis_name=AXIS)(x, list(vals))

            return jax.jit(run)

        return self._get(key, build)

    # ---------------------------------------------------- sharded runner
    def _prep_specs(self, cfg: SortConfig, mesh_axis: str, n_values: int):
        if cfg.route == "radix":
            splits_spec = (P(mesh_axis),)  # counted (p+1,) boundaries
        elif cfg.algorithm == "det":
            splits_spec = (P(mesh_axis),) * 3
        else:
            splits_spec = None
        return PreparedSort(
            xs=P(mesh_axis), vals=(P(mesh_axis),) * n_values, splits=splits_spec
        )

    def prepare_sharded(
        self, cfg: SortConfig, mesh, mesh_axis: str, n_values: int
    ) -> Callable:
        pcfg = cfg.prepare_key()
        key = ("prepare", "sharded", pcfg, n_values, mesh, mesh_axis)

        def build():
            prepare = spmd_prepare_fn(pcfg)

            def body(xk, *vk):
                prep = prepare(xk[0], axis=mesh_axis, values=[v[0] for v in vk])
                return jax.tree.map(lambda a: a[None], prep)

            shmapped = jax.shard_map(
                body,
                mesh=mesh,
                check_vma=False,
                in_specs=(P(mesh_axis),) * (1 + n_values),
                out_specs=self._prep_specs(pcfg, mesh_axis, n_values),
            )

            def run(x, *vals):
                self._count_trace(key)
                return shmapped(x, *vals)

            return jax.jit(run)

        return self._get(key, build)

    def route_sharded(
        self, tier_cfg: SortConfig, mesh, mesh_axis: str, n_values: int
    ) -> Callable:
        key = ("route", "sharded", tier_cfg, n_values, mesh, mesh_axis)

        def build():
            route = spmd_route_fn(tier_cfg)

            def body(prep, rng_data):
                prep_k = jax.tree.map(lambda a: a[0], prep)
                rng = jax.random.wrap_key_data(rng_data)
                buf, vbufs, count, overflow = route(prep_k, axis=mesh_axis, rng=rng)
                return (
                    buf[None],
                    tuple(v[None] for v in vbufs),
                    count[None],
                    overflow[None],
                )

            shmapped = jax.shard_map(
                body,
                mesh=mesh,
                check_vma=False,
                in_specs=(
                    self._prep_specs(tier_cfg, mesh_axis, n_values),
                    P(),
                ),
                out_specs=(
                    P(mesh_axis),
                    (P(mesh_axis),) * n_values,
                    P(mesh_axis),
                    P(mesh_axis),
                ),
            )

            def run(prep, rng_data):
                self._count_trace(key)
                return shmapped(prep, rng_data)

            return jax.jit(run)

        return self._get(key, build)

    def sort_sharded(
        self, cfg: SortConfig, mesh, mesh_axis: str, n_values: int
    ) -> Callable:
        key = ("sort", "sharded", cfg, n_values, mesh, mesh_axis)

        def build():
            fn = spmd_sort_fn(cfg)

            def body(rng_data, xk, *vk):
                rng = jax.random.wrap_key_data(rng_data)
                buf, vbufs, count, overflow = fn(
                    xk[0], axis=mesh_axis, values=[v[0] for v in vk], rng=rng
                )
                return (
                    buf[None],
                    tuple(v[None] for v in vbufs),
                    count[None],
                    overflow[None],
                )

            shmapped = jax.shard_map(
                body,
                mesh=mesh,
                check_vma=False,
                in_specs=(P(),) + (P(mesh_axis),) * (1 + n_values),
                out_specs=(
                    P(mesh_axis),
                    (P(mesh_axis),) * n_values,
                    P(mesh_axis),
                    P(mesh_axis),
                ),
            )

            def run(rng_data, x, *vals):
                self._count_trace(key)
                return shmapped(rng_data, x, *vals)

            return jax.jit(run)

        return self._get(key, build)


#: process-wide default registry; drivers accept ``executor=`` for isolation.
_EXECUTOR = SortExecutor()


def default_executor() -> SortExecutor:
    return _EXECUTOR


class InFlightSort:
    """A *launched* overflow-safe sort whose completion has not been awaited.

    Construction dispatches the first ladder rung's route stage to the
    device queue and returns immediately — JAX's async dispatch means the
    host is free while the device executes, so a caller can plan/pack/launch
    the *next* batch before blocking here. :meth:`wait` is the only sync
    point: it reads the rung's overflow flag (the escalation decision) and,
    on a fault, launches the next rung — the same escalation loop
    ``bsp_sort_safe`` always ran, split at the host-sync boundary.

    The rng is folded per tier so a randomized retry is an independent trial
    (re-drawing the failed splitter sample would correlate failures).
    ``run_tier(tier_cfg, tier_rng) -> (SortResult, value_bufs)``. ``ladder``
    is (a suffix of) ``SortConfig.tier_ladder()`` — a planner policy may
    have sliced the doomed cheap rungs off the front. ``scope`` is a context
    factory entered around every device launch (the segmented service needs
    ``enable_x64`` re-entered when escalation re-launches from ``wait``);
    ``on_complete(stats)`` fires once, after the winning rung — completion-
    callback hooks (planner feedback) ride it instead of blocking the
    launcher. ``wait`` is idempotent: the result is cached.

    ``tracer``/``trace_meta`` (``repro.obs``) record one "route" span per
    rung — opened at the device launch here or in :meth:`wait`'s escalation,
    closed at the overflow host-sync — carrying the rung's traced h-relation
    size, superstep count, and received-key balance. Both default to off and
    only ever touch host-side bookkeeping around the jitted calls.
    """

    def __init__(
        self,
        ladder: tuple,
        rng: jax.Array,
        stats: Optional[TierStats],
        run_tier: Callable,
        *,
        scope: Optional[Callable] = None,
        on_complete: Optional[Callable] = None,
        tracer=None,
        trace_meta: Optional[Dict] = None,
        chaos=None,
    ) -> None:
        self.stats = stats if stats is not None else TierStats()
        self._ladder = ladder
        self._rng = rng
        self._run_tier = run_tier
        self._scope = scope if scope is not None else contextlib.nullcontext
        self._on_complete = on_complete
        self._tracer = tracer
        # chaos capacity-fault injection: a host-side flip of the overflow
        # decision for non-terminal rungs only (repro.chaos.FaultPlan) —
        # the escalation it forces is the real recovery path, and the next
        # rung's result is byte-identical to an unfaulted run's
        self._chaos = chaos
        self._chaos_key = chaos.next_sort() if chaos is not None else 0
        self._meta = trace_meta if trace_meta is not None else {}
        #: timeline lane of this sort's spans (None when untraced) — the
        #: segmented service uses it to attach its own points to the lane.
        self.trace_tid = self._meta.get("tid") if tracer is not None else None
        self._out: Optional[Tuple[SortResult, List[jnp.ndarray], TierStats]] = None
        self._i = 0
        self._t_launch = tracer.now() if tracer is not None else 0.0
        with self._scope():
            self._pending = run_tier(ladder[0][1], jax.random.fold_in(rng, 0))

    def done(self) -> bool:
        """Whether :meth:`wait` has already resolved (never blocks)."""
        return self._out is not None

    def _record_route(self, res: SortResult, tier: str, tier_cfg, ok, t_sync):
        """Close the launch-opened route span at the overflow host-sync."""
        tr = self._tracer
        t_end = tr.now()
        cat = self._meta.get("cat", "sort")
        tid = self.trace_tid or "main"
        counts = np.asarray(res.count)
        recv_max = int(counts.max())
        recv_mean = float(counts.mean())
        row_bytes = int(self._meta.get("row_bytes", 4))
        # h of the route stage in 32-bit words: the larger of what any proc
        # sent (its n_per_proc rows) and what any proc received, times the
        # packed row width of the fused exchange.
        h_words = (max(recv_max, tier_cfg.n_per_proc) * row_bytes) // 4
        args = dict(
            tier=tier,
            rung=self._i,
            runner=self._meta["runner"],
            routing=tier_cfg.routing,
            ok=ok,
            sync_s=round(t_end - t_sync, 6),
            h_words=h_words,
            supersteps=routing.route_supersteps(tier_cfg.routing, tier_cfg.p),
            recv_max=recv_max,
            recv_mean=recv_mean,
            imbalance=(recv_max / recv_mean) if recv_mean > 0 else 1.0,
        )
        if tier_cfg.p <= 64:
            args["recv"] = counts.tolist()  # per-proc key counts
        tr.add_span("route", self._t_launch, t_end=t_end, cat=cat, tid=tid, **args)
        tr.point("host_sync", cat=cat, tid=tid, what="overflow", rung=self._i, ok=ok)

    def wait(self) -> Tuple[SortResult, List[jnp.ndarray], TierStats]:
        """Block until a rung's overflow flag is clean; escalate on faults."""
        if self._out is not None:
            return self._out
        while True:
            res, vbufs = self._pending
            tier, tier_cfg = self._ladder[self._i]
            t_sync = self._tracer.now() if self._tracer is not None else 0.0
            ok = not bool(res.overflow)  # host sync: the retry decision point
            if (
                ok
                and self._chaos is not None
                and self._i + 1 < len(self._ladder)  # never fault terminal
                and self._chaos.fault_capacity(self._chaos_key, self._i)
            ):
                ok = False  # injected capacity fault: walk the next rung
                if self._tracer is not None:
                    self._tracer.point(
                        "chaos_capacity_fault",
                        cat="chaos",
                        tid=self.trace_tid or "main",
                        rung=self._i,
                        tier=tier,
                    )
            if self._tracer is not None:
                self._record_route(res, tier, tier_cfg, ok, t_sync)
            self.stats.record(tier, ok)
            if ok:
                self._out = (res, vbufs, self.stats)
                if self._on_complete is not None:
                    self._on_complete(self.stats)
                return self._out
            self._i += 1
            if self._i >= len(self._ladder):
                raise RuntimeError(
                    "capacity escalation exhausted — unreachable: the "
                    "allgather/full tier cannot overflow (ladder: "
                    f"{[t for t, _ in self._ladder]})"
                )
            if self._tracer is not None:
                self._t_launch = self._tracer.now()
            with self._scope():
                self._pending = self._run_tier(
                    self._ladder[self._i][1],
                    jax.random.fold_in(self._rng, self._i),
                )


def _escalate(
    ladder: tuple,
    rng: jax.Array,
    stats: Optional[TierStats],
    run_tier: Callable,
    *,
    tracer=None,
    trace_meta: Optional[Dict] = None,
) -> Tuple[SortResult, List[jnp.ndarray], TierStats]:
    """Blocking escalation: launch rung 0 and wait through the ladder."""
    return InFlightSort(
        ladder, rng, stats, run_tier, tracer=tracer, trace_meta=trace_meta
    ).wait()


def _trace_meta_for(tracer, x, values, runner: str, cat: str = "sort") -> Optional[Dict]:
    """Per-launch trace metadata: a fresh timeline lane, the runner
    (``"vmap"`` or ``"sharded"``) and the packed row width."""
    if tracer is None:
        return None
    return {
        "tid": tracer.next_tid("sort"),
        "cat": cat,
        "runner": runner,
        "row_bytes": routing.packed_row_bytes(x.dtype, [v.dtype for v in values]),
    }


def _prepare_span(tracer, meta: Optional[Dict], cfg: SortConfig):
    """The host's launch of the prepare stage as a ``prepare`` span.

    It does not wait for the device: the stage's device time is in the
    device trace, under its superstep scopes, so a traced run keeps the
    untraced schedule. A null context when untraced.
    """
    if tracer is None:
        return contextlib.nullcontext()
    return tracer.span(
        "prepare",
        tid=meta["tid"],
        runner=meta["runner"],
        algorithm=cfg.algorithm,
        route=cfg.route,
        routing=cfg.routing,
        p=cfg.p,
        n_per_proc=cfg.n_per_proc,
    )


def _radix_exact_ladder(cfg: SortConfig, prep: PreparedSort) -> tuple:
    """The radix route's whole ladder: ONE rung at the host-counted capacity.

    ``prep.splits[0]`` carries the counted (p, p+1) bucket boundaries, so
    the true per-(src,dst) maximum and the true receive total are known
    *before any data moves* — a (p², ) int32 host read (the launch path's
    only extra sync; the boundaries were computed by prepare anyway). Both
    bounds are quantized up to ~16 octave steps (a relative 1/16 grid, so
    a balanced batch's pair capacity stays within ~6% of the true n_p/p
    count instead of rounding to a coarse absolute step) — nearby batches
    share compiled route programs while distinct capacities stay
    logarithmic in n_p. Then clamped to the exact-tier sizes — the rung
    can never exceed what ``pair_capacity="exact"`` + ``n_max_mode="full"``
    would have allocated, and since cap ≥ true count on every pair,
    overflow (and hence any retry) is impossible.
    """
    sendc = host_send_counts(prep.splits[0])  # counts[src, dst]
    pair_true = int(sendc.max())
    recv_true = int(sendc.sum(axis=0).max())

    def _quant(true, hi):
        step = max(cfg.pad_align, 1 << max(0, true.bit_length() - 4))
        return min(hi, -(-max(true, 1) // step) * step)

    qpair = _quant(pair_true, cfg.n_per_proc)
    qrecv = _quant(recv_true, cfg.n)
    tier = dataclasses.replace(
        cfg,
        pair_capacity="planned",
        pair_cap_override=qpair,
        capacity_factor=1.0,
        n_max_mode="bound",
        n_max_override=qrecv,
    )
    return (("radix", tier),)


def bsp_sort_safe_launch(
    x: jnp.ndarray,
    cfg: Optional[SortConfig] = None,
    *,
    values: Sequence[jnp.ndarray] = (),
    rng: Optional[jax.Array] = None,
    stats: Optional[TierStats] = None,
    executor: Optional[SortExecutor] = None,
    resume: bool = True,
    planner=None,
    scope: Optional[Callable] = None,
    **overrides,
) -> InFlightSort:
    """Launch an overflow-safe sort without awaiting it.

    ``prepare`` plus the first ladder rung's ``route`` are dispatched to the
    device queue and an :class:`InFlightSort` is returned immediately —
    the caller overlaps host work (planning the next batch) with the device
    execution and blocks only at :meth:`InFlightSort.wait`. The async
    service dispatcher (``repro.service.dispatch``) is the primary consumer.

    ``planner`` (a :class:`repro.planner.CapacityPlanner`) is an optional
    traffic-learned policy: repeated sorts of the same shape/config that
    keep faulting their cheap rung start one rung up next time (and probe
    back down after a clean streak) — the ladder above the learned start is
    unchanged, so safety is untouched. Its outcome feedback runs as a
    completion callback on ``wait``. ``scope`` is a context factory entered
    around every device launch (``enable_x64`` for int64 composites).
    """
    p, n_p = x.shape
    if cfg is None:
        cfg = SortConfig(p=p, n_per_proc=n_p, **overrides)
    tracer = resolve_tracer(cfg.obs)
    chaos = resolve_chaos(cfg.chaos)
    if cfg.obs is not None or cfg.chaos is not None:
        # Hold the tracer/chaos plan as locals only: the cfg the ladder/
        # executor see carries obs=None/chaos=None, so registry keys never
        # pin a Tracer or FaultPlan. (Both are hash/compare-excluded —
        # this changes no cache key.)
        cfg = dataclasses.replace(cfg, obs=None, chaos=None)
    meta = _trace_meta_for(tracer, x, values, "vmap")
    if rng is None:
        rng = jax.random.key(cfg.seed)
    ex = executor if executor is not None else _EXECUTOR
    nv = len(values)

    ladder = cfg.tier_ladder()
    bucket = None
    if planner is not None and len(ladder) > 1:
        bucket = (
            f"sort/{cfg.algorithm}/p{p}/npp{n_p}/{cfg.pair_capacity}"
        )
        ladder = ladder[planner.rung_for(bucket, len(ladder)) :]
    stats = stats if stats is not None else TierStats()
    retries_before = stats.retries

    on_complete = None
    if bucket is not None:
        n_rungs = len(cfg.tier_ladder())

        def on_complete(st: TierStats, _bucket=bucket) -> None:
            planner.observe(_bucket, st.retries > retries_before, n_rungs)

    if not resume:

        def run_tier(tier_cfg, tier_rng):
            fn = ex.sort_vmap(tier_cfg, nv)
            buf, vbufs, count, overflow = fn(
                x, jax.random.key_data(tier_rng), *values
            )
            return SortResult(buf=buf, count=count, overflow=overflow.any()), list(
                vbufs
            )

    else:
        # Ph2 (+ det Ph3, or the radix counting pass), exactly once — inside
        # the scope: the prepare stage consumes the (possibly int64) input
        # directly
        def _prepare():
            if scope is not None:
                with scope():
                    return ex.prepare_vmap(cfg, nv)(x, *values)
            return ex.prepare_vmap(cfg, nv)(x, *values)

        with _prepare_span(tracer, meta, cfg):
            prep = _prepare()
        if cfg.route == "radix":
            # counts are in hand: collapse the ladder to one rung sized to
            # the true maxima — zero retries by construction
            if tracer is not None:
                tracer.point(
                    "host_sync", tid=meta["tid"], what="radix_counts"
                )
            ladder = _radix_exact_ladder(cfg, prep)

        def run_tier(tier_cfg, tier_rng):
            fn = ex.route_vmap(tier_cfg, nv)
            buf, vbufs, count, overflow = fn(prep, jax.random.key_data(tier_rng))
            return SortResult(buf=buf, count=count, overflow=overflow.any()), list(
                vbufs
            )

    return InFlightSort(
        ladder,
        rng,
        stats,
        run_tier,
        scope=scope,
        on_complete=on_complete,
        tracer=tracer,
        trace_meta=meta,
        chaos=chaos,
    )


def bsp_sort_safe(
    x: jnp.ndarray,
    cfg: Optional[SortConfig] = None,
    *,
    values: Sequence[jnp.ndarray] = (),
    rng: Optional[jax.Array] = None,
    stats: Optional[TierStats] = None,
    executor: Optional[SortExecutor] = None,
    resume: bool = True,
    planner=None,
    **overrides,
) -> Tuple[SortResult, List[jnp.ndarray], TierStats]:
    """Overflow-safe :func:`bsp_sort`: escalate through the capacity ladder.

    Runs ``prepare`` once, then the jitted ``route`` stage at each tier of
    ``cfg.tier_ladder()``; the first tier whose ``overflow`` flag is clean
    wins. The terminal tier holds the whole input, so no key is ever dropped
    regardless of skew or adversarial placement. ``resume=False`` falls back
    to re-running the whole sort per rung (the pre-pipeline behaviour, kept
    for the ``retry_cost`` benchmark comparison). Returns
    ``(result, value_bufs, stats)``. The blocking form of
    :func:`bsp_sort_safe_launch` — launch + immediate wait, byte-identical.
    """
    return bsp_sort_safe_launch(
        x,
        cfg,
        values=values,
        rng=rng,
        stats=stats,
        executor=executor,
        resume=resume,
        planner=planner,
        **overrides,
    ).wait()


def bsp_sort_sharded_safe(
    x: jnp.ndarray,
    mesh,
    mesh_axis: str,
    cfg: Optional[SortConfig] = None,
    *,
    values: Sequence[jnp.ndarray] = (),
    rng: Optional[jax.Array] = None,
    stats: Optional[TierStats] = None,
    executor: Optional[SortExecutor] = None,
    resume: bool = True,
    **overrides,
) -> Tuple[SortResult, List[jnp.ndarray], TierStats]:
    """Overflow-safe :func:`bsp_sort_sharded` — same resumable escalation on
    real devices. Shard-mapped prepare/route callables come from the executor
    registry, so repeated calls with the same mesh/cfg reuse one compiled
    program per stage instead of rebuilding ``shard_map`` per call."""
    p, n_p = x.shape
    if cfg is None:
        cfg = SortConfig(p=p, n_per_proc=n_p, **overrides)
    tracer = resolve_tracer(cfg.obs)
    if cfg.obs is not None or cfg.chaos is not None:
        # chaos injection targets the vmapped service path; the sharded
        # driver only strips the handle so executor keys stay clean
        cfg = dataclasses.replace(cfg, obs=None, chaos=None)
    meta = _trace_meta_for(tracer, x, values, "sharded")
    if rng is None:
        rng = jax.random.key(cfg.seed)
    ex = executor if executor is not None else _EXECUTOR
    nv = len(values)

    if not resume:

        def run_tier(tier_cfg, tier_rng):
            fn = ex.sort_sharded(tier_cfg, mesh, mesh_axis, nv)
            buf, vbufs, count, overflow = fn(
                jax.random.key_data(tier_rng), x, *values
            )
            return SortResult(buf=buf, count=count, overflow=overflow.any()), list(
                vbufs
            )

        return _escalate(
            cfg.tier_ladder(), rng, stats, run_tier, tracer=tracer, trace_meta=meta
        )

    with _prepare_span(tracer, meta, cfg):
        prep = ex.prepare_sharded(cfg, mesh, mesh_axis, nv)(x, *values)
    ladder = cfg.tier_ladder()
    if cfg.route == "radix":
        if tracer is not None:
            tracer.point("host_sync", tid=meta["tid"], what="radix_counts")
        ladder = _radix_exact_ladder(cfg, prep)

    def run_tier(tier_cfg, tier_rng):
        fn = ex.route_sharded(tier_cfg, mesh, mesh_axis, nv)
        buf, vbufs, count, overflow = fn(prep, jax.random.key_data(tier_rng))
        return SortResult(buf=buf, count=count, overflow=overflow.any()), list(vbufs)

    return _escalate(ladder, rng, stats, run_tier, tracer=tracer, trace_meta=meta)


def gathered_output(result: SortResult) -> np.ndarray:
    """Host-side: concatenate valid prefixes into the full sorted sequence."""
    buf = np.asarray(result.buf)
    count = np.asarray(result.count)
    return np.concatenate([buf[k, : count[k]] for k in range(buf.shape[0])])


# ------------------------------------------------- phase-decomposed (bench)
def phase_fns(cfg: SortConfig, rng: Optional[jax.Array] = None) -> Dict[str, Callable]:
    """Separately-jittable phase functions over the global (p, n_p) layout.

    Mirrors the paper's Ph2..Ph6 instrumentation (Tables 4-7). Each callable
    consumes the previous phase's output so a benchmark can block between
    phases. Only det/iran decompose; ran/bitonic are single calls.

    This is a thin view over the pipeline: SeqSort (+ Sampling for ``det``)
    is exactly the prepare stage's work, Prefix/Routing/Merging the route
    stage's — each phase calls the same stage function the sort bodies use.
    """
    cfg.validate()
    if rng is None:
        rng = jax.random.key(cfg.seed)

    def vm(f):
        return jax.jit(jax.vmap(f, axis_name=AXIS))

    def ph2(x):
        return local_sort(x, cfg.local_sort)[0]

    def ph3(xs):
        return splitters.splitter_stage(xs, cfg, AXIS, rng)

    def ph4(xs, splits):
        return splitters.searchsorted_tagged(xs, splits, AXIS)

    def ph5(xs, bounds):
        buf, _, count, overflow = routing.route(xs, bounds, cfg, AXIS)
        return buf, count, overflow

    def ph6(buf):
        return merge_mod.merge_by_sort(buf)[0]

    return {
        "SeqSort": vm(ph2),
        "Sampling": vm(ph3),
        "Prefix": vm(ph4),
        "Routing": vm(ph5),
        "Merging": vm(ph6),
    }
