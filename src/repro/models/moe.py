"""Mixture-of-Experts with BSP-sort token dispatch — the paper's technique
as a first-class framework feature (DESIGN.md §4).

Dispatching tokens to experts *is* steps 9-11 of SORT_DET_BSP: an integer
key sort (key = expert id) followed by a balanced all-to-all, with the
stable inverse permutation restoring token order exactly — the paper's
stability guarantee doing real work. Two paths:

* **EP** (experts ≥ model-axis size; granite 32e, jamba 16e): experts are
  sharded over the ``model`` axis. Inside a ``shard_map`` over
  (data-like axes × model), each shard stable-sorts its token records by
  expert id (the paper's Ph2/step-9 "set formation"), computes per-dest
  segment boundaries, and routes through ONE ``lax.all_to_all`` (expert ids
  and token rows word-packed into a single send buffer — the fused
  h-relation of ``core/routing.pack_words``) with a
  capacity = (tokens/shard)·cf — the Claim 5.1-style w.h.p. bound with
  overflow *detected* and surfaced (``aux['overflow']``), never silently
  dropped. The reverse all_to_all + stable unsort is the combine.
* **TP grouped-GEMM** (experts < model axis; mixtral 8e): experts are
  replicated with their FFN hidden dim TP-sharded; tokens are *grouped* by
  the same stable integer sort into (E, capacity) blocks so each expert
  runs one dense GEMM (MegaBlocks-style), then scattered back.

Router aux losses (load-balance + z-loss) are returned for the trainer.

Capacity-tier ladder: a fixed ``capacity_factor`` is exactly the w.h.p.
pair-capacity guess of the sort's ``whp`` tier, and token drop is the same
retriable capacity fault as sort overflow. :func:`moe_ep_safe` runs EP
dispatch through the sort driver's ladder (whp → whp×2 → full) at host
level: the overflow flag escalates the capacity instead of silently
dropping tokens, with per-tier attempts recorded in a shared
:class:`repro.core.TierStats`. (Inside a jitted train step there is no host
sync, so the training path keeps the fixed-capacity body and surfaces
``aux['overflow']`` for the metrics loop.)

``moe_ep_safe(route="radix")`` drops the guesswork entirely: expert ids are
small dense integers, so a router-only counting pass (:func:`moe_ep_counts`)
yields the exact per-(src, dst) record counts, and the single dispatch runs
with a receive buffer bounded by the true maximum count — the MoE face of
``SortConfig(route="radix")``'s count-then-distribute h-relation. Zero
retries by construction, and never the ``full``-tier p·n worst case.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import obs as obs_mod
from repro.configs.base import ArchConfig
from repro.core import TierStats
from repro.core import routing
from repro.models.layers import _dense, dtype_of


@dataclasses.dataclass(frozen=True)
class MoEMeshInfo:
    """How the MoE layer sees the mesh (None = single-device smoke path)."""

    mesh: object = None
    model_axis: str = "model"
    data_axes: tuple = ("data",)

    @property
    def model_size(self) -> int:
        return 1 if self.mesh is None else self.mesh.shape[self.model_axis]


def init_moe(rng, cfg: ArchConfig, layers: int, d_ff: int | None = None) -> Dict:
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    E = cfg.moe_experts
    ks = jax.random.split(rng, 4)
    dt = dtype_of(cfg)
    return {
        "router": _dense(ks[0], (layers, D, E), D, jnp.float32),
        "w_gate": _dense(ks[1], (layers, E, D, F), D, dt),
        "w_up": _dense(ks[2], (layers, E, D, F), D, dt),
        "w_down": _dense(ks[3], (layers, E, F, D), F, dt),
    }


def _router(x2d: jnp.ndarray, w: jnp.ndarray, top_k: int):
    """Top-k routing. x2d (T, D) -> (probs (T,k), experts (T,k), aux)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), w)
    probs_full = jax.nn.softmax(logits, axis=-1)
    probs, experts = lax.top_k(probs_full, top_k)
    probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    # Shazeer-style load-balance loss + router z-loss
    e = w.shape[-1]
    me = probs_full.mean(0)
    ce = jnp.zeros((e,)).at[experts.reshape(-1)].add(1.0) / max(
        experts.size, 1
    )
    aux_lb = e * jnp.sum(me * ce)
    aux_z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return probs, experts.astype(jnp.int32), {"lb_loss": aux_lb, "z_loss": aux_z}


def _expert_ffn(x, wg, wu, wd):
    g = jnp.einsum("td,df->tf", x, wg)
    u = jnp.einsum("td,df->tf", x, wu)
    return jnp.einsum("tf,fd->td", jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u, wd)


# -------------------------------------------------- TP grouped-GEMM path
def _grouped_gemm_moe(params: Dict, x2d: jnp.ndarray, cfg: ArchConfig, capacity_factor):
    """Core grouped-GEMM dispatch on a 2-D token block (paper step 9: stable
    integer sort by expert id → dense (E, C, D)·(E, D, F) GEMMs)."""
    T, D = x2d.shape
    E, k = cfg.moe_experts, cfg.moe_top_k
    probs, experts, aux = _router(x2d, params["router"], k)

    n = T * k
    # decode/small-batch regime: full capacity (no record may ever drop at
    # serving time — exactness is cheap when n is small); capacity-managed
    # at scale with the overflow flag surfaced.
    cap = n if n <= 512 else int(-(-n * capacity_factor // E))
    flat_e = experts.reshape(-1)  # record i = (token i//k, choice i%k)
    order = jnp.argsort(flat_e, stable=True)  # paper step 9
    sorted_e = flat_e[order]
    # position of each record within its expert block
    bounds = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    within = jnp.arange(n) - bounds[sorted_e]
    slot = sorted_e * cap + within
    ok = within < cap
    aux["overflow"] = jnp.any(~ok)
    slot = jnp.where(ok, slot, E * cap)  # dropped slots -> scratch row

    grouped = jnp.zeros((E * cap + 1, D), x2d.dtype).at[slot].set(x2d[order // k])
    grouped = grouped[:-1].reshape(E, cap, D)
    h = jnp.einsum("ecd,edf->ecf", grouped, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", grouped, params["w_up"])
    h = jax.nn.silu(h.astype(jnp.float32)).astype(x2d.dtype) * u
    out_g = jnp.einsum("ecf,efd->ecd", h, params["w_down"]).reshape(E * cap, D)

    # combine: gather each record's output back, weight, segment-sum per token
    rec_out = jnp.where(ok[:, None], out_g[jnp.minimum(slot, E * cap - 1)], 0.0)
    y = jnp.zeros((T, D), x2d.dtype)
    y = y.at[order // k].add(
        (rec_out * probs.reshape(-1)[order][:, None]).astype(x2d.dtype)
    )
    return y, aux


def moe_tp(params: Dict, x: jnp.ndarray, cfg: ArchConfig, capacity_factor=1.25):
    """Grouped-GEMM MoE under plain pjit (single device / smoke path)."""
    *lead, D = x.shape
    y, aux = _grouped_gemm_moe(params, x.reshape(-1, D), cfg, capacity_factor)
    return y.reshape(*lead, D), aux


def moe_tp_sharded(
    params: Dict,
    x: jnp.ndarray,
    cfg: ArchConfig,
    mesh_info: MoEMeshInfo,
    capacity_factor=1.25,
):
    """Grouped-GEMM MoE under shard_map (§Perf iteration B1).

    Tokens stay local ((pod,data)×model sharded — same layout as the EP
    path); expert weights are replicated over experts with the FFN hidden
    dim TP-sharded, so the only collective is ONE psum of the (T_loc, D)
    combined output per layer (the row-parallel reduction), instead of the
    partitioner's full-batch gathers around the data-dependent scatter that
    plain pjit produced (205 s → ~2 s collective term on mixtral train_4k).
    """
    axis = mesh_info.model_axis
    all_axes = tuple(mesh_info.data_axes) + (axis,)

    def body(xl, router_w, wg, wu, wd):
        bl, sl, D = xl.shape
        lp = {"router": router_w, "w_gate": wg, "w_up": wu, "w_down": wd}
        y, aux = _grouped_gemm_moe(lp, xl.reshape(-1, D), cfg, capacity_factor)
        y = lax.psum(y, axis)  # row-parallel combine over the F shards
        ov = aux.pop("overflow")
        aux = {kk: lax.pmean(vv, all_axes) for kk, vv in aux.items()}
        aux["overflow"] = lax.pmax(ov.astype(jnp.int32), all_axes) > 0
        return y.reshape(bl, sl, D), aux

    dp = _dp_spec(mesh_info, x.shape[0])
    seq = axis if x.shape[1] % mesh_info.model_size == 0 else None
    return jax.shard_map(
        body,
        mesh=mesh_info.mesh,
        check_vma=False,
        in_specs=(
            P(dp, seq, None),
            P(),
            P(None, None, axis),
            P(None, None, axis),
            P(None, axis, None),
        ),
        out_specs=(P(dp, seq, None), P()),
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])


# --------------------------------------------------------- EP (a2a) path
def moe_ep(
    params: Dict,
    x: jnp.ndarray,
    cfg: ArchConfig,
    mesh_info: MoEMeshInfo,
    capacity_factor=1.25,
    pair_cap_override: Optional[int] = None,
):
    """Expert-parallel MoE via the BSP routing machinery under shard_map.

    x: (B, S, D) — B sharded over data axes, S sharded over the model axis
    (so all 256 devices hold distinct tokens), D replicated. Expert weights
    (E, D, F) sharded on E over the model axis.

    ``pair_cap_override`` pins the per-(src,dst) row capacity directly —
    the count-then-distribute ``route="radix"`` path of :func:`moe_ep_safe`
    host-reads the true per-destination counts first and passes their
    (quantized) maximum here, so the dispatch buffer is bounded by what the
    router actually routed instead of a ``capacity_factor`` guess.
    """
    p = mesh_info.model_size
    E, k = cfg.moe_experts, cfg.moe_top_k
    assert E % p == 0, "EP path requires experts divisible by the model axis"
    e_loc = E // p
    axis = mesh_info.model_axis
    all_axes = (
        tuple(mesh_info.data_axes) + (axis,) if mesh_info.mesh is not None else (axis,)
    )

    def body(xl, router_w, wg, wu, wd):
        # xl: (B_loc, S_loc, D); weights: router (D,E), wg/wu/wd (e_loc,D,F)..
        bl, sl, D = xl.shape
        x2d = xl.reshape(-1, D)
        t_loc = x2d.shape[0]
        probs, experts, aux = _router(x2d, router_w, k)

        n = t_loc * k
        if pair_cap_override is not None:
            pair_cap = min(int(pair_cap_override), n)
        else:
            pair_cap = int(-(-n * capacity_factor // p))
        cap = p * pair_cap

        # paper step 9: stable integer sort of records by expert id
        flat_e = experts.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        dest = sorted_e // e_loc  # destination shard (contiguous in sorted order)
        bounds = jnp.searchsorted(dest, jnp.arange(p + 1), side="left").astype(jnp.int32)
        counts = jnp.diff(bounds)
        # aux terms must leave the shard_map replicated: reduce over the mesh
        aux = {kk: lax.pmean(vv, all_axes) for kk, vv in aux.items()}
        aux["overflow"] = (
            lax.pmax(jnp.any(counts > pair_cap).astype(jnp.int32), all_axes) > 0
        )

        # paper steps 10-11: segment rows + ONE all_to_all (keys + payload
        # word-packed into a single send buffer — the fused h-relation, same
        # helpers as core/routing's Ph5)
        tix = jnp.arange(pair_cap)[None, :]
        gidx = jnp.clip(bounds[:-1][:, None] + tix, 0, n - 1)
        valid = tix < counts[:, None]
        rows_e = jnp.where(valid, sorted_e[gidx], -1)  # (p, pair_cap)
        sorted_tok = x2d[order // k]  # record i ↔ token order[i]//k
        rows_x = jnp.where(valid[..., None], sorted_tok[gidx], 0).astype(xl.dtype)
        fused, metas = routing.pack_words([rows_e, rows_x], lead=1)
        recv_e, recv_x = routing.unpack_words(
            lax.all_to_all(fused, axis, 0, 0), metas, lead=1
        )

        # local expert compute (masked over e_loc experts; e_loc ≤ 2 in all
        # assigned configs — bounded FLOP inflation, see DESIGN.md §4)
        me = lax.axis_index(axis)
        flat_re = recv_e.reshape(cap)
        flat_rx = recv_x.reshape(cap, D)
        out = jnp.zeros_like(flat_rx)
        for e in range(e_loc):
            sel = flat_re == (me * e_loc + e)
            y_e = _expert_ffn(flat_rx, wg[e], wu[e], wd[e])
            out = jnp.where(sel[:, None], y_e, out)

        # reverse all_to_all: back to source order
        back = lax.all_to_all(out.reshape(p, pair_cap, D), axis, 0, 0)
        # un-segment: record at sorted position bounds[i]+t came back in row i
        sorted_out = jnp.zeros((n, D), xl.dtype)
        src_pos = jnp.where(valid, bounds[:-1][:, None] + tix, n)
        sorted_out = sorted_out.at[src_pos.reshape(-1)].add(
            back.reshape(-1, D), mode="drop"
        )
        # stable unsort (inverse of the step-9 permutation)
        rec_out = jnp.zeros((n, D), xl.dtype).at[order].set(sorted_out)
        w = probs.reshape(-1)[:, None].astype(xl.dtype)
        y = (rec_out * w).reshape(t_loc, k, D).sum(1)
        return y.reshape(bl, sl, D), aux

    if mesh_info.mesh is None:
        # single-device smoke path: p == 1, same code, dummy axis via vmap
        out, aux = jax.vmap(
            lambda xl: body(
                xl,
                params["router"],
                params["w_gate"],
                params["w_up"],
                params["w_down"],
            ),
            axis_name=axis,
        )(x[None])
        return out[0], jax.tree.map(lambda a: a[0], aux)

    dp = _dp_spec(mesh_info, x.shape[0])
    seq = axis if x.shape[1] % mesh_info.model_size == 0 else None
    return jax.shard_map(
        body,
        mesh=mesh_info.mesh,
        check_vma=False,
        in_specs=(
            P(dp, seq, None),
            P(),
            P(axis, None, None),
            P(axis, None, None),
            P(axis, None, None),
        ),
        out_specs=(P(dp, seq, None), P()),
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])


def _dp_spec(mesh_info: MoEMeshInfo, batch: int):
    """Batch sharding over the data axes, or replication when indivisible
    (e.g. the global_batch=1 long-context decode cell)."""
    n = 1
    for a in mesh_info.data_axes:
        n *= mesh_info.mesh.shape[a]
    return mesh_info.data_axes if batch % n == 0 else None


def moe_ep_counts(params: Dict, x: jnp.ndarray, cfg: ArchConfig, mesh_info: MoEMeshInfo):
    """Count-only routing pass for the radix EP route.

    Runs just the router (a (T, D)·(D, E) GEMM — a sliver of the FFN cost)
    and tallies records per destination shard, returning the replicated
    global maximum per-(src, dst) count as a scalar. This is the MoE
    analogue of the sort's count-then-distribute route: expert ids are
    small dense ints, so one counting pass yields the exact dispatch
    capacity and there is nothing to sample or to guess.
    """
    p = mesh_info.model_size
    E, k = cfg.moe_experts, cfg.moe_top_k
    assert E % p == 0, "EP path requires experts divisible by the model axis"
    e_loc = E // p
    axis = mesh_info.model_axis
    all_axes = (
        tuple(mesh_info.data_axes) + (axis,) if mesh_info.mesh is not None else (axis,)
    )

    def body(xl, router_w):
        x2d = xl.reshape(-1, xl.shape[-1])
        _, experts, _ = _router(x2d, router_w, k)
        dest = experts.reshape(-1) // e_loc
        counts = jnp.zeros((p,), jnp.int32).at[dest].add(1)
        return lax.pmax(counts.max(), all_axes)

    if mesh_info.mesh is None:
        return jax.vmap(lambda xl: body(xl, params["router"]), axis_name=axis)(
            x[None]
        )[0]
    dp = _dp_spec(mesh_info, x.shape[0])
    seq = axis if x.shape[1] % mesh_info.model_size == 0 else None
    return jax.shard_map(
        body,
        mesh=mesh_info.mesh,
        check_vma=False,
        in_specs=(P(dp, seq, None), P()),
        out_specs=P(),
    )(x, params["router"])


def moe_capacity_ladder(capacity_factor: float, p: int) -> tuple:
    """EP dispatch capacity tiers, mirroring ``SortConfig.tier_ladder``.

    ``whp``  — the configured guess (pair_cap = ⌈n·cf/p⌉);
    ``whp2`` — the same bound ×2 (squares the failure probability);
    ``full`` — pair_cap = n: the per-destination row can hold every record,
    so no routing pattern can overflow it and the ladder always terminates.
    """
    tiers = [("whp", float(capacity_factor)), ("whp2", 2.0 * capacity_factor)]
    if 2.0 * capacity_factor < p:
        tiers.append(("full", float(p)))
    else:  # whp2 already at/above full capacity — dedupe the terminal rung
        tiers[-1] = ("full", float(p))
    return tuple(tiers)


#: jitted EP dispatch callables keyed by (cfg, mesh_info, capacity_factor) —
#: all frozen/hashable, so each ladder rung compiles once per process.
_EP_JIT_CACHE: Dict[tuple, object] = {}


def _moe_ep_jitted(
    cfg: ArchConfig,
    mesh_info: MoEMeshInfo,
    capacity_factor: float,
    pair_cap: Optional[int] = None,
):
    key = (cfg, mesh_info, float(capacity_factor), pair_cap)
    fn = _EP_JIT_CACHE.get(key)
    if fn is None:
        fn = _EP_JIT_CACHE[key] = jax.jit(
            lambda p, x: moe_ep(
                p, x, cfg, mesh_info, capacity_factor, pair_cap_override=pair_cap
            )
        )
    return fn


def _moe_ep_counts_jitted(cfg: ArchConfig, mesh_info: MoEMeshInfo):
    key = ("counts", cfg, mesh_info)
    fn = _EP_JIT_CACHE.get(key)
    if fn is None:
        fn = _EP_JIT_CACHE[key] = jax.jit(
            lambda p, x: moe_ep_counts(p, x, cfg, mesh_info)
        )
    return fn


def moe_ep_safe(
    params: Dict,
    x: jnp.ndarray,
    cfg: ArchConfig,
    mesh_info: MoEMeshInfo,
    capacity_factor: float = 1.25,
    stats: Optional[TierStats] = None,
    planner=None,
    route: str = "sample",
    obs=None,
) -> Tuple[jnp.ndarray, Dict, TierStats]:
    """Overflow-safe EP dispatch: escalate the capacity tier on token drop.

    The host-side analogue of ``bsp_sort_safe`` for MoE routing: run the
    jitted EP layer at each rung of :func:`moe_capacity_ladder`, inspect the
    replicated ``aux['overflow']`` flag, and retry at the next capacity tier
    until no token was dropped. The terminal ``full`` rung sizes every
    (src, dst) row at n records, which cannot overflow. Use at serving /
    evaluation time (top-level calls with a host sync per layer); the jitted
    train step keeps the fixed-capacity :func:`moe_ep`.

    ``route="radix"`` replaces the guess-and-retry ladder with
    count-then-distribute: one cheap router-only pass
    (:func:`moe_ep_counts`) host-reads the true maximum per-(src, dst)
    record count, and the dispatch runs exactly once with the receive
    buffer bounded by that count (quantized to octave steps so the jit
    cache stays bounded). No ``capacity_factor`` guess, no ``whp`` rungs,
    no ``full``-tier p·n fallback — overflow is impossible by
    construction, so radix batches always report zero retries.

    ``planner`` (a :class:`repro.planner.CapacityPlanner`) is an optional
    traffic-learned policy over the same ladder: a model whose router
    keeps dropping tokens at the ``whp`` guess stops paying the doomed
    attempt and starts at the rung that empirically serves. (The radix
    route has a single rung, so the planner has nothing to learn there.)

    ``obs`` (a :class:`repro.obs.Tracer`) records per-attempt dispatch
    spans on a ``moe`` timeline lane — the counting-pass host sync and each
    rung's launch-to-decision wall — without touching the jitted programs.
    """
    stats = stats if stats is not None else TierStats()
    tracer = obs_mod.resolve_tracer(obs)
    tid = tracer.next_tid("moe") if tracer is not None else None
    if route == "radix":
        # one host sync: the true max records any (src, dst) pair carries
        t0 = tracer.now() if tracer is not None else 0.0
        pair_true = int(_moe_ep_counts_jitted(cfg, mesh_info)(params, x))
        if tracer is not None:
            tracer.add_span(
                "count", t0, cat="moe", tid=tid, pair_true=pair_true
            )
            tracer.point("host_sync", cat="moe", tid=tid, what="moe_counts")
        # quantize up to ~16 steps per octave: bounds distinct compiled
        # programs while staying within 1/16th of the exact bound
        step = max(8, 1 << max(0, pair_true.bit_length() - 4))
        qpair = -(-max(pair_true, 1) // step) * step
        t1 = tracer.now() if tracer is not None else 0.0
        y, aux = _moe_ep_jitted(cfg, mesh_info, 1.0, pair_cap=qpair)(params, x)
        overflow = bool(aux["overflow"])
        if tracer is not None:
            tracer.add_span(
                "dispatch", t1, cat="moe", tid=tid,
                tier="radix", ok=not overflow, pair_cap=qpair,
            )
        obs_mod.metrics().counter("moe.radix_dispatches").inc()
        if overflow:  # caps >= true counts: unreachable
            raise RuntimeError(
                "radix EP dispatch overflowed its counted capacity"
            )
        stats.record("radix", True)
        return y, aux, stats
    ladder = moe_capacity_ladder(capacity_factor, mesh_info.model_size)
    n_rungs, bucket = len(ladder), None
    if planner is not None and n_rungs > 1:
        bucket = (
            f"moe/{cfg.name}/ep{mesh_info.model_size}"
            f"/t{x.shape[0] * x.shape[1]}/cf{capacity_factor}"
        )
        ladder = ladder[planner.rung_for(bucket, n_rungs) :]
    faulted = False
    for tier, cf in ladder:
        t0 = tracer.now() if tracer is not None else 0.0
        y, aux = _moe_ep_jitted(cfg, mesh_info, cf)(params, x)
        ok = not bool(aux["overflow"])
        if tracer is not None:
            tracer.add_span(
                "dispatch", t0, cat="moe", tid=tid,
                tier=tier, ok=ok, capacity_factor=cf,
            )
        stats.record(tier, ok)
        if ok:
            if bucket is not None:
                planner.observe(bucket, faulted, n_rungs)
            return y, aux, stats
        faulted = True
    raise RuntimeError(
        "EP capacity escalation exhausted — unreachable: the full tier "
        "holds every record"
    )


def moe_ep_decode(params: Dict, x: jnp.ndarray, cfg: ArchConfig, mesh_info: MoEMeshInfo):
    """EP MoE for tiny token counts (decode): every shard evaluates its local
    experts on every token (cheap at T = batch), combined with one psum — no
    all_to_all, no capacity. The absolute extra FLOPs are O(B·E·D·F), dwarfed
    by the attention cache reads at decode time."""
    p = mesh_info.model_size
    E, k = cfg.moe_experts, cfg.moe_top_k
    e_loc = E // p
    axis = mesh_info.model_axis
    all_axes = tuple(mesh_info.data_axes) + (axis,)

    def body(xl, router_w, wg, wu, wd):
        bl, sl, D = xl.shape
        x2d = xl.reshape(-1, D)
        probs, experts, aux = _router(x2d, router_w, k)
        me = lax.axis_index(axis)
        y = jnp.zeros_like(x2d)
        for e in range(e_loc):
            ge = me * e_loc + e
            w_tok = (probs * (experts == ge)).sum(-1).astype(xl.dtype)  # (T,)
            y = y + w_tok[:, None] * _expert_ffn(x2d, wg[e], wu[e], wd[e])
        y = lax.psum(y, axis)
        aux = {kk: lax.pmean(vv, all_axes) for kk, vv in aux.items()}
        aux["overflow"] = jnp.zeros((), bool)
        return y.reshape(bl, sl, D), aux

    dp = _dp_spec(mesh_info, x.shape[0])
    return jax.shard_map(
        body,
        mesh=mesh_info.mesh,
        check_vma=False,
        in_specs=(
            P(dp, None, None),
            P(),
            P(axis, None, None),
            P(axis, None, None),
            P(axis, None, None),
        ),
        out_specs=(P(dp, None, None), P()),
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
