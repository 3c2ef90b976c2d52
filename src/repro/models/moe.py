"""Mixture-of-Experts layer routed through the paper's crossbar mechanism.

The mapping is exact, not an analogy:

- *sources* are token groups (the data-parallel regions a batch shard came
  from — the crossbar's master ports),
- *destinations* are experts (slave ports),
- the *WRR package quota* per (source, destination) pair is the per-group
  expert capacity ``C`` — bandwidth allocation in packages (§IV-E.1),
- *isolation masks* restrict which experts a tenant's tokens may reach
  (§IV-E.2), enforced inside the dispatch exactly like the one-hot-AND,
- over-quota packets are dropped with the paper's error codes and surface in
  the router's drop statistics (the register file's status read-back).

Grouped dense formulation (Switch/Mesh-TF style): groups keep the dispatch
tensor O(G * Tg^2) instead of O(T^2); each group independently enforces the
pairwise quota — which is precisely ``pairwise_dispatch_plan`` vmapped over
groups. Group size is a tunable (perf hillclimb lever).

Mesh expert parallelism (``dispatch_impl="sharded"``): experts become slave
ports *partitioned across a mesh axis* and tokens cross the axis through
``repro.fabric.ShardedBackend``'s global-WRR ``all_to_all`` — one crossbar
over the whole mesh instead of local per-group fabrics.  The register file
is a traced argument end to end, so a live ``Shell`` reconfigures routing
between jitted steps with zero retraces (see ``moe_apply_sharded`` /
``moe_forward_sharded`` and ``tests/test_moe_sharded.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParamDef
from repro.models.config import MoEConfig


def moe_defs(d_model: int, d_ff: int, moe: MoEConfig, act: str) -> Dict[str, ParamDef]:
    f_in = 2 * d_ff if act in ("swiglu", "geglu") else d_ff
    return {
        "w_router": ParamDef((d_model, moe.n_experts), ("fsdp", None)),
        "w_in": ParamDef((moe.n_experts, d_model, f_in), (None, "fsdp", "tp")),
        "w_out": ParamDef((moe.n_experts, d_ff, d_model), (None, "tp", "fsdp")),
    }


def expert_capacity(group_tokens: int, moe: MoEConfig, multiple: int = 8) -> int:
    c = math.ceil(moe.capacity_factor * group_tokens * moe.top_k / moe.n_experts)
    return max(multiple, math.ceil(c / multiple) * multiple)


def dropless_capacity(group_tokens: int, multiple: int = 8) -> int:
    """The smallest capacity, in the rule's multiple, under which no packet
    of a group of ``group_tokens`` tokens can drop: a token sends at most
    one packet to any expert, so no expert receives more than
    ``group_tokens``."""
    return max(multiple, math.ceil(group_tokens / multiple) * multiple)


def moe_apply(params, x: jax.Array, moe: MoEConfig, act: str, *,
              group_size: int = 1024,
              expert_mask: Optional[jax.Array] = None,
              dispatch_impl: str = "dense",
              registers=None, axis_name: str = "expert",
              capacity: Optional[int] = None,
              kernel_mode: Optional[str] = None
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: [B, S, d] -> (y [B, S, d], stats).

    ``expert_mask``: optional [E] bool — the tenant's allowed-destinations
    register; disallowed experts receive no traffic and their packets are
    dropped (INVALID_DEST analogue), surfacing in ``stats['iso_dropped']``.

    ``dispatch_impl``: "dense" is the Mesh-TF one-hot matmul formulation
    (the faithful baseline — the crossbar's selection matrix realised on
    the MXU). Its dispatch/combine einsums cost 2*T*k*E*C*d FLOPs and an
    O(T*E*C) selection tensor — ~60x the expert matmuls at pod scale.
    "gather" routes by indexed scatter/gather instead: O(T*k*d) data
    movement and no selection tensor (§Perf iteration "moe-gather").
    Identical packet semantics: same ranks, same WRR quota drops.

    "sharded" is mesh expert parallelism: it must run *inside a shard_map*
    over ``axis_name`` (experts are slave ports partitioned across the
    axis, tokens cross it via the global-WRR ``all_to_all``) and routes
    through :func:`moe_apply_sharded` — ``registers``/``capacity`` pass
    through, ``group_size`` is ignored (the shard is the group).

    ``capacity`` (every impl) overrides the per-(group, expert) quota;
    by default it is ``expert_capacity(group, moe)``, the configuration's
    rule.  ``dropless_capacity(group)`` makes any group dropless.

    Any other value names a ``repro.fabric`` backend ("reference",
    "pallas", or a registered custom): the layer then routes every group
    through a ``Fabric.transfer`` round-trip — experts are crossbar slave
    ports, ``expert_mask`` is the isolation row, capacity is the slab
    depth — so the MoE data plane and the shell's interconnect share one
    implementation (and one plan semantics) instead of re-deriving ranks
    here.

    ``kernel_mode`` (``repro.fabric.KernelMode`` or its string aliases)
    selects the fabric's kernel *lowering* on the fabric-backed impls —
    resolved once when the geometry's fabric is first built, never inside
    the traced call (docs/training.md).  The dense/gather impls have no
    kernels and ignore it.
    """
    if dispatch_impl == "gather":
        return moe_apply_gather(params, x, moe, act, group_size=group_size,
                                expert_mask=expert_mask, capacity=capacity)
    if dispatch_impl == "sharded":
        return moe_apply_sharded(params, x, moe, act, registers=registers,
                                 axis_name=axis_name,
                                 expert_mask=expert_mask, capacity=capacity,
                                 kernel_mode=kernel_mode)
    if dispatch_impl != "dense":
        return moe_apply_fabric(params, x, moe, act, group_size=group_size,
                                expert_mask=expert_mask,
                                backend=dispatch_impl,
                                capacity=capacity,
                                kernel_mode=kernel_mode)
    B, S, d = x.shape
    E, k = moe.n_experts, moe.top_k
    T = B * S
    g = min(group_size, T)
    G = T // g
    assert G * g == T, f"tokens {T} not divisible by group size {g}"
    xf = x.reshape(G, g, d)

    logits = jnp.einsum("gtd,de->gte", xf, params["w_router"]).astype(jnp.float32)
    if expert_mask is not None:
        logits = jnp.where(expert_mask[None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)                    # [G, g, E]
    top_p, top_e = jax.lax.top_k(probs, k)                     # [G, g, k]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # --- crossbar dispatch plan: per-(group, expert) package ranks -------
    dst = top_e.reshape(G, g * k)                              # packets
    w = top_p.reshape(G, g * k).astype(x.dtype)
    cap = capacity if capacity is not None else expert_capacity(g, moe)
    e_oh = jax.nn.one_hot(dst, E, dtype=jnp.int32)             # [G, gk, E]
    rank = jnp.cumsum(e_oh, axis=1) - e_oh
    rank = jnp.take_along_axis(rank, dst[..., None], axis=2,
                               mode="clip")[..., 0]
    keep = rank < cap                                          # WRR quota
    if expert_mask is not None:
        iso_ok = expert_mask[dst]
        keep &= iso_ok
        iso_dropped = jnp.sum(~iso_ok)
    else:
        iso_dropped = jnp.zeros((), jnp.int32)
    slot = jnp.where(keep, rank, 0)

    sel = (jax.nn.one_hot(dst, E, dtype=x.dtype)
           * keep[..., None].astype(x.dtype))                  # [G, gk, E]
    slot_oh = jax.nn.one_hot(slot, cap, dtype=x.dtype)         # [G, gk, C]
    disp = sel[..., :, None] * slot_oh[..., None, :]           # [G, gk, E, C]

    xk = jnp.repeat(xf, k, axis=1)                             # [G, gk, d]
    xe = jnp.einsum("gtec,gtd->gecd", disp, xk)                # [G, E, C, d]

    h = jnp.einsum("gecd,edf->gecf", xe, params["w_in"])
    if act in ("swiglu", "geglu"):
        gate, up = jnp.split(h, 2, axis=-1)
        a = jax.nn.silu(gate.astype(jnp.float32)) if act == "swiglu" \
            else jax.nn.gelu(gate.astype(jnp.float32))
        h = (a * up.astype(jnp.float32)).astype(x.dtype)
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    ye = jnp.einsum("gecf,efd->gecd", h, params["w_out"])      # [G, E, C, d]

    comb = disp * w[..., None, None]
    y = jnp.einsum("gtec,gecd->gtd", comb, ye)                 # [G, gk, d]
    y = y.reshape(G, g, k, d).sum(axis=2).reshape(B, S, d)

    # --- router statistics (load-balance aux loss + drop read-back) ------
    frac_tokens = jnp.mean(sel, axis=(0, 1))                   # [E]
    frac_probs = jnp.mean(probs, axis=(0, 1))                  # [E]
    aux_loss = E * jnp.sum(frac_tokens.astype(jnp.float32) * frac_probs)
    stats = {
        "aux_loss": aux_loss,
        "dropped": jnp.sum(~keep),
        "iso_dropped": iso_dropped,
        "capacity": jnp.asarray(cap),
    }
    return y, stats


def moe_apply_gather(params, x: jax.Array, moe: MoEConfig, act: str, *,
                     group_size: int = 1024,
                     expert_mask: Optional[jax.Array] = None,
                     capacity: Optional[int] = None
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Gather/scatter MoE dispatch — same grant semantics, no dense
    selection tensor.

    Packet slot assignment is identical to the dense path (rank within the
    (group, expert) stream == the WRR package counter); the slab is filled
    with ``.at[slot].add`` (unique slots, so add == set) and results return
    with ``take_along_axis``. FLOPs: experts only. Bytes: O(T*k*d).
    """
    B, S, d = x.shape
    E, k = moe.n_experts, moe.top_k
    T = B * S
    g = min(group_size, T)
    G = T // g
    assert G * g == T, f"tokens {T} not divisible by group size {g}"
    xf = x.reshape(G, g, d)

    logits = jnp.einsum("gtd,de->gte", xf, params["w_router"]).astype(jnp.float32)
    if expert_mask is not None:
        logits = jnp.where(expert_mask[None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    dst = top_e.reshape(G, g * k)
    w = top_p.reshape(G, g * k).astype(x.dtype)
    cap = capacity if capacity is not None else expert_capacity(g, moe)
    e_oh = jax.nn.one_hot(dst, E, dtype=jnp.int32)
    rank = jnp.cumsum(e_oh, axis=1) - e_oh
    rank = jnp.take_along_axis(rank, dst[..., None], axis=2,
                               mode="clip")[..., 0]
    keep = rank < cap
    if expert_mask is not None:
        iso_ok = expert_mask[dst]
        keep &= iso_ok
        iso_dropped = jnp.sum(~iso_ok)
    else:
        iso_dropped = jnp.zeros((), jnp.int32)

    # --- indexed dispatch: packet -> (expert, slot) flat address ---------
    # Dropped packets write to a trash slot (index E*cap) that is sliced off.
    slot_addr = jnp.where(keep, dst * cap + jnp.where(keep, rank, 0),
                          E * cap)                       # [G, gk]
    xk = jnp.repeat(xf, k, axis=1)                       # [G, gk, d]

    def fill(slabs_g, addr_g, xk_g):
        return slabs_g.at[addr_g].add(
            xk_g.astype(slabs_g.dtype))  # fablint: trash-row

    slabs = jnp.zeros((G, E * cap + 1, d), x.dtype)
    slabs = jax.vmap(fill)(slabs, slot_addr, xk)
    xe = slabs[:, :E * cap].reshape(G, E, cap, d)

    ye = jax.vmap(lambda xg: _expert_ffn(xg, params["w_in"],
                                         params["w_out"], act))(xe)

    # --- indexed combine: gather each packet's result, weight, sum top-k -
    ye_flat = ye.reshape(G, E * cap, d)
    ye_flat = jnp.concatenate(
        [ye_flat, jnp.zeros((G, 1, d), ye.dtype)], axis=1)  # trash slot
    back = jnp.take_along_axis(ye_flat, slot_addr[..., None], axis=1,
                               mode="clip")
    back = back * (w * keep.astype(w.dtype))[..., None]
    y = back.reshape(G, g, k, d).sum(axis=2).reshape(B, S, d)

    sel_frac = jnp.mean(
        jax.nn.one_hot(dst, E, dtype=jnp.float32)
        * keep[..., None].astype(jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux_loss = E * jnp.sum(sel_frac * frac_probs)
    stats = {
        "aux_loss": aux_loss,
        "dropped": jnp.sum(~keep),
        "iso_dropped": iso_dropped,
        "capacity": jnp.asarray(cap),
    }
    return y, stats


def _group_fabric(n_experts: int, capacity: int, backend: str,
                  axis_name: Optional[str] = None,
                  kernel_mode: Optional[str] = None):
    """Normalizing front door for :func:`_group_fabric_cached`: ``"auto"``
    and ``None`` both mean "the platform default" and must share one cache
    key (lm-configured layers say ``"auto"``, direct callers say nothing —
    they should hit the same fabric and the same trace counters)."""
    if kernel_mode == "auto":
        kernel_mode = None
    return _group_fabric_cached(n_experts, capacity, backend, axis_name,
                                kernel_mode)


@functools.lru_cache(maxsize=None)
def _group_fabric_cached(n_experts: int, capacity: int, backend: str,
                         axis_name: Optional[str] = None,
                         kernel_mode: Optional[str] = None):
    """One cached fabric (and its jit caches) per MoE geometry.

    The fabric reads its registers through a mutable cell so the caller
    can swap in the tenant's isolation mask per forward pass — values
    steer routing, the compiled dispatch/combine programs are reused
    across calls (and across layers sharing a geometry).  ``axis_name``
    selects the sharded backend's mesh axis (sharded fabrics are keyed
    per axis so different meshes don't share WRR geometry);
    ``kernel_mode`` is the lowering seam (``repro.fabric.KernelMode``) —
    part of the cache key, so two modes never share compiled programs."""
    from repro.core.registers import CrossbarRegisters
    from repro.fabric import Fabric
    # The cell must hold *concrete* registers even when the cache misses
    # inside a jit/grad trace (e.g. the first call ever is a jitted train
    # step): staged-out register arrays would be cached as dead tracers
    # and poison every later trace with UnexpectedTracerError.
    with jax.ensure_compile_time_eval():
        cell = {"regs": CrossbarRegisters.create(n_experts,
                                                 capacity=capacity)}
    kw = {"axis_name": axis_name} if axis_name is not None else {}
    fabric = Fabric(lambda: cell["regs"], backend=backend,
                    capacity=capacity, kernel_mode=kernel_mode, **kw)
    return fabric, cell


def moe_fabric(n_experts: int, capacity: int, backend: str,
               axis_name: Optional[str] = None,
               kernel_mode: Optional[str] = None):
    """The cached ``Fabric`` a given MoE geometry dispatches through.

    Exposed so tests and telemetry can read ``fabric.trace_count`` (the
    zero-retrace-across-reconfiguration regression pin) or attach
    ``fabric.probe()`` for the layer that serves a geometry."""
    return _group_fabric(n_experts, capacity, backend, axis_name,
                         kernel_mode)[0]


def _moe_router(params, xf: jax.Array, moe: MoEConfig,
                expert_mask: Optional[jax.Array]):
    """Shared router: flat tokens [T, d] -> (dst [T*k], w [T*k], probs).

    ``dst`` is the packet destination stream (expert = slave port id,
    token-major, k packets per token) and ``w`` the renormalized top-k
    combine weights — the single routing semantics every dispatch_impl
    (and the sharded oracle) agrees on."""
    E, k = moe.n_experts, moe.top_k
    logits = jnp.einsum("td,de->te", xf,
                        params["w_router"]).astype(jnp.float32)
    if expert_mask is not None:
        logits = jnp.where(expert_mask[None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)                    # [T, E]
    top_p, top_e = jax.lax.top_k(probs, k)                     # [T, k]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    dst = top_e.reshape(-1)                                    # [T*k]
    w = top_p.reshape(-1).astype(xf.dtype)
    return dst, w, probs


@jax.named_scope("moe.expert_ffn")
def _expert_ffn(slabs: jax.Array, w_in: jax.Array, w_out: jax.Array,
                act: str) -> jax.Array:
    """The expert MLP over receive slabs [E?, C, d] (any expert count —
    the sharded path passes each shard's local expert block).  Its ops
    carry the op-name scope ``moe.expert_ffn`` in every path, so a device
    trace can time the expert layer."""
    h = jnp.einsum("ecd,edf->ecf", slabs, w_in)
    if act in ("swiglu", "geglu"):
        gate, up = jnp.split(h, 2, axis=-1)
        a = jax.nn.silu(gate.astype(jnp.float32)) if act == "swiglu" \
            else jax.nn.gelu(gate.astype(jnp.float32))
        h = (a * up.astype(jnp.float32)).astype(slabs.dtype)
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(slabs.dtype)
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def moe_apply_fabric(params, x: jax.Array, moe: MoEConfig, act: str, *,
                     group_size: int = 1024,
                     expert_mask: Optional[jax.Array] = None,
                     backend: str = "reference",
                     capacity: Optional[int] = None,
                     kernel_mode: Optional[str] = None
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """MoE dispatch as a ``repro.fabric`` transfer — one data-plane impl.

    Per group: tokens are packets from one master port, experts are the
    slave ports, ``expert_mask`` is the tenant isolation row, and the
    expert capacity is the receive-slab depth.  The whole
    plan/dispatch/expert/combine round-trip is a single vmapped
    ``Fabric.transfer`` with the expert FFN as ``apply_fn`` — so whichever
    backend serves the shell (reference oracle, blockwise Pallas kernels)
    also serves the MoE layer, with the paper's error codes as the drop
    statistics.  ``capacity`` is the slab depth (default: the
    configuration's rule for the group).
    """
    from repro.core.registers import ErrorCode

    B, S, d = x.shape
    E, k = moe.n_experts, moe.top_k
    T = B * S
    g = min(group_size, T)
    G = T // g
    assert G * g == T, f"tokens {T} not divisible by group size {g}"
    xf = x.reshape(G, g, d)

    logits = jnp.einsum("gtd,de->gte", xf, params["w_router"]).astype(jnp.float32)
    if expert_mask is not None:
        logits = jnp.where(expert_mask[None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    dst = top_e.reshape(G, g * k)
    w = top_p.reshape(G, g * k).astype(x.dtype)
    cap = capacity if capacity is not None else expert_capacity(g, moe)

    fabric, cell = _group_fabric(E, cap, backend, kernel_mode=kernel_mode)
    canonical = cell["regs"]
    # Fully specify the isolation mask every call — the cell is shared
    # across calls (and tenants) on this geometry, so nothing may inherit
    # a previous call's mask; restored below so no (possibly traced) mask
    # outlives this forward pass.
    allowed = (jnp.broadcast_to(expert_mask[None, :], (E, E))
               if expert_mask is not None
               else jnp.ones((E, E), bool))
    cell["regs"] = dataclasses.replace(canonical, allowed=allowed)
    src = jnp.zeros((g * k,), jnp.int32)

    def experts_fn(slabs):                                 # [E, C, d]
        return _expert_ffn(slabs, params["w_in"], params["w_out"], act)

    def one_group(xg, dg, wg):
        # dispatch/combine are the fabric's shape-cached jits; the expert
        # compute stays in the caller's trace (params close over nothing
        # that would key a recompile).
        xk = jnp.repeat(xg, k, axis=0)                     # [gk, d]
        slabs, plan = fabric.dispatch(xk, dg, src)
        return fabric.combine(experts_fn(slabs), plan, weights=wg), plan

    try:
        y, plans = jax.vmap(one_group)(xf, dst, w)         # y [G, gk, d]
    finally:
        cell["regs"] = canonical
    y = y.reshape(G, g, k, d).sum(axis=2).reshape(B, S, d)

    frac_tokens = (jnp.sum(plans.counts, axis=0) / (G * g * k)
                   ).astype(jnp.float32)
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux_loss = E * jnp.sum(frac_tokens * frac_probs)
    stats = {
        "aux_loss": aux_loss,
        "dropped": jnp.sum(~plans.keep),
        "iso_dropped": jnp.sum(plans.drops[:, ErrorCode.INVALID_DEST]),
        "capacity": jnp.asarray(cap),
    }
    return y, stats


def moe_apply_sharded(params, x: jax.Array, moe: MoEConfig, act: str, *,
                      registers=None, axis_name: str = "expert",
                      expert_mask: Optional[jax.Array] = None,
                      capacity: Optional[int] = None,
                      kernel_mode: Optional[str] = None
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Mesh expert parallelism through the sharded fabric backend.

    Must run **inside a shard_map** over ``axis_name`` (use
    :func:`moe_forward_sharded` for the wrapper): tokens are sharded
    across the axis (``x`` is this shard's [B_loc, S, d] slice), experts
    are crossbar slave ports partitioned contiguously across it
    (``params['w_in']``/``['w_out']`` are this shard's [E_loc, ...]
    blocks; ``params['w_router']`` is replicated).  Tokens cross the axis
    via the oracle-equivalent global-WRR ``all_to_all``
    (``ShardedBackend``), so the expert-parallel data plane and the
    shell's interconnect are the same implementation.

    ``registers`` is the E-port crossbar register file and stays a
    *traced argument*: pass it through the enclosing jit/shard_map and a
    ``Shell.post(Grow/Shrink/FailRegion)`` re-routes the next step with
    zero retraces (``moe_fabric(E, cap, "sharded", axis).trace_count`` is
    the regression pin).  Defaults to a fully-open file when omitted.

    Extra stats over the local paths: ``offered_packets`` /
    ``granted_packets`` (global, ``dropped = offered - granted``),
    ``counts`` (global per-expert grant histogram) and
    ``remote_packets`` / ``local_packets`` — packets that crossed the
    mesh axis vs. stayed on their source shard (the §IV-E crossbar hops
    that cost ICI bandwidth) — plus their per-*port* splits
    ``remote_counts`` / ``local_counts`` ([E] vectors), so the manager can
    rank individual ports (and the Migrate moves that would relocate
    them) by ICI savings.  ``Fabric.account_stats`` folds all of them
    into manager telemetry.
    """
    from repro.core.registers import CrossbarRegisters, ErrorCode

    E, k = moe.n_experts, moe.top_k
    B_loc, S, d = x.shape
    T_loc = B_loc * S
    E_loc = params["w_in"].shape[0]
    if E_loc == 0 or E % E_loc:
        raise ValueError(
            f"local expert block ({E_loc}) must divide n_experts ({E}); "
            f"shard w_in/w_out over the '{axis_name}' mesh axis")
    n_shards = E // E_loc
    cap = (capacity if capacity is not None
           else expert_capacity(T_loc * n_shards, moe))
    if registers is None:
        registers = CrossbarRegisters.create(E, capacity=cap)
    xf = x.reshape(T_loc, d)
    dst, w, probs = _moe_router(params, xf, moe, expert_mask)

    fabric, _ = _group_fabric(E, cap, "sharded", axis_name, kernel_mode)
    xk = jnp.repeat(xf, k, axis=0)                         # [T_loc*k, d]
    src = jnp.zeros((T_loc * k,), jnp.int32)               # axis idx wins

    def experts_fn(slabs):                                 # [E_loc, C, d]
        return _expert_ffn(slabs, params["w_in"], params["w_out"], act)

    y, plan = fabric.transfer(xk, dst, src, apply_fn=experts_fn,
                              weights=w, registers=registers)
    y = y.reshape(T_loc, k, d).sum(axis=1).reshape(B_loc, S, d)

    me = jax.lax.axis_index(axis_name)
    # top_k destinations are always in [0, E); mode="drop" states the OOB
    # policy outright instead of a clip that would alias onto expert E-1.
    local_counts = jax.lax.psum(
        jnp.zeros((E,), jnp.int32).at[dst].add(
            (plan.keep & (dst // E_loc == me)).astype(jnp.int32),
            mode="drop"),
        axis_name)                                         # [E] per-port
    local = jnp.sum(local_counts)
    offered = jnp.asarray(T_loc * k * n_shards, jnp.int32)
    granted = jnp.sum(plan.counts)
    frac_tokens = (plan.counts / (T_loc * n_shards * k)).astype(jnp.float32)
    frac_probs = (jax.lax.psum(jnp.sum(probs, axis=0), axis_name)
                  / (T_loc * n_shards))
    aux_loss = E * jnp.sum(frac_tokens * frac_probs)
    stats = {
        "aux_loss": aux_loss,
        "dropped": offered - granted,
        "iso_dropped": plan.drops[ErrorCode.INVALID_DEST],
        "capacity": jnp.asarray(cap),
        "counts": plan.counts,
        "offered_packets": offered,
        "granted_packets": granted,
        "local_packets": local,
        "remote_packets": granted - local,
        "local_counts": local_counts,
        "remote_counts": plan.counts - local_counts,
    }
    return y, stats


def moe_apply_sharded_reference(params, x: jax.Array, moe: MoEConfig,
                                act: str, *, n_shards: int,
                                registers=None,
                                expert_mask: Optional[jax.Array] = None,
                                capacity: Optional[int] = None
                                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-device oracle for :func:`moe_apply_sharded`.

    Same router, same register file, same stats — but the whole batch on
    one device through the *reference* backend, with each token's source
    port set to the shard that would own it (batch is laid out
    shard-major, exactly the shard_map partition).  The sharded path must
    match this bit-for-bit on plans and to float tolerance on outputs;
    the forced-4-device tests pin that.
    """
    from repro.core.registers import CrossbarRegisters, ErrorCode

    E, k = moe.n_experts, moe.top_k
    B, S, d = x.shape
    T = B * S
    if B % n_shards or E % n_shards:
        raise ValueError(f"batch {B} and n_experts {E} must both divide "
                         f"into {n_shards} shards")
    T_loc = T // n_shards
    E_loc = E // n_shards
    cap = capacity if capacity is not None else expert_capacity(T, moe)
    if registers is None:
        registers = CrossbarRegisters.create(E, capacity=cap)
    xf = x.reshape(T, d)
    dst, w, probs = _moe_router(params, xf, moe, expert_mask)

    fabric, _ = _group_fabric(E, cap, "reference")
    xk = jnp.repeat(xf, k, axis=0)
    src = jnp.repeat(jnp.arange(n_shards, dtype=jnp.int32), T_loc * k)

    def experts_fn(slabs):                                 # [E, C, d]
        return _expert_ffn(slabs, params["w_in"], params["w_out"], act)

    y, plan = fabric.transfer(xk, dst, src, apply_fn=experts_fn,
                              weights=w, registers=registers)
    y = y.reshape(T, k, d).sum(axis=1).reshape(B, S, d)

    # top_k destinations are always in [0, E); mode="drop" states the OOB
    # policy outright instead of a clip that would alias onto expert E-1.
    local_counts = jnp.zeros((E,), jnp.int32).at[dst].add(
        (plan.keep & (dst // E_loc == src)).astype(jnp.int32), mode="drop")
    local = jnp.sum(local_counts)
    offered = jnp.asarray(T * k, jnp.int32)
    granted = jnp.sum(plan.counts)
    frac_tokens = (plan.counts / (T * k)).astype(jnp.float32)
    aux_loss = E * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    stats = {
        "aux_loss": aux_loss,
        "dropped": offered - granted,
        "iso_dropped": plan.drops[ErrorCode.INVALID_DEST],
        "capacity": jnp.asarray(cap),
        "counts": plan.counts,
        "offered_packets": offered,
        "granted_packets": granted,
        "local_packets": local,
        "remote_packets": granted - local,
        "local_counts": local_counts,
        "remote_counts": plan.counts - local_counts,
    }
    return y, stats


def moe_forward_sharded(params, x: jax.Array, moe: MoEConfig, act: str, *,
                        mesh, axis_name: str = "expert", registers=None,
                        expert_mask: Optional[jax.Array] = None,
                        capacity: Optional[int] = None,
                        kernel_mode: Optional[str] = None
                        ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The model-side shard_map wrapper around :func:`moe_apply_sharded`.

    Shards ``x`` on its batch dim and the expert-indexed params over
    ``axis_name``; the register file and router weights stay replicated.
    Jit this (with ``registers`` as an argument!) and reconfiguration is
    value-only: ``jax.jit(lambda p, r, xx: moe_forward_sharded(p, xx, ...,
    registers=r))`` compiles once per shape and every ``Shell.post`` after
    that re-routes without a retrace.
    """
    import functools as _ft

    from jax.sharding import PartitionSpec as P

    from repro.core.registers import CrossbarRegisters

    n = mesh.shape[axis_name]
    T = x.shape[0] * x.shape[1]
    cap = capacity if capacity is not None else expert_capacity(T, moe)
    if registers is None:
        registers = CrossbarRegisters.create(moe.n_experts, capacity=cap)
    pspec = {"w_router": P(), "w_in": P(axis_name), "w_out": P(axis_name)}
    in_specs = [pspec, P(axis_name), P()]
    args = [params, x, registers]
    if expert_mask is not None:
        in_specs.append(P())
        args.append(expert_mask)

    @_ft.partial(jax.shard_map, mesh=mesh, in_specs=tuple(in_specs),
                 out_specs=(P(axis_name), P()))
    def run(p, xs, regs, *mask):
        return moe_apply_sharded(
            p, xs, moe, act, registers=regs, axis_name=axis_name,
            expert_mask=mask[0] if mask else None, capacity=cap,
            kernel_mode=kernel_mode)

    # Under jit, shard_map runs on Explicit and Auto meshes alike.
    return jax.jit(run)(*args)
