"""Pluggable data-plane backends for :class:`repro.fabric.Fabric`.

Every backend realises the same §IV-E interconnect contract — *plan* grant
decisions from the live register file, *dispatch* packets into destination
slabs, *combine* results back to packet order — and all of them are
plan-equivalent: identical ``keep``/``slot``/``error``/``counts`` for the
same packets and registers (property-tested against the dense oracle in
``tests/test_fabric.py``).

All three backends share the **scatter-native data plane** of
``repro.core.arbiter``: granted packets scatter straight into the flat
``dst * capacity + slot`` slab row with ``.at[addr].add`` and gather back
with ``jnp.take`` — O(T·D) bytes, no [T, S, C] selection tensor (the dense
one-hot/einsum formulations survive as ``arbiter.dispatch_dense`` /
``combine_dense``, test-only oracles).  What distinguishes the backends is
how the *plan* is computed and where the slabs live:

- ``reference`` — the pure-jnp plan oracle (``arbiter.wrr_dispatch_plan``:
  segment-cumsum stream ranks + the closed-form WRR slots).  The
  semantics ground truth.
- ``pallas``    — ONE fused multi-source plan kernel (``repro.kernels
  .crossbar_dispatch.ops._plan_multi``) grids over token blocks once and
  computes every (src, dst) stream's ranks and iso/quota verdicts in a
  single sweep — no per-master-port launches, no stacked [n, T]
  intermediates.  Ranks compose into global WRR slots with the shared
  closed form (``arbiter.wrr_slots``):

      slot(t) = sum_s' min(rank_t, granted[s', dst_t])
              + #{s' < src_t : granted[s', dst_t] > rank_t}

  which is exactly the lexicographic (round, source) position the rotating
  arbiter serves.  Token padding to the kernel block size is internal
  (``dst = -1`` rows drop via the isolation check).  Data movement uses
  the shared scatter path by default; ``data_plane="kernel"`` selects the
  historical blockwise MXU scatter/combine kernels instead.
- ``sharded``   — regions are shards of a mesh axis; dispatch scatters
  local packets into a flat send slab and ``all_to_all``s it, combine
  routes *addresses* across the axis (a second ``all_to_all`` pair) so
  each shard pulls exactly its own packets' result rows — bytes on the
  interconnect scale with packets, not with ``n_ports * capacity`` slabs.
  Methods must run inside ``shard_map`` over the axis; the per-source
  granted counts are ``all_gather``-ed so every shard computes the same
  global WRR slots the dense oracle assigns.  The register file's port
  space may be *larger* than the axis: ``n_ports`` destinations partition
  contiguously into ``n_ports // axis_size`` slave ports per shard (MoE
  expert parallelism: experts are slave ports, each shard owns an expert
  block), while source ids stay the axis indices.

Packets carry *values*, never shapes, from the register file — so an ERM
register rewrite re-routes traffic through already-compiled dispatch code.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import arbiter
from repro.core.arbiter import DispatchPlan, wrr_slots
from repro.core.registers import CrossbarRegisters, ErrorCode
from repro.fabric.interface import KernelMode


def _empty_plan(dst: jax.Array, n_ports: int) -> DispatchPlan:
    """The zero-packet plan: no grants, empty histogram."""
    T = dst.shape[0]
    z = jnp.zeros((T,), jnp.int32)
    return DispatchPlan(keep=z.astype(bool), slot=z,
                        dst=dst.astype(jnp.int32), error=z,
                        counts=jnp.zeros((n_ports,), jnp.int32),
                        drops=jnp.zeros((4,), jnp.int32))


# The closed-form WRR interleave every backend composes slots with now
# lives beside the plan oracle; re-exported here for compatibility.
_wrr_slots = wrr_slots


# ----------------------------------------------------------------------
# reference — pure-jnp plan oracle + shared scatter data plane
# ----------------------------------------------------------------------
class ReferenceBackend:
    """The plan-semantics ground truth (``arbiter.wrr_dispatch_plan``),
    moving packets through the shared scatter/gather path.  The dense
    one-hot formulations it used to run live on as ``arbiter
    .dispatch_dense`` / ``combine_dense``, the property suite's oracles."""

    name = "reference"
    #: data movement is the shared flat-address scatter/gather — the
    #: fabric's plan cache may substitute memoized address vectors.
    uses_shared_scatter = True

    def plan(self, dst: jax.Array, src: jax.Array,
             regs: CrossbarRegisters) -> DispatchPlan:
        if dst.shape[0] == 0:
            return _empty_plan(dst, regs.n_ports)
        return arbiter.wrr_dispatch_plan(dst, src, regs)

    def dispatch(self, x: jax.Array, plan: DispatchPlan,
                 regs: CrossbarRegisters, capacity: int) -> jax.Array:
        return arbiter.dispatch(x, plan, regs.n_ports, capacity)

    def combine(self, y: jax.Array, plan: DispatchPlan,
                weights: jax.Array) -> jax.Array:
        return arbiter.combine(y, plan, weights)


# ----------------------------------------------------------------------
# pallas — blockwise kernels + closed-form WRR slot composition
# ----------------------------------------------------------------------
class PallasBackend:
    """Fused multi-source plan kernel + scatter-native data movement.

    ``plan`` is ONE kernel launch: a single grid sweep over token blocks
    computes every (src, dst) stream's ranks and iso/quota verdicts at
    once (``_plan_multi``), and the global WRR slots compose from the
    granted-count matrix with the shared closed form.  Padding and the
    zero-packet edge are handled here so callers never see block sizes or
    ``dst = -1`` rows.

    ``data_plane`` selects how packets move: ``"scatter"`` (default) is
    the shared flat-address scatter/gather of ``repro.core.arbiter`` —
    XLA-native dynamic scatter, O(T·D) bytes; ``"kernel"`` keeps the
    historical blockwise MXU one-hot kernels (scatter re-expressed as a
    matmul) for experimentation on hardware where that wins.
    """

    name = "pallas"

    def __init__(self, *, block_t: int = 256,
                 interpret: Optional[bool] = None,
                 data_plane: str = "scatter",
                 kernel_mode: Optional[KernelMode] = None):
        if data_plane not in ("scatter", "kernel"):
            raise ValueError(f"data_plane must be 'scatter' or 'kernel', "
                             f"got {data_plane!r}")
        self.block_t = block_t
        self.interpret = interpret
        self.data_plane = data_plane
        self.kernel_mode: Optional[KernelMode] = None
        self._force_ref = False
        if kernel_mode is not None:
            self.apply_kernel_mode(kernel_mode)

    def apply_kernel_mode(self, mode: KernelMode) -> None:
        """Bind a resolved :class:`~repro.fabric.interface.KernelMode` —
        called exactly once, by ``Fabric.__init__`` (or the constructor).

        The mode decides the kernel *lowering* behind the unchanged
        ``plan``/``dispatch``/``combine`` surface: ``PALLAS`` /
        ``PALLAS_INTERPRET`` pin ``interpret`` for every pallas_call;
        ``XLA`` routes the plan through its compiled ``lax.scan``
        reference (bit-identical by the pinned kernel-vs-ref sweeps) and
        the data plane through the shared scatter/gather.  An explicit
        legacy ``interpret=`` wins over the mode — it is the narrower,
        older contract."""
        self.kernel_mode = mode
        self._force_ref = mode is KernelMode.XLA
        if self.interpret is None and mode.uses_pallas:
            self.interpret = mode.interpret

    @property
    def uses_shared_scatter(self) -> bool:
        """True on the default scatter data plane (the fabric's plan cache
        may substitute memoized address vectors); the historical blockwise
        MXU kernels move data their own way.  ``KernelMode.XLA`` forces
        the shared path — it *is* the XLA lowering of the data plane."""
        return self.data_plane == "scatter" or self._force_ref

    def plan(self, dst: jax.Array, src: jax.Array,
             regs: CrossbarRegisters) -> DispatchPlan:
        from repro.kernels.crossbar_dispatch.ops import _plan_multi
        n = regs.n_ports
        T = dst.shape[0]
        if T == 0:
            return _empty_plan(dst, n)
        dst = dst.astype(jnp.int32)
        src = src.astype(jnp.int32)
        dstc = jnp.clip(dst, 0, n - 1)
        srcc = jnp.clip(src, 0, n - 1)
        # Fold reset gating into the isolation matrix the kernel consumes;
        # quota is stored [dst, src] in the register file, the kernel
        # indexes [src, dst].
        allowed_eff = (regs.allowed & ~regs.reset[:, None]
                       & ~regs.reset[None, :]).astype(jnp.int32)
        keep_pre, rank, err_pre, granted = _plan_multi(
            dst, src, allowed_eff, regs.quota.T, block_t=self.block_t,
            interpret=self.interpret, force_ref=self._force_ref)
        keep_pre = keep_pre > 0                              # iso & quota

        slot = wrr_slots(rank, granted, dstc, srcc[None, :])
        cap_ok = slot < regs.capacity[dstc]
        keep = keep_pre & cap_ok
        error = jnp.where(err_pre != ErrorCode.OK, err_pre,
                          jnp.where(cap_ok, jnp.int32(ErrorCode.OK),
                                    jnp.int32(ErrorCode.ACK_TIMEOUT)))
        counts = jnp.zeros((n,), jnp.int32).at[dstc].add(
            keep.astype(jnp.int32), mode="drop")
        drops = jnp.zeros((4,), jnp.int32).at[error].add(1, mode="drop")
        return DispatchPlan(keep=keep, slot=jnp.where(keep, slot, 0),
                            dst=dst, error=error, counts=counts, drops=drops)

    def dispatch(self, x: jax.Array, plan: DispatchPlan,
                 regs: CrossbarRegisters, capacity: int) -> jax.Array:
        if self.uses_shared_scatter:
            return arbiter.dispatch(x, plan, regs.n_ports, capacity)
        from repro.kernels.crossbar_dispatch.ops import \
            _dispatch as kernel_dispatch
        return kernel_dispatch(x, plan.dst, plan.keep.astype(jnp.int32),
                               plan.slot, n_ports=regs.n_ports,
                               capacity=capacity, block_t=self.block_t,
                               interpret=self.interpret)

    def combine(self, y: jax.Array, plan: DispatchPlan,
                weights: jax.Array) -> jax.Array:
        if self.uses_shared_scatter:
            return arbiter.combine(y, plan, weights)
        from repro.kernels.crossbar_dispatch.ops import \
            _combine as kernel_combine
        return kernel_combine(y, plan.dst, plan.keep.astype(jnp.int32),
                              plan.slot, weights, block_t=self.block_t,
                              interpret=self.interpret)


# ----------------------------------------------------------------------
# sharded — regions as shards of a mesh axis (inside shard_map)
# ----------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CombineRoute:
    """The ``all_to_all`` lane layout of one sharded combine, persisted.

    ``ShardedBackend.combine`` routes *addresses* before it routes rows:
    each source scatters the slab rows its packets occupy into
    per-destination-shard lanes and one ``all_to_all`` delivers them.  That
    address half depends only on the plan (which depends only on the
    offered packets and the register epoch) — so steady-state decode ticks
    can build it once per reconfiguration (``build_route``) and replay it
    (``combine(..., route=...)``), paying ICI setup per epoch instead of
    per token.  Replaying a route built for a different plan/slab shape is
    a correctness bug on the caller.
    """

    addr_recv: jax.Array   # [n_src, W] int32 — my slab rows to serve, per
    #                        requesting source shard (-1 = empty lane row)
    keep: jax.Array        # [T] bool — granted and within this slab depth
    pos: jax.Array         # [T] int32 — packet's lane position in its group
    dshard: jax.Array      # [T] int32 — destination shard per packet


# ----------------------------------------------------------------------
# sharded data movement with custom VJPs
#
# ``all_to_all(split_axis=0, concat_axis=0)`` is a self-inverse block
# permutation, so the transpose of (scatter -> all_to_all -> sum) is
# (broadcast -> the same all_to_all -> gather at the same flat address):
# the backward pass rides the identical ICI route the forward memoized —
# O(packets · D) bytes, no dense routing matrix, no slab all-gather.
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sharded_dispatch_at(axis_name, geom, x, addr):
    """Scatter local packets into the send slab at flat ``dst*C+slot``
    addresses, ``all_to_all`` the per-shard blocks, and sum per-source
    contributions into this shard's receive slabs [pps, C, D].
    ``geom = (n_src, pps, capacity)`` — static, resolved outside.
    Backward oracle: :func:`sharded_dispatch_at_bwd_ref`."""
    n_src, pps, capacity = geom
    n_dst = n_src * pps
    D = x.shape[-1]
    send = jnp.zeros((n_dst * capacity + 1, D),
                     x.dtype).at[addr].add(x)  # fablint: trash-row
    send = send[:n_dst * capacity].reshape(n_src, pps, capacity, D)
    recv = jax.lax.all_to_all(send, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
    return jnp.sum(recv, axis=0)                             # [pps, C, D]


def _sharded_dispatch_at_fwd(axis_name, geom, x, addr):
    return _sharded_dispatch_at(axis_name, geom, x, addr), addr


def _sharded_dispatch_at_bwd(axis_name, geom, addr, g):
    n_src, pps, capacity = geom
    n_dst = n_src * pps
    D = g.shape[-1]
    # The forward's sum over sources broadcasts; the self-inverse
    # all_to_all carries every destination shard's cotangent block home.
    gb = jnp.broadcast_to(g[None], (n_src, pps, capacity, D))
    back = jax.lax.all_to_all(gb, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
    flat = jnp.concatenate(
        [back.reshape(n_dst * capacity, D), jnp.zeros((1, D), g.dtype)],
        axis=0)
    return jnp.take(flat, addr, axis=0, mode="clip"), None


_sharded_dispatch_at.defvjp(_sharded_dispatch_at_fwd,
                            _sharded_dispatch_at_bwd)


def sharded_dispatch_at_bwd_ref(axis_name, geom, g, addr):
    """Dense one-hot oracle for the :func:`_sharded_dispatch_at` backward
    (explicit [T, n_dst*C+1] routing matrix — test-only; must still run
    inside the same ``shard_map``)."""
    n_src, pps, capacity = geom
    n_dst = n_src * pps
    D = g.shape[-1]
    gb = jnp.broadcast_to(g[None], (n_src, pps, capacity, D))
    back = jax.lax.all_to_all(gb, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
    flat = jnp.concatenate(
        [back.reshape(n_dst * capacity, D), jnp.zeros((1, D), g.dtype)],
        axis=0)
    oh = (addr[:, None]
          == jnp.arange(n_dst * capacity + 1)[None, :]).astype(g.dtype)
    return jnp.einsum("tr,rd->td", oh, flat)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sharded_combine_at(axis_name, n_src, y, addr_recv, idx, gate,
                        weights):
    """Address-routed sharded combine over a prebuilt route: gather my
    slab rows per requesting shard (``addr_recv``; -1 = empty lane),
    ``all_to_all`` them home, and read each packet's lane at ``idx =
    dshard * W + min(pos, W-1)`` gated by ``gate`` (the route's ``keep``).
    Backward oracle: :func:`sharded_combine_at_bwd_ref`."""
    pps, C, D = y.shape
    W = addr_recv.shape[-1]
    rows = jnp.take(y.reshape(pps * C, D), addr_recv, axis=0,
                    mode="clip")
    rows = rows * (addr_recv >= 0).astype(y.dtype)[..., None]
    back = jax.lax.all_to_all(rows, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
    flat = back.reshape(n_src * W, D)
    out = jnp.take(flat, idx, axis=0, mode="clip")
    return out * (gate.astype(y.dtype) * weights)[:, None]


def _sharded_combine_at_fwd(axis_name, n_src, y, addr_recv, idx, gate,
                            weights):
    pps, C, D = y.shape
    W = addr_recv.shape[-1]
    rows = jnp.take(y.reshape(pps * C, D), addr_recv, axis=0,
                    mode="clip")
    rows = rows * (addr_recv >= 0).astype(y.dtype)[..., None]
    back = jax.lax.all_to_all(rows, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
    flat = back.reshape(n_src * W, D)
    pre = jnp.take(flat, idx, axis=0, mode="clip")
    out = pre * (gate.astype(y.dtype) * weights)[:, None]
    return out, (y, pre, addr_recv, idx, gate, weights)


def _sharded_combine_at_bwd(axis_name, n_src, res, g):
    y, pre, addr_recv, idx, gate, weights = res
    pps, C, _ = y.shape
    y_dtype = y.dtype
    W = addr_recv.shape[-1]
    D = g.shape[-1]
    gw = g * (gate.astype(g.dtype) * weights.astype(g.dtype))[:, None]
    # Scatter each packet's weighted cotangent into its lane (dropped
    # packets carry exact zeros and park in the trash lane row), ride the
    # self-inverse all_to_all back to the owning shard, and scatter-add
    # into its slab at the same served addresses.
    lane = jnp.where(gate, idx, jnp.int32(n_src * W))
    d_flat = jnp.zeros((n_src * W + 1, D), y_dtype).at[lane].add(
        gw.astype(y_dtype))  # fablint: trash-row
    d_back = d_flat[:n_src * W].reshape(n_src, W, D)
    d_rows = jax.lax.all_to_all(d_back, axis_name, split_axis=0,
                                concat_axis=0, tiled=False)
    live = addr_recv >= 0
    d_rows = d_rows * live.astype(y_dtype)[..., None]
    raddr = jnp.where(live, addr_recv, jnp.int32(pps * C))
    d_y = jnp.zeros((pps * C + 1, D), y_dtype).at[
        raddr.reshape(-1)].add(
        d_rows.reshape(-1, D))  # fablint: trash-row
    d_y = d_y[:pps * C].reshape(pps, C, D)
    d_w = (jnp.sum(g * pre.astype(g.dtype), axis=-1)
           * gate.astype(g.dtype)).astype(weights.dtype)
    return d_y, None, None, None, d_w


_sharded_combine_at.defvjp(_sharded_combine_at_fwd,
                           _sharded_combine_at_bwd)


def sharded_combine_at_bwd_ref(axis_name, n_src, g, y, addr_recv, idx,
                               gate, weights):
    """Dense one-hot oracle for the :func:`_sharded_combine_at` backward
    ((d_y, d_weights) via explicit routing matrices — test-only; must run
    inside the same ``shard_map``)."""
    pps, C, D = y.shape
    W = addr_recv.shape[-1]
    gf = g.astype(jnp.float32)
    gw = gf * (gate.astype(jnp.float32) * weights.astype(jnp.float32))[:, None]
    oh_lane = ((idx[:, None] == jnp.arange(n_src * W)[None, :])
               & gate[:, None]).astype(jnp.float32)
    d_back = jnp.einsum("tl,td->ld", oh_lane, gw).reshape(n_src, W, D)
    d_rows = jax.lax.all_to_all(d_back, axis_name, split_axis=0,
                                concat_axis=0, tiled=False)
    oh_recv = ((addr_recv[..., None] == jnp.arange(pps * C)[None, None, :])
               & (addr_recv >= 0)[..., None]).astype(jnp.float32)
    d_y = jnp.einsum("swr,swd->rd", oh_recv, d_rows).reshape(pps, C, D)
    rows = jnp.einsum("swr,rd->swd", oh_recv,
                      y.reshape(pps * C, D).astype(jnp.float32))
    back = jax.lax.all_to_all(rows, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
    pre = jnp.einsum("tl,ld->td", oh_lane,
                     back.reshape(n_src * W, D))
    d_w = jnp.sum(gf * pre, axis=-1)
    return d_y.astype(y.dtype), d_w.astype(weights.dtype)


class ShardedBackend:
    """Crossbar over ICI collectives: every method must be called inside a
    ``shard_map`` over ``axis_name``; each shard is one source region (its
    source id is the axis index — the ``src`` argument is ignored) and
    holds its local packets.  The register file's ``n_ports`` destinations
    partition contiguously across the axis (``ports_per_shard = n_ports //
    axis_size`` slave ports per shard — 1 in the region-per-shard case, an
    expert block in MoE expert parallelism); after ``dispatch`` each shard
    owns the receive slabs of its own port block.  ``counts``/``drops``
    are psummed so every shard sees the oracle's global histogram."""

    name = "sharded"
    #: slabs are partitioned across the axis; the fabric's single-device
    #: address cache does not describe this data plane.
    uses_shared_scatter = False

    def __init__(self, axis_name: str):
        self.axis_name = axis_name

    def effective_src(self, src: jax.Array) -> jax.Array:
        """The source port this backend actually plans with — its mesh
        axis index, not the caller's ``src`` vector (which it ignores).
        The checkify sanitizer asks for this so its isolation re-check
        matches the plan's own arbitration inputs."""
        return jnp.full_like(src.astype(jnp.int32),
                             jax.lax.axis_index(self.axis_name))

    def ports_per_shard(self, regs: CrossbarRegisters) -> int:
        """Slave ports each shard owns; ``n_ports`` must divide evenly."""
        n_src = jax.lax.axis_size(self.axis_name)
        n_dst = regs.n_ports
        if n_dst % n_src:
            raise ValueError(
                f"sharded backend needs n_ports ({n_dst}) divisible by the "
                f"'{self.axis_name}' axis size ({n_src}) so the port space "
                f"partitions into equal per-shard blocks")
        return n_dst // n_src

    def plan(self, dst: jax.Array, src: jax.Array,
             regs: CrossbarRegisters) -> DispatchPlan:
        ax = self.axis_name
        n_dst = regs.n_ports
        self.ports_per_shard(regs)                           # divisibility
        me = jax.lax.axis_index(ax)
        dst = dst.astype(jnp.int32)
        in_range = (dst >= 0) & (dst < n_dst)
        dstc = jnp.clip(dst, 0, n_dst - 1)
        iso_ok = (in_range & regs.allowed[me, dstc]
                  & ~regs.reset[me] & ~regs.reset[dstc])
        rank = arbiter._stream_ranks(dstc, iso_ok, n_dst)
        quota = regs.quota[dstc, me]
        keep_pre = iso_ok & ((quota == 0) | (rank < quota))

        # Global WRR slots from the all-gathered per-source granted counts.
        mine = jnp.zeros((n_dst,), jnp.int32).at[dstc].add(
            keep_pre.astype(jnp.int32), mode="drop")
        granted = jax.lax.all_gather(mine, ax)               # [src, dst]
        slot = wrr_slots(rank, granted, dstc, me)
        cap_ok = slot < regs.capacity[dstc]
        keep = keep_pre & cap_ok
        error = jnp.where(
            ~iso_ok, jnp.int32(ErrorCode.INVALID_DEST),
            jnp.where(~keep_pre, jnp.int32(ErrorCode.GRANT_TIMEOUT),
                      jnp.where(cap_ok, jnp.int32(ErrorCode.OK),
                                jnp.int32(ErrorCode.ACK_TIMEOUT))))
        counts = jax.lax.psum(
            jnp.zeros((n_dst,), jnp.int32).at[dstc].add(
                keep.astype(jnp.int32), mode="drop"),
            ax)
        drops = jax.lax.psum(
            jnp.zeros((4,), jnp.int32).at[error].add(1, mode="drop"), ax)
        return DispatchPlan(keep=keep, slot=jnp.where(keep, slot, 0),
                            dst=dst, error=error, counts=counts, drops=drops)

    def dispatch(self, x: jax.Array, plan: DispatchPlan,
                 regs: CrossbarRegisters, capacity: int) -> jax.Array:
        """Local packets [T_loc, D] -> this shard's receive slabs [P, C, D]
        (``P = ports_per_shard`` — the shard's contiguous slave-port block).

        The send slab is scatter-built at the shared flat ``dst * C +
        slot`` address (no [T, n_dst, C] selection tensor); slots are
        globally unique per destination, so the per-source contributions
        coming out of the ``all_to_all`` just sum."""
        n_src = jax.lax.axis_size(self.axis_name)
        n_dst = regs.n_ports
        pps = self.ports_per_shard(regs)
        addr = arbiter.flat_slot_addr(plan, n_dst, capacity)
        # The custom-VJP primitive replays the same flat address route in
        # the backward pass (gather after the self-inverse all_to_all).
        return _sharded_dispatch_at(self.axis_name, (n_src, pps, capacity),
                                    x, addr)                 # [P, C, D]

    def build_route(self, plan: DispatchPlan,
                    capacity: int) -> CombineRoute:
        """The address half of :meth:`combine`: one ``all_to_all`` of int
        addresses that tells every shard which of its slab rows each
        source's packets occupy.  Depends only on the plan and the slab
        depth — persist it across ticks within a register epoch and replay
        via ``combine(..., route=...)`` (a shell event that bumps the epoch
        changes the plan, so the route must be rebuilt with it)."""
        ax = self.axis_name
        n_src = jax.lax.axis_size(ax)
        n_dst = plan.counts.shape[0]
        pps = n_dst // n_src
        C = capacity
        T = plan.dst.shape[0]
        # Row budget per (source, destination-shard) lane: a source cannot
        # land more packets on one shard than it has packets, nor more than
        # the shard's port block holds.
        W = min(T, pps * C)
        dstc = jnp.clip(plan.dst, 0, n_dst - 1)
        dshard = dstc // pps
        # Over-slab slots drop like everywhere else on the scatter data
        # plane (the dispatch trashed them via ``flat_slot_addr``); without
        # this guard the clip in ``combine`` would alias them onto the
        # last row.
        keep = plan.keep & (plan.slot < C)
        # Position of each kept packet within its destination-shard group.
        pos = arbiter._stream_ranks(dshard, keep, n_src)
        local_addr = (dstc % pps) * C + plan.slot            # row in dest's y
        # Scatter addresses into the per-destination-shard send lanes
        # (lane W is the trash slot for drops; -1 marks empty rows).
        lane = dshard * (W + 1) + jnp.where(keep, jnp.minimum(pos, W), W)
        addr_send = jnp.full((n_src * (W + 1),), -1, jnp.int32).at[lane].set(
            jnp.where(keep, local_addr, -1))  # fablint: trash-row (lane W)
        addr_send = addr_send.reshape(n_src, W + 1)[:, :W]
        addr_recv = jax.lax.all_to_all(addr_send, ax, split_axis=0,
                                       concat_axis=0, tiled=False)
        return CombineRoute(addr_recv=addr_recv, keep=keep, pos=pos,
                            dshard=dshard)

    def combine(self, y: jax.Array, plan: DispatchPlan,
                weights: jax.Array, *,
                route: Optional[CombineRoute] = None) -> jax.Array:
        """Local result slabs [P, C, D] -> local packets [T_loc, D], weighted.

        Address-route gather: each source shard sends, per destination
        shard, the local slab rows its packets occupy (one ``all_to_all``
        of int addresses), the destination gathers those rows out of its
        own [P, C, D] block, and a second ``all_to_all`` carries them
        home.  Bytes on the interconnect are O(packets · D) — the
        all-gather of *entire* result slabs this replaces shipped the full
        [n_src, P, C, D] capacity surface to every shard, even though each
        source only reads its own packets' rows.  Dropped packets get
        zeros.

        ``route`` replays a persisted :class:`CombineRoute` (built by
        :meth:`build_route` for THIS plan and this slab depth), skipping
        the address ``all_to_all`` — the steady-state mode where ICI
        setup is paid once per reconfiguration, not per token.  Results
        are bit-identical with and without a route."""
        ax = self.axis_name
        n_src = jax.lax.axis_size(ax)
        pps, C, D = y.shape
        T = plan.dst.shape[0]
        if T == 0 or C == 0:        # nothing sent / nothing grantable
            return jnp.zeros((T, D), y.dtype)
        if route is None:
            route = self.build_route(plan, C)
        W = route.addr_recv.shape[-1]
        # In-range by construction (dshard < n_src, min(pos, W-1) < W);
        # dropped packets read a garbage row that ``keep`` zeros.  The
        # custom-VJP primitive replays the identical lane route backward.
        idx = route.dshard * W + jnp.minimum(route.pos, W - 1)
        return _sharded_combine_at(ax, n_src, y, route.addr_recv, idx,
                                   route.keep, weights)


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------
_BACKENDS: Dict[str, Callable[..., object]] = {
    "reference": ReferenceBackend,
    "pallas": PallasBackend,
    "sharded": ShardedBackend,
}


def register_fabric_backend(name: str, factory: Callable[..., object],
                            ) -> None:
    """Register a custom backend factory under ``name`` (duck-typed:
    ``plan``/``dispatch``/``combine`` with the signatures above).

    Once registered, the name works everywhere a backend is selected —
    ``Fabric(regs, backend=name)``, ``shell.fabric(backend=name)``, and
    ``moe_apply(dispatch_impl=name)``:

    >>> from repro.fabric import (Fabric, ReferenceBackend, get_backend,
    ...                           register_fabric_backend)
    >>> class LoggingBackend(ReferenceBackend):
    ...     name = "logging"
    >>> register_fabric_backend("logging", LoggingBackend)
    >>> get_backend("logging").name
    'logging'
    """
    _BACKENDS[name] = factory


def get_backend(spec, **kwargs):
    """Resolve a backend: an instance passes through, a name constructs."""
    if not isinstance(spec, str):
        return spec
    try:
        factory = _BACKENDS[spec]
    except KeyError:
        raise ValueError(f"unknown fabric backend {spec!r}; "
                         f"registered: {sorted(_BACKENDS)}") from None
    return factory(**kwargs)


def backend_names():
    return sorted(_BACKENDS)
