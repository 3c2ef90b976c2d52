"""DEPRECATED compat shims — use ``repro.fabric.Fabric`` instead.

This module predates the unified data-plane API.  New code constructs a
:class:`repro.fabric.Fabric` (``backend="reference" | "pallas" |
"sharded"``) bound to a register file or a live ``Shell``; the functions
here remain as thin wrappers for existing callers:

- **local** (:func:`exchange_local` / :func:`combine_local`): one
  reference-backend dispatch round — identical to
  ``Fabric(regs, backend="reference").dispatch(...)``.
- **distributed** (:func:`exchange_sharded` / :func:`combine_sharded`):
  the *legacy pair-owned-slot* sharded path — each (src, dst) pair owns its
  own ``capacity`` slots, so its slot numbering differs from the dense
  oracle's shared WRR interleave.  ``repro.fabric.ShardedBackend`` is the
  plan-equivalent replacement (global WRR slots, oracle-identical plans).

The register file gates everything: isolation masks, quotas and resets are
*values*, so the Elastic Resource Manager re-routes traffic by rewriting
registers — never by recompiling the tenant program.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.arbiter import DispatchPlan
from repro.core.registers import CrossbarRegisters, ErrorCode


def _warn_deprecated(what: str, use: str) -> None:
    warnings.warn(f"DEPRECATED {what} — migrate to {use} "
                  f"(see docs/migration.md, repro.fabric)",
                  DeprecationWarning, stacklevel=3)


# ----------------------------------------------------------------------
# Local (single-shard) crossbar — shim over the fabric reference backend.
# ----------------------------------------------------------------------
def exchange_local(x: jax.Array, dst: jax.Array, src: jax.Array,
                   regs: CrossbarRegisters, capacity: int
                   ) -> Tuple[jax.Array, DispatchPlan]:
    """Route packets ``x`` [T, D] to per-destination slabs [S, capacity, D].

    Deprecated: ``Fabric(regs, backend="reference",
    capacity=capacity).dispatch(x, dst, src)`` is the maintained spelling.
    """
    _warn_deprecated("core.crossbar.exchange_local",
                     'Fabric(regs, backend="reference", capacity=C)'
                     '.dispatch(x, dst, src)')
    from repro.fabric.backends import ReferenceBackend
    backend = ReferenceBackend()
    plan = backend.plan(dst, src, regs)
    return backend.dispatch(x, plan, regs, capacity), plan


def combine_local(y: jax.Array, plan: DispatchPlan,
                  weights: Optional[jax.Array] = None) -> jax.Array:
    """Deprecated: use ``Fabric.combine``."""
    _warn_deprecated("core.crossbar.combine_local", "Fabric.combine(y, plan)")
    from repro.fabric.backends import ReferenceBackend
    if weights is None:
        weights = jnp.ones_like(plan.keep, dtype=y.dtype)
    return ReferenceBackend().combine(y, plan, weights)


# ----------------------------------------------------------------------
# Distributed crossbar — regions are shards of `axis_name`.
# ----------------------------------------------------------------------
def pairwise_dispatch_plan(dst: jax.Array, src_index: jax.Array,
                           regs: CrossbarRegisters, capacity: int
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-(src,dst)-pair slot assignment for the all_to_all send buffer.

    Returns (keep[T], slot[T] in [0, capacity), error[T]). ``src_index`` is
    this region's id (scalar). Slots are ranks within the packet's (src, dst)
    stream — each pair owns its own `capacity` slots, so no cross-source
    arbitration is needed on the send side; the WRR interleave appears on the
    receive side by reading (slot, src)-ordered.
    """
    n = regs.n_ports
    dst = dst.astype(jnp.int32)
    iso_ok = regs.allowed[src_index, dst] & ~regs.reset[dst] & ~regs.reset[src_index]
    dst_oh = jax.nn.one_hot(dst, n, dtype=jnp.int32) * iso_ok[:, None]
    rank = jnp.cumsum(dst_oh, axis=0) - dst_oh
    # Legacy shim: keep the default (fill) gather semantics bit-exact for
    # external callers; the fabric seam is the supported path.
    rank = jnp.take_along_axis(rank, dst[:, None], axis=1)[:, 0]  # fablint: disable=FAB001
    quota = regs.quota[dst, src_index]
    quota_ok = (quota == 0) | (rank < quota)
    cap_ok = rank < capacity
    keep = iso_ok & quota_ok & cap_ok
    error = jnp.where(~iso_ok, jnp.int32(ErrorCode.INVALID_DEST),
             jnp.where(~quota_ok, jnp.int32(ErrorCode.GRANT_TIMEOUT),
              jnp.where(~cap_ok, jnp.int32(ErrorCode.ACK_TIMEOUT),
                        jnp.int32(ErrorCode.OK))))
    return keep, jnp.where(keep, rank, 0), error


def exchange_sharded(x: jax.Array, dst: jax.Array, regs: CrossbarRegisters,
                     capacity: int, axis_name: str
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Inside shard_map: send local packets to their destination regions.

    ``x`` [T_local, D]; returns (recv [n, capacity, D], recv_mask [n, capacity],
    keep [T_local], slot [T_local]) where recv[i] holds what region ``i`` sent
    here. Reading recv as [capacity, n] (slot-major) is the WRR service order.
    """
    _warn_deprecated("core.crossbar.exchange_sharded",
                     'Fabric(regs, backend="sharded", axis_name=...)'
                     ".dispatch inside shard_map (oracle-identical slots)")
    n = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    keep, slot, _err = pairwise_dispatch_plan(dst, me, regs, capacity)

    T, D = x.shape
    dst_oh = jax.nn.one_hot(dst, n, dtype=x.dtype)
    slot_oh = jax.nn.one_hot(slot, capacity, dtype=x.dtype)
    sel = dst_oh[:, :, None] * slot_oh[:, None, :] * keep[:, None, None].astype(x.dtype)
    send = jnp.einsum("tsc,td->scd", sel, x)                  # [n, cap, D]
    mask = jnp.einsum("tsc->sc", sel)                          # [n, cap]

    recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    recv_mask = jax.lax.all_to_all(mask, axis_name, split_axis=0,
                                   concat_axis=0, tiled=False)
    return recv, recv_mask, keep, slot


def combine_sharded(y: jax.Array, dst: jax.Array, keep: jax.Array,
                    slot: jax.Array, weights: jax.Array, capacity: int,
                    axis_name: str) -> jax.Array:
    """Inverse of :func:`exchange_sharded`: bring results home and weight them."""
    _warn_deprecated("core.crossbar.combine_sharded",
                     'Fabric(regs, backend="sharded", axis_name=...)'
                     ".combine inside shard_map")
    n = jax.lax.axis_size(axis_name)
    back = jax.lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)                     # [n, cap, D]
    dst_oh = jax.nn.one_hot(dst, n, dtype=y.dtype)
    slot_oh = jax.nn.one_hot(slot, capacity, dtype=y.dtype)
    sel = dst_oh[:, :, None] * slot_oh[:, None, :] * (
        keep.astype(y.dtype) * weights)[:, None, None]
    return jnp.einsum("tsc,scd->td", sel, back)


@dataclasses.dataclass
class CrossbarInterconnect:
    """Deprecated wrapper binding a register file to exchange/combine ops.

    ``repro.fabric.Fabric`` supersedes this: it adds backend selection,
    epoch tracking against a live ``Shell``, and the fused ``transfer``
    round-trip.  ``as_fabric()`` converts in place."""

    regs: CrossbarRegisters
    capacity: int

    def exchange(self, x, dst, src):
        return exchange_local(x, dst, src, self.regs, self.capacity)

    def combine(self, y, plan, weights=None):
        return combine_local(y, plan, weights)

    def reconfigure(self, **updates) -> "CrossbarInterconnect":
        """ERM write: new register values, same compiled program."""
        return dataclasses.replace(self, regs=self.regs.write(**updates))

    def as_fabric(self, backend: str = "reference", **kw):
        """The maintained replacement: a ``Fabric`` over the same file."""
        from repro.fabric import Fabric
        return Fabric(self.regs, backend=backend, capacity=self.capacity,
                      **kw)
