"""``Fabric`` — one data-plane object over the crossbar register file.

PR 1 put the control plane behind ``Shell.post``; this is the matching seam
for the data plane (§IV-E).  One object binds a register file (or a live
``Shell``) to a dispatch backend and exposes the whole packet round-trip:

    fabric = Fabric(regs, backend="pallas", capacity=64)
    plan          = fabric.plan(dst, src)
    slabs, plan   = fabric.dispatch(x, dst, src)
    y             = fabric.combine(slabs, plan)
    y, plan       = fabric.transfer(x, dst, src, apply_fn=module_fn)

**Epoch awareness is the point.**  Every jitted entry point takes the
register file as a *traced argument*: shapes are static, values are read at
call time.  A fabric bound to a ``Shell`` (``shell.fabric()``) re-reads
``shell.registers`` on every call, so a ``shell.post(Grow(...))`` re-routes
the very next ``transfer`` without a single recompile — the paper's cheap
reconfiguration surface, enforced at the API boundary.  ``trace_count``
exposes how often XLA retraced, which the regression tests pin across
reconfigurations.  Callers that are *already inside a trace* (a model's
shard_map body under an outer jit — the sharded-MoE path) pass the register
file they received as an argument via ``registers=`` so the same guarantee
holds one level up.

Backends (``reference`` / ``pallas`` / ``sharded``) are plan-equivalent and
selected at construction; see ``repro.fabric.backends``.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import checkify

from repro.core import arbiter
from repro.core.arbiter import DispatchPlan
from repro.core.registers import CrossbarRegisters, ErrorCode
from repro.fabric import sanitize
from repro.fabric.backends import get_backend
from repro.fabric.cache import PlanCache, plan_key
from repro.fabric.interface import KernelMode, resolve_kernel_mode

ApplyFn = Callable[[jax.Array], jax.Array]

#: env hook: ``REPRO_FABRIC_DEBUG=1`` (or ``sanitize``/``strict``) turns the
#: checkify sanitizer on for every fabric constructed without an explicit
#: ``debug=`` — see :mod:`repro.fabric.sanitize` and docs/invariants.md.
DEBUG_ENV_VAR = "REPRO_FABRIC_DEBUG"


def _resolve_debug(debug) -> Union[bool, str]:
    """Normalize the ``debug`` constructor argument (or, when it is None,
    the ``REPRO_FABRIC_DEBUG`` environment variable) to one of
    ``False | "sanitize" | "strict"``."""
    if debug is None:
        env = os.environ.get(DEBUG_ENV_VAR, "").strip().lower()
        if env in ("1", "true", "on", "sanitize"):
            return "sanitize"
        if env == "strict":
            return "strict"
        return False
    if debug is True:
        return "strict"
    if debug in (False, "off", "none", ""):
        return False
    if debug in sanitize.LEVELS:
        return debug
    raise ValueError(
        f"debug must be True/False, 'sanitize' or 'strict'; got {debug!r}")


def _in_trace(*vals) -> bool:
    """True when any array leaf is a tracer — i.e. the caller sits inside
    an outer jit/vmap/shard_map trace rather than at the host level."""
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(vals))


class Fabric:
    """Register-gated packet transfer with a pluggable dispatch backend.

    Parameters
    ----------
    registers:
        A ``CrossbarRegisters``, a live ``Shell`` (tracked: every call
        reads the shell's current, delta-maintained file), or a zero-arg
        callable returning the current registers.
    backend:
        ``"reference"`` | ``"pallas"`` | ``"sharded"`` | a backend
        instance.  ``backend_kw`` feed the named factory (e.g.
        ``block_t=`` for pallas, ``axis_name=`` for sharded).
    capacity:
        Static receive-slab depth (tokens per destination).  Grant checks
        use ``min(registers.capacity, capacity)`` so register values stay
        the dynamic bandwidth knob while shapes stay compiled.  Defaults
        to the bound register file's max capacity at construction.
    debug:
        The checkify sanitizer (``repro.fabric.sanitize``).  ``False``
        (checks compile to nothing — the default), ``"sanitize"``
        (structural invariants that only a data-plane bug can fire),
        ``"strict"``/``True`` (sanitize + raise on masked faults: invalid
        destinations, over-capacity ACK_TIMEOUT bursts).  ``None`` reads
        ``REPRO_FABRIC_DEBUG`` (``1``/``sanitize``/``strict``).

        Host-level calls raise ``checkify.JaxRuntimeError`` directly.
        Calls already inside a trace keep their checks only when ``debug``
        was passed *explicitly* — the caller must then functionalize them
        (``checkify.checkify`` around its outer jit; ``shard_map`` bodies
        additionally need ``check_vma=False``).  Env-sourced debug skips
        in-trace checks so exporting the variable cannot break programs
        that never opted in.
    plan_cache:
        The steady-state fast path (``repro.fabric.cache``): ``True`` (a
        default-sized LRU), an int (LRU size), or ``False``/``None`` (off
        — the default).  When on, host-level ``plan``/``dispatch``/
        ``combine``/``transfer`` calls against the *bound* register file
        memoize their ``DispatchPlan`` and scatter address vectors per
        ``(register_epoch, offered-bytes)`` key; the epoch counter
        ``Shell.post`` maintains invalidates everything automatically, so
        a cached result is always from the current routing table.  Cached
        paths are bit-identical to uncached ones (the plan-equivalence
        suite pins this) and hit/miss/invalidation counters flow through
        ``probe()`` into ``Signals``.  Calls made inside a trace or with
        a ``registers=`` override always bypass the cache.
    kernel_mode:
        The kernel-lowering seam (:class:`repro.fabric.KernelMode`):
        ``"auto"``/``None`` (pallas on TPU, XLA elsewhere — resolved once,
        here), ``"xla"``, ``"pallas"``, or ``"pallas_interpret"``.  The
        resolved mode is bound into the backend at construction so
        real-TPU sweeps and ``launch/roofline.py`` select lowerings
        without touching any ``plan``/``dispatch``/``combine``/
        ``transfer`` call site; passing nothing keeps each backend's
        historical defaults bit-for-bit.  See docs/training.md.
    """

    def __init__(self, registers, *, backend: Union[str, Any] = "reference",
                 capacity: Optional[int] = None,
                 debug: Optional[Union[bool, str]] = None,
                 plan_cache: Union[bool, int, None] = False,
                 kernel_mode: Union[str, KernelMode, None] = None,
                 **backend_kw):
        if isinstance(registers, CrossbarRegisters):
            regs0 = registers
            self._regs_fn = lambda: regs0
            self._epoch_fn = lambda: int(regs0.version)
        elif hasattr(registers, "registers"):
            # duck-typed Shell: live property, re-read on every call
            self._regs_fn = lambda: registers.registers
            # The shell already tracks the epoch as a host value; fall back
            # to reading the register file's version counter.
            if hasattr(registers, "epoch"):
                self._epoch_fn = lambda: int(registers.epoch)
            else:
                self._epoch_fn = lambda: int(self._regs_fn().version)
        elif callable(registers):
            self._regs_fn = registers
            self._epoch_fn = lambda: int(self._regs_fn().version)
        else:
            raise TypeError(f"cannot bind fabric to {type(registers)!r}")
        self.backend = get_backend(backend, **backend_kw)
        # ---- kernel-mode seam (repro.fabric.interface) -----------------
        # Resolved exactly ONCE, here: "auto" probes the platform at
        # construction, never inside a jitted call site, and the resolved
        # mode is pushed into the backend (pallas derives its interpret
        # flag / XLA-reference routing from it; the pure-XLA backends have
        # nothing to bind).  Legacy string kwargs keep working — see
        # docs/migration.md for the alias table.
        self.kernel_mode = resolve_kernel_mode(kernel_mode)
        bind_mode = getattr(self.backend, "apply_kernel_mode", None)
        if bind_mode is not None and kernel_mode is not None:
            bind_mode(self.kernel_mode)
        if capacity is None:
            capacity = int(np.max(np.asarray(self.registers.capacity)))
        self.capacity = int(capacity)
        # Host-side cumulative traffic counters, fed by ``account(plan)``
        # (the ``ElasticServer`` tick and sharded-MoE training loops call
        # it); ``FabricProbe`` samples them into manager telemetry.
        self.port_traffic = np.zeros(self.registers.n_ports, np.int64)
        self.offered_packets = 0
        self.granted_packets = 0
        self.remote_packets = 0         # granted into another shard's ports
        self.local_packets = 0          # granted into the source's own ports
        # Per-destination-port splits of the remote/local tallies — the
        # manager ranks individual Migrate moves by the remote (ICI-costing)
        # traffic of the port they would relocate.
        self.remote_port_traffic = np.zeros(self.registers.n_ports, np.int64)
        self.local_port_traffic = np.zeros(self.registers.n_ports, np.int64)
        # Per-SOURCE-port attribution of the drop tally: masked packets
        # (INVALID_DEST — the paper's crossbar masking path) and all
        # non-granted offers are charged to the port that *originated*
        # them, so hostile traffic debits the offender's own budget
        # instead of folding into the global counters (PR 9 isolation
        # telemetry).  Only calls that pass ``account(plan, src)`` fill
        # these — a plan alone does not carry its sources.
        self.masked_by_src = np.zeros(self.registers.n_ports, np.int64)
        self.dropped_by_src = np.zeros(self.registers.n_ports, np.int64)
        self._trace_counts = {"plan": 0, "dispatch": 0, "combine": 0,
                              "transfer": 0}
        self._debug_explicit = debug is not None
        self.debug = _resolve_debug(debug)
        self._jit_plan = jax.jit(self._plan_impl)
        self._jit_dispatch = jax.jit(self._dispatch_impl)
        self._jit_combine = jax.jit(self._combine_impl)
        self._jit_transfer = jax.jit(self._transfer_impl,
                                     static_argnames=("apply_fn",))
        # ---- steady-state plan cache (repro.fabric.cache) --------------
        # The cached-path programs trace once each on first use and are
        # counted under their own keys; like every other entry point they
        # must never RE-trace across reconfigurations (the register file
        # stays a traced argument on the cached paths too).
        self._shared_scatter = bool(getattr(self.backend,
                                            "uses_shared_scatter", False))
        if plan_cache:
            size = 128 if plan_cache is True else int(plan_cache)
            self.plan_cache: Optional[PlanCache] = PlanCache(maxsize=size)
            self._trace_counts.update(addrs=0, dispatch_cached=0,
                                      combine_cached=0, transfer_cached=0)
            self._jit_addrs = jax.jit(self._addrs_impl)
            self._jit_dispatch_cached = jax.jit(self._dispatch_cached_impl)
            self._jit_combine_cached = jax.jit(self._combine_cached_impl)
            self._jit_transfer_cached = jax.jit(
                self._transfer_cached_impl, static_argnames=("apply_fn",))
            if self.debug:
                dbg = dict(debug=self.debug)
                self._chk_dispatch_cached = jax.jit(checkify.checkify(
                    functools.partial(self._dispatch_cached_impl, **dbg)))
                self._chk_combine_cached = jax.jit(checkify.checkify(
                    functools.partial(self._combine_cached_impl, **dbg)))
                self._chk_transfer_cached_cache = {}
        else:
            self.plan_cache = None
        if self.debug:
            dbg = dict(debug=self.debug)
            # In-trace entry points with bare checks: the enclosing program
            # functionalizes them (checkify.checkify around its outer jit).
            self._jit_plan_dbg = jax.jit(
                functools.partial(self._plan_impl, **dbg))
            self._jit_dispatch_dbg = jax.jit(
                functools.partial(self._dispatch_impl, **dbg))
            self._jit_combine_dbg = jax.jit(
                functools.partial(self._combine_impl, **dbg))
            self._jit_transfer_dbg = jax.jit(
                functools.partial(self._transfer_impl, **dbg),
                static_argnames=("apply_fn",))
            # Host-level entry points: jit OUTERMOST so each (shape) traces
            # once and returns a concrete error to throw.
            self._chk_plan = jax.jit(checkify.checkify(
                functools.partial(self._plan_impl, **dbg)))
            self._chk_dispatch = jax.jit(checkify.checkify(
                functools.partial(self._dispatch_impl, **dbg)))
            self._chk_combine = jax.jit(checkify.checkify(
                functools.partial(self._combine_impl, **dbg)))
            self._chk_transfer_cache = {}

    # ---- live views ---------------------------------------------------
    @property
    def registers(self) -> CrossbarRegisters:
        """The register file read *now* (live when bound to a shell)."""
        return self._regs_fn()

    @property
    def epoch(self) -> int:
        return self._epoch_fn()

    @property
    def n_ports(self) -> int:
        return self.registers.n_ports

    @property
    def trace_count(self) -> int:
        """Total retraces across all entry points (regression-pinned:
        reconfigurations must not increase it)."""
        return sum(self._trace_counts.values())

    @property
    def trace_counts(self):
        return dict(self._trace_counts)

    def probe(self):
        """A ``repro.manager`` telemetry probe over this fabric (epoch +
        retrace counters — the manager's zero-recompile regression signal —
        plus whatever traffic ``account`` has accumulated)."""
        from repro.manager.telemetry import FabricProbe
        return FabricProbe(self)

    def reset_accounting(self, *, cold_cache: bool = False) -> None:
        """Zero every cumulative traffic counter (and the plan cache's
        hit/miss/invalidation stats — entries stay warm by default) so a
        new measurement window starts clean.  ``ElasticServer.reset``
        calls this; a fabric shared across scenarios must not leak one
        run's ``port_traffic`` into the next run's first ``Signals``
        window.

        ``cold_cache=True`` additionally drops the memoized entries
        (``PlanCache.reset``): the record→replay mode, where a replayed
        scenario must observe the *same* hit/miss sequence the recording
        did — warm entries would turn its first offers into hits and skew
        ``plan_cache_hit_rate`` off the recorded value."""
        self.port_traffic = np.zeros_like(self.port_traffic)
        self.remote_port_traffic = np.zeros_like(self.remote_port_traffic)
        self.local_port_traffic = np.zeros_like(self.local_port_traffic)
        self.masked_by_src = np.zeros_like(self.masked_by_src)
        self.dropped_by_src = np.zeros_like(self.dropped_by_src)
        self.offered_packets = 0
        self.granted_packets = 0
        self.remote_packets = 0
        self.local_packets = 0
        if self.plan_cache is not None:
            if cold_cache:
                self.plan_cache.reset()
            else:
                self.plan_cache.reset_stats()

    def account(self, plan, src=None, *, src_shard: Optional[int] = None,
                n_shards: Optional[int] = None) -> None:
        """Fold one concrete ``DispatchPlan`` into the cumulative traffic
        counters (host-side; call it with plans that have left the device).

        ``port_traffic`` accumulates per-destination grants, ``offered_``/
        ``granted_packets`` the drop tally (``dst = -1`` padding rows are
        never offered load).  ``src`` — the [T] source-port vector the plan
        was computed from — additionally charges every masked packet
        (INVALID_DEST) and every non-granted offer to its *originating*
        port (``masked_by_src`` / ``dropped_by_src``): the isolation
        attribution the manager's abuse telemetry reads, so a tenant
        spraying invalid destinations debits only its own budget.  When
        ``src_shard``/``n_shards`` are given the grants also split into
        ``local_packets`` (granted into the source shard's own contiguous
        port block) vs ``remote_packets`` (granted across the mesh axis —
        the §IV-E crossbar hops that actually cost ICI bandwidth), each
        with a per-port vector (``local_port_traffic`` /
        ``remote_port_traffic``); the manager's ``Signals`` surfaces all
        of them.

        Plans handed back by the plan cache take a device-free fast path:
        the counts/offered/granted scalars *and* the per-source
        attribution vectors are pulled to the host once per entry and
        replayed as numpy values on every later tick.
        """
        cache = self.plan_cache
        if cache is not None and src_shard is None:
            entry = cache.entry_for_plan(self.epoch, plan)
            if entry is not None:
                if entry.acct is None:
                    src_v = src if src is not None else entry.src
                    entry.acct = (np.asarray(plan.counts, np.int64),
                                  int((np.asarray(plan.dst) >= 0).sum()),
                                  int(np.asarray(plan.keep).sum()),
                                  self._src_attribution(plan, src_v))
                counts, offered, granted, by_src = entry.acct
                self._add_counts(counts)
                self.offered_packets += offered
                self.granted_packets += granted
                if by_src is not None:
                    self._add_src_counts(*by_src)
                return
        self._add_counts(plan.counts)
        dst = np.asarray(plan.dst)
        keep = np.asarray(plan.keep)
        self.offered_packets += int((dst >= 0).sum())
        granted = int(keep.sum())
        self.granted_packets += granted
        by_src = self._src_attribution(plan, src)
        if by_src is not None:
            self._add_src_counts(*by_src)
        if src_shard is not None and n_shards:
            # Port space comes from the PLAN, not the cumulative vectors —
            # those may be longer (a wider register file was accounted
            # earlier, or the file shrank) and would skew pps/shapes.
            counts = np.asarray(plan.counts, np.int64)
            n = counts.shape[0]
            pps = max(1, n // n_shards)
            is_local = keep & (dst // pps == src_shard)
            local_counts = np.bincount(np.clip(dst, 0, n - 1),
                                       weights=is_local.astype(np.int64),
                                       minlength=n).astype(np.int64)[:n]
            local = int(local_counts.sum())
            self.local_packets += local
            self.remote_packets += granted - local
            self._add_split_counts(local_counts, counts - local_counts)

    def account_stats(self, stats) -> None:
        """Fold a sharded-MoE ``stats`` mapping (the second return of
        ``moe_apply(dispatch_impl="sharded")``, whose remote/local split is
        psummed in-graph where the shard index is known) into the same
        cumulative counters ``account`` maintains."""
        if "counts" in stats:
            self._add_counts(stats["counts"])
        self.offered_packets += int(stats.get("offered_packets", 0))
        self.granted_packets += int(stats.get("granted_packets", 0))
        self.remote_packets += int(stats.get("remote_packets", 0))
        self.local_packets += int(stats.get("local_packets", 0))
        if "local_counts" in stats or "remote_counts" in stats:
            n = self.port_traffic.shape[0]
            self._add_split_counts(
                np.asarray(stats.get("local_counts", np.zeros(n)), np.int64),
                np.asarray(stats.get("remote_counts", np.zeros(n)), np.int64))

    @staticmethod
    def _src_attribution(plan, src) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Per-source-port (masked, dropped) histograms for one plan.

        A packet is *offered* when ``dst >= 0`` (padding rows carry no
        load), *masked* when the arbiter answered INVALID_DEST (isolation
        violation, out-of-range destination or a reset port), *dropped*
        when offered but not granted for any reason.  Both tallies key on
        the originating source port — the attribution the abuse-penalty
        policies consume."""
        if src is None:
            return None
        src = np.asarray(src)
        dst = np.asarray(plan.dst)
        err = np.asarray(plan.error)
        keep = np.asarray(plan.keep).astype(bool)
        n = int(np.asarray(plan.counts).shape[0])
        offered = dst >= 0
        srcc = np.clip(src, 0, n - 1)
        masked = offered & (err == int(ErrorCode.INVALID_DEST))
        dropped = offered & ~keep
        return (np.bincount(srcc[masked], minlength=n)[:n].astype(np.int64),
                np.bincount(srcc[dropped], minlength=n)[:n].astype(np.int64))

    def _add_src_counts(self, masked: np.ndarray, dropped: np.ndarray) -> None:
        n = max(masked.shape[0], dropped.shape[0])
        self.masked_by_src = self._grow_to(self.masked_by_src, n)
        self.dropped_by_src = self._grow_to(self.dropped_by_src, n)
        self.masked_by_src[:masked.shape[0]] += masked
        self.dropped_by_src[:dropped.shape[0]] += dropped

    @staticmethod
    def _grow_to(vec: np.ndarray, n: int) -> np.ndarray:
        if n <= vec.shape[0]:
            return vec
        grown = np.zeros(n, np.int64)
        grown[:vec.shape[0]] = vec
        return grown

    def _add_counts(self, counts) -> None:
        counts = np.asarray(counts, np.int64)
        self.port_traffic = self._grow_to(self.port_traffic, counts.shape[0])
        self.port_traffic[:counts.shape[0]] += counts

    def _add_split_counts(self, local_counts, remote_counts) -> None:
        n = max(local_counts.shape[0], remote_counts.shape[0])
        self.local_port_traffic = self._grow_to(self.local_port_traffic, n)
        self.remote_port_traffic = self._grow_to(self.remote_port_traffic, n)
        self.local_port_traffic[:local_counts.shape[0]] += local_counts
        self.remote_port_traffic[:remote_counts.shape[0]] += remote_counts

    def _gated(self, regs: CrossbarRegisters) -> CrossbarRegisters:
        """Register capacities clamped to the static slab depth, so every
        backend grants into slots that exist."""
        return dataclasses.replace(
            regs, capacity=jnp.minimum(regs.capacity,
                                       jnp.int32(self.capacity)))

    # ---- jitted impls (register values are traced arguments) ----------
    # ``debug`` is a trace-time constant (bound via functools.partial at
    # construction): when False — the default jit wrappers — no check
    # enters the jaxpr and the compiled program is byte-identical to a
    # debug-less build.
    def _plan_impl(self, regs, dst, src, *, debug=False):
        self._trace_counts["plan"] += 1          # python: counts traces only
        gated = self._gated(regs)
        plan = self.backend.plan(dst, src, gated)
        if debug:
            sanitize.check_plan(plan, gated, src, self.backend, debug)
        return plan

    def _dispatch_impl(self, regs, x, dst, src, *, debug=False):
        self._trace_counts["dispatch"] += 1
        gated = self._gated(regs)
        plan = self.backend.plan(dst, src, gated)
        slabs = self.backend.dispatch(x, plan, regs, self.capacity)
        if debug:
            sanitize.check_plan(plan, gated, src, self.backend, debug)
            sanitize.check_slabs(slabs, debug)
        return slabs, plan

    def _combine_impl(self, regs, y, plan, weights, *, debug=False):
        self._trace_counts["combine"] += 1
        if debug:
            sanitize.check_combine(plan, y.shape[-2], debug)
        return self.backend.combine(y, plan, weights)

    def _transfer_impl(self, regs, x, dst, src, weights, *, apply_fn,
                       debug=False):
        self._trace_counts["transfer"] += 1
        gated = self._gated(regs)
        plan = self.backend.plan(dst, src, gated)
        slabs = self.backend.dispatch(x, plan, gated, self.capacity)
        if debug:
            sanitize.check_plan(plan, gated, src, self.backend, debug)
            sanitize.check_slabs(slabs, debug)
        y = slabs if apply_fn is None else apply_fn(slabs)
        if debug:
            sanitize.check_slabs(y, debug)
        return self.backend.combine(y, plan, weights), plan

    # ---- cached-path impls (plan + addresses are traced arguments) -----
    # The plan cache only kicks in at host level against the bound
    # register file, so these run with a concrete memoized plan; the
    # registers still flow in traced — reconfigurations that do NOT bump
    # the epoch (impossible via Shell.post, but the contract holds) would
    # still re-route values without retracing.
    def _addrs_impl(self, plan):
        self._trace_counts["addrs"] += 1     # python: counts traces only
        n = plan.counts.shape[0]
        daddr = arbiter.flat_slot_addr(plan, n, self.capacity)
        caddr, cmask = arbiter.combine_addr(plan, n, self.capacity)
        return daddr, caddr, cmask

    def _dispatch_cached_impl(self, regs, x, plan, src, daddr, *,
                              debug=False):
        self._trace_counts["dispatch_cached"] += 1
        gated = self._gated(regs)
        if self._shared_scatter:
            slabs = arbiter.dispatch_at(x, daddr, plan.counts.shape[0],
                                        self.capacity)
        else:
            slabs = self.backend.dispatch(x, plan, regs, self.capacity)
        if debug:
            sanitize.check_plan(plan, gated, src, self.backend, debug)
            sanitize.check_slabs(slabs, debug)
        return slabs, plan

    def _combine_cached_impl(self, regs, y, plan, caddr, cmask, weights, *,
                             debug=False):
        self._trace_counts["combine_cached"] += 1
        if debug:
            sanitize.check_combine(plan, y.shape[-2], debug)
        fast = (self._shared_scatter
                and tuple(y.shape[:2]) == (plan.counts.shape[0],
                                           self.capacity))
        if fast:
            return arbiter.combine_at(y, caddr, cmask, weights)
        return self.backend.combine(y, plan, weights)

    def _transfer_cached_impl(self, regs, x, plan, src, daddr, caddr,
                              cmask, weights, *, apply_fn, debug=False):
        self._trace_counts["transfer_cached"] += 1
        gated = self._gated(regs)
        n = plan.counts.shape[0]
        if self._shared_scatter:
            slabs = arbiter.dispatch_at(x, daddr, n, self.capacity)
        else:
            slabs = self.backend.dispatch(x, plan, gated, self.capacity)
        if debug:
            sanitize.check_plan(plan, gated, src, self.backend, debug)
            sanitize.check_slabs(slabs, debug)
        y = slabs if apply_fn is None else apply_fn(slabs)
        if debug:
            sanitize.check_slabs(y, debug)
        fast = (self._shared_scatter
                and tuple(y.shape[:2]) == (n, self.capacity))
        if fast:
            out = arbiter.combine_at(y, caddr, cmask, weights)
        else:
            out = self.backend.combine(y, plan, weights)
        return out, plan

    # ---- cache plumbing (host-side; never consulted inside a trace) ----
    def _cache_lookup(self, dst, src, registers):
        """The live entry for this offer, or None (cache off, an explicit
        ``registers=`` override — the epoch key only speaks for the bound
        file — or traced inputs)."""
        cache = self.plan_cache
        if cache is None or registers is not None:
            return None
        if isinstance(dst, jax.core.Tracer) or \
                isinstance(src, jax.core.Tracer):
            return None
        return cache.lookup(self.epoch, plan_key(dst, src))

    def _cache_store(self, dst, src, registers, new_plan) -> None:
        cache = self.plan_cache
        if cache is None or registers is not None:
            return
        if isinstance(dst, jax.core.Tracer) or \
                isinstance(src, jax.core.Tracer):
            return
        cache.store(self.epoch, plan_key(dst, src), new_plan,
                    jnp.asarray(src))

    def _cache_entry_for(self, plan_obj, registers, y):
        cache = self.plan_cache
        if cache is None or registers is not None:
            return None
        if isinstance(y, jax.core.Tracer):
            return None
        return cache.entry_for_plan(self.epoch, plan_obj)

    def _cache_addrs(self, entry):
        """Fill the entry's memoized scatter/gather address vectors on
        first data-plane use (plan-only workloads never pay for them)."""
        if entry.daddr is None:
            entry.daddr, entry.caddr, entry.cmask = \
                self._jit_addrs(entry.plan)
        return entry

    def _chk_transfer_cached(self, apply_fn):
        """Checkified cached transfer, per ``apply_fn`` (see
        :meth:`_chk_transfer`)."""
        fn = self._chk_transfer_cached_cache.get(apply_fn)
        if fn is None:
            fn = jax.jit(checkify.checkify(functools.partial(
                self._transfer_cached_impl, apply_fn=apply_fn,
                debug=self.debug)))
            self._chk_transfer_cached_cache[apply_fn] = fn
        return fn

    # ---- debug routing -------------------------------------------------
    def _debug_call(self, kind, chk_fn, dbg_fn, plain_fn, *args):
        """Pick the checked variant for a debug-mode call.  Host-level
        calls run the checkified program and throw; in-trace calls keep
        bare checks only under *explicit* debug (the caller functionalizes
        them) — env-sourced debug must never change programs that did not
        opt in, so those fall through to the unchecked path."""
        if _in_trace(*args):
            if self._debug_explicit:
                return dbg_fn(*args)
            return plain_fn(*args)
        err, out = chk_fn(*args)
        err.throw()
        return out

    # ---- public API ---------------------------------------------------
    # Every method takes an optional ``registers=`` override: the bound
    # file is the default, but code already *inside* a trace (a model's
    # shard_map body, an outer jit) must pass the register file it received
    # as a traced argument — that is what keeps reconfiguration
    # recompile-free end to end.

    def plan(self, dst: jax.Array, src: jax.Array, *,
             registers: Optional[CrossbarRegisters] = None) -> DispatchPlan:
        """Grant decisions for packets ``src[t] -> dst[t]`` under the
        current register values (``dst = -1`` marks padding).

        The plan is the paper's arbitration read-back: ``keep`` (granted),
        ``slot`` (global WRR receive slot), ``error`` (Table III codes for
        drops), ``counts`` (per-destination grant histogram), ``drops``
        (error-code histogram).

        >>> import jax.numpy as jnp
        >>> from repro.core.registers import CrossbarRegisters
        >>> from repro.fabric import Fabric
        >>> regs = CrossbarRegisters.create(4, capacity=8)
        >>> regs = regs.with_quota(dst=2, src=0, packages=1)  # WRR quota
        >>> fabric = Fabric(regs, backend="reference", capacity=8)
        >>> plan = fabric.plan(jnp.asarray([2, 2, 1]), jnp.asarray([0, 0, 0]))
        >>> int(plan.keep.sum())        # second packet to port 2 over quota
        2
        """
        entry = self._cache_lookup(dst, src, registers)
        if entry is not None:
            return entry.plan
        regs = self.registers if registers is None else registers
        if self.debug:
            out = self._debug_call("plan", self._chk_plan,
                                   self._jit_plan_dbg, self._jit_plan,
                                   regs, dst, src)
        else:
            out = self._jit_plan(regs, dst, src)
        self._cache_store(dst, src, registers, out)
        return out

    def dispatch(self, x: jax.Array, dst: jax.Array, src: jax.Array, *,
                 registers: Optional[CrossbarRegisters] = None
                 ) -> Tuple[jax.Array, DispatchPlan]:
        """Plan + scatter packets ``x`` [T, D] into destination receive
        slabs: [n_ports, C, D] for the single-device backends, this shard's
        [ports_per_shard, C, D] block for the sharded backend.  Dropped
        packets land nowhere; their error codes are in the returned plan."""
        regs = self.registers if registers is None else registers
        entry = self._cache_lookup(dst, src, registers)
        if entry is not None:
            self._cache_addrs(entry)
            if self.debug:
                err, out = self._chk_dispatch_cached(
                    regs, x, entry.plan, entry.src, entry.daddr)
                err.throw()
            else:
                out = self._jit_dispatch_cached(regs, x, entry.plan,
                                                entry.src, entry.daddr)
            # Hand back the memoized plan OBJECT (values are identical):
            # combine/account recognise it by identity and stay device-free.
            return out[0], entry.plan
        if self.debug:
            out = self._debug_call("dispatch", self._chk_dispatch,
                                   self._jit_dispatch_dbg,
                                   self._jit_dispatch, regs, x, dst, src)
        else:
            out = self._jit_dispatch(regs, x, dst, src)
        self._cache_store(dst, src, registers, out[1])
        return out

    def combine(self, y: jax.Array, plan: DispatchPlan,
                weights: Optional[jax.Array] = None, *,
                registers: Optional[CrossbarRegisters] = None) -> jax.Array:
        """Gather result slabs back to packet order ([T, D]), scaled by
        ``weights`` (e.g. MoE router probabilities); dropped packets get
        zeros (their error codes live in ``plan.error``)."""
        if weights is None:
            weights = jnp.ones(plan.keep.shape, y.dtype)
        regs = self.registers if registers is None else registers
        entry = self._cache_entry_for(plan, registers, y)
        if entry is not None:
            self._cache_addrs(entry)
            if self.debug:
                err, out = self._chk_combine_cached(
                    regs, y, entry.plan, entry.caddr, entry.cmask, weights)
                err.throw()
                return out
            return self._jit_combine_cached(regs, y, entry.plan,
                                            entry.caddr, entry.cmask,
                                            weights)
        if self.debug:
            return self._debug_call("combine", self._chk_combine,
                                    self._jit_combine_dbg,
                                    self._jit_combine, regs, y, plan,
                                    weights)
        return self._jit_combine(regs, y, plan, weights)

    def transfer(self, x: jax.Array, dst: jax.Array, src: jax.Array,
                 apply_fn: Optional[ApplyFn] = None,
                 weights: Optional[jax.Array] = None, *,
                 registers: Optional[CrossbarRegisters] = None
                 ) -> Tuple[jax.Array, DispatchPlan]:
        """Fused round-trip: plan -> dispatch -> ``apply_fn`` on the slabs
        -> combine.  One compiled program per (shape, ``apply_fn``)
        combination — pass a stable function, not a fresh lambda per call,
        or you pay a retrace each time.

        >>> import jax.numpy as jnp
        >>> from repro.core.registers import CrossbarRegisters
        >>> from repro.fabric import Fabric
        >>> regs = CrossbarRegisters.create(2, capacity=4)
        >>> fabric = Fabric(regs, backend="reference", capacity=4)
        >>> x = jnp.ones((3, 2))
        >>> dst = jnp.asarray([0, 1, 1]); src = jnp.asarray([0, 0, 0])
        >>> y, plan = fabric.transfer(x, dst, src, apply_fn=lambda s: s * 2)
        >>> y.shape, int(plan.keep.sum()), fabric.trace_counts["transfer"]
        ((3, 2), 3, 1)
        """
        if weights is None:
            weights = jnp.ones(dst.shape, x.dtype)
        regs = self.registers if registers is None else registers
        entry = self._cache_lookup(dst, src, registers)
        if entry is not None:
            self._cache_addrs(entry)
            if self.debug:
                err, out = self._chk_transfer_cached(apply_fn)(
                    regs, x, entry.plan, entry.src, entry.daddr,
                    entry.caddr, entry.cmask, weights)
                err.throw()
            else:
                out = self._jit_transfer_cached(
                    regs, x, entry.plan, entry.src, entry.daddr,
                    entry.caddr, entry.cmask, weights, apply_fn=apply_fn)
            return out[0], entry.plan       # identity-stable plan object
        if self.debug:
            out = self._debug_call(
                "transfer", self._chk_transfer(apply_fn),
                functools.partial(self._jit_transfer_dbg, apply_fn=apply_fn),
                functools.partial(self._jit_transfer, apply_fn=apply_fn),
                regs, x, dst, src, weights)
        else:
            out = self._jit_transfer(regs, x, dst, src, weights,
                                     apply_fn=apply_fn)
        self._cache_store(dst, src, registers, out[1])
        return out

    def _chk_transfer(self, apply_fn):
        """Checkified host-level transfer, cached per ``apply_fn`` (the
        same one-compiled-program-per-(shape, fn) contract as the normal
        path; checkify cannot thread a static callable, so it is closed
        over here instead)."""
        fn = self._chk_transfer_cache.get(apply_fn)
        if fn is None:
            fn = jax.jit(checkify.checkify(functools.partial(
                self._transfer_impl, apply_fn=apply_fn, debug=self.debug)))
            self._chk_transfer_cache[apply_fn] = fn
        return fn


def fabric_for_shell(shell, *, backend="reference", capacity=None,
                     **backend_kw) -> Fabric:
    """A fabric tracking ``shell.registers`` across epochs (the
    implementation behind ``Shell.fabric``)."""
    if capacity is None:
        capacity = getattr(shell, "capacity", None)
    return Fabric(shell, backend=backend, capacity=capacity, **backend_kw)
