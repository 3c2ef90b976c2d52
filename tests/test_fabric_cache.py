"""Epoch-keyed plan cache (``repro.fabric.cache``): the serving fast path.

The contract under test (docs/invariants.md):

- a cache hit hands back the *identical* plan object the miss stored, and
  the hit is bit-identical to recomputation by construction (keys are the
  exact offered bytes);
- every ``Shell.post`` bumps the register epoch and flushes the cache —
  a stale entry is never served across a reconfiguration.  Pinned both on
  a deterministic event script and (when hypothesis is installed) on
  randomized Grow/Shrink/FailRegion/heal sequences, each checked against
  an *uncached* oracle fabric over the same live register file;
- the cached data-plane paths (``dispatch``/``combine``/``transfer``) are
  bit-identical to the uncached ones under ``debug="strict"`` — the
  checkify sanitizer re-validates the memoized plan on every replay — on
  the reference and pallas backends at host level.  The sharded backend
  never sees the host-side cache (its methods only exist inside a
  ``shard_map``, where traced inputs bypass it); its steady-state memo is
  the persisted :class:`~repro.fabric.backends.CombineRoute`, covered in
  a forced-topology subprocess below;
- ``Fabric.account`` on a cache-hit plan takes the device-free fast path
  and accumulates exactly the counters the uncached path does;
- the cache never costs a retrace: trace counts stay flat across hits,
  misses and epoch flushes.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.module import ModuleFootprint
from repro.core.registers import CrossbarRegisters
from repro.fabric import Fabric
from repro.fabric.cache import PlanCache, plan_key
from repro.shell import FailRegion, Grow, Shell, Shrink, Submit

GB = 1 << 30
PLAN_FIELDS = ("keep", "slot", "error", "counts", "drops")
REPO = Path(__file__).resolve().parent.parent
SRC = str(REPO / "src")


def fp(gb=1):
    return ModuleFootprint(param_bytes=gb * GB, flops_per_token=1e9,
                           activation_bytes_per_token=4096)


def make_shell(n=4):
    from repro.core.elastic import Region
    return Shell([Region(rid=i, n_chips=16, hbm_bytes=16 * GB)
                  for i in range(n)])


def assert_plans_equal(a, b, msg=""):
    for f in PLAN_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{msg} field {f}")


# ----------------------------------------------------------------------
# PlanCache in isolation (host-side, no jax)
# ----------------------------------------------------------------------
class TestPlanCacheUnit:
    def test_plan_key_is_exact_bytes(self):
        d = np.arange(8, dtype=np.int32)
        s = np.zeros(8, np.int32)
        assert plan_key(d, s) == plan_key(d.copy(), s.copy())
        assert plan_key(d, s) != plan_key(d + 1, s)          # content
        assert plan_key(d, s) != plan_key(d[:7], s[:7])      # shape
        assert plan_key(d, s) != plan_key(d.astype(np.int64),
                                          s.astype(np.int64))  # dtype
        assert plan_key(d, s) != plan_key(s, d)              # order matters

    def test_miss_store_hit_counters(self):
        cache = PlanCache()
        key = plan_key(np.arange(4), np.zeros(4))
        assert cache.lookup(0, key) is None
        plan = object()
        entry = cache.store(0, key, plan)
        hit = cache.lookup(0, key)
        assert hit is entry and hit.plan is plan
        assert (cache.hits, cache.misses, len(cache)) == (1, 1, 1)
        assert cache.hit_rate == 0.5
        # identity-keyed side table: account/combine find the entry from
        # the plan object a hit handed back, nothing else.
        assert cache.entry_for_plan(0, plan) is entry
        assert cache.entry_for_plan(0, object()) is None

    def test_epoch_move_flushes_and_counts_once(self):
        cache = PlanCache()
        k1 = plan_key(np.arange(4), np.zeros(4))
        k2 = plan_key(np.arange(5), np.zeros(5))
        cache.store(0, k1, object())
        cache.store(0, k2, object())
        assert cache.lookup(1, k1) is None      # epoch moved: stale flushed
        assert cache.invalidations == 1
        assert len(cache) == 0
        # an epoch move over an EMPTY cache is not an invalidation
        assert cache.lookup(2, k1) is None
        assert cache.invalidations == 1
        # ... and moving back to an old epoch is still a flush boundary
        cache.store(2, k1, object())
        assert cache.lookup(0, k1) is None
        assert cache.invalidations == 2

    def test_lru_eviction_and_store_replace(self):
        cache = PlanCache(maxsize=2)
        keys = [plan_key(np.arange(i + 1), np.zeros(i + 1)) for i in range(3)]
        e0 = cache.store(0, keys[0], object())
        cache.store(0, keys[1], object())
        assert cache.lookup(0, keys[0]) is e0   # touch: 0 is now MRU
        cache.store(0, keys[2], object())       # evicts 1, not 0
        assert cache.lookup(0, keys[1]) is None
        assert cache.lookup(0, keys[0]) is e0
        # replacing a key drops the old entry from the identity table too
        e0b = cache.store(0, keys[0], object())
        assert cache.entry_for_plan(0, e0.plan) is None
        assert cache.entry_for_plan(0, e0b.plan) is e0b
        with pytest.raises(ValueError):
            PlanCache(maxsize=0)

    def test_reset_stats_keeps_entries_warm(self):
        cache = PlanCache()
        key = plan_key(np.arange(4), np.zeros(4))
        entry = cache.store(3, key, object())
        cache.lookup(3, key)
        cache.reset_stats()
        assert cache.stats() == {"plan_cache_hits": 0,
                                 "plan_cache_misses": 0,
                                 "plan_cache_invalidations": 0,
                                 "plan_cache_entries": 1}
        assert cache.lookup(3, key) is entry    # still warm


# ----------------------------------------------------------------------
# Fabric-level: hits, epoch invalidation, bypasses
# ----------------------------------------------------------------------
class TestFabricPlanCache:
    def offers(self, fabric, shell, T=12, seed=0):
        rng = np.random.default_rng(seed)
        dst = jnp.asarray(rng.integers(-1, fabric.n_ports, T), jnp.int32)
        src = jnp.full((T,), shell.state.host_port, jnp.int32)
        return dst, src

    def test_hit_returns_identical_plan_object(self):
        shell = make_shell()
        shell.submit("a", [fp(2), fp(2)], app_id=0)
        fabric = shell.fabric(plan_cache=True, capacity=8)
        dst, src = self.offers(fabric, shell)
        p0 = fabric.plan(dst, src)
        p1 = fabric.plan(dst, src)
        assert p1 is p0                        # memo, not recomputation
        stats = fabric.plan_cache.stats()
        assert stats["plan_cache_hits"] == 1
        assert stats["plan_cache_misses"] == 1
        assert fabric.trace_counts["plan"] == 1

    def test_deterministic_event_script_never_serves_stale(self):
        """Submit/Shrink/Grow/FailRegion each bump the epoch; after every
        post the cached fabric must agree bit-for-bit with an uncached
        oracle over the same live register file."""
        shell = make_shell()
        shell.submit("a", [fp(2), fp(2)], app_id=0)
        cached = shell.fabric(plan_cache=True, capacity=8)
        oracle = shell.fabric(plan_cache=False, capacity=8)
        dst, src = self.offers(cached, shell)

        stale = cached.plan(dst, src)
        assert cached.plan(dst, src) is stale
        events = [Submit(tenant="b", footprints=(fp(1),), app_id=1),
                  Shrink(tenant="a", n_regions=1),
                  Grow(tenant="a", n_regions=2),
                  FailRegion(rid=2)]
        for event in events:
            inval_before = cached.plan_cache.invalidations
            shell.post(event)
            assert cached.epoch == shell.epoch
            fresh = cached.plan(dst, src)
            assert fresh is not stale
            assert_plans_equal(fresh, oracle.plan(dst, src),
                               type(event).__name__)
            assert cached.plan_cache.invalidations == inval_before + 1
            assert cached.plan(dst, src) is fresh   # re-warmed
            stale = fresh
        # FailRegion(2) actually re-routed: the failed port grants nothing.
        port = 3                              # region 2 = slave port 3
        mask = np.asarray(dst) == port
        assert not np.asarray(stale.keep)[mask].any()
        assert cached.trace_counts["plan"] == 1

    OPS = [
        ("fail_r1", lambda sh: sh.fail_region(1)),
        ("fail_r2", lambda sh: sh.fail_region(2)),
        ("heal_r1", lambda sh: sh.heal_region(1)),
        ("heal_r2", lambda sh: sh.heal_region(2)),
        ("shrink_a", lambda sh: sh.shrink("a", 1)),
        ("grow_a", lambda sh: sh.grow("a", 1)),
    ]

    def check_epoch_bump_property(self, offer_seed, op_indices):
        """Randomized reconfiguration sequences (fail/heal/shrink/grow in
        any — possibly invalid — order): every successful post bumps the
        epoch and flushes the cache; a rejected post leaves both alone; the
        cached plan always equals the uncached oracle's."""
        shell = make_shell()
        shell.submit("a", [fp(2), fp(2)], app_id=0)
        cached = shell.fabric(plan_cache=True, capacity=8)
        oracle = shell.fabric(plan_cache=False, capacity=8)
        dst, src = self.offers(cached, shell, seed=offer_seed)
        ops = [self.OPS[i] for i in op_indices]

        warm = cached.plan(dst, src)
        for label, op in ops:
            epoch_before = shell.epoch
            inval_before = cached.plan_cache.invalidations
            try:
                op(shell)
            except Exception:
                # invalid under the current pool state (healing a healthy
                # region, shrinking past zero, ...): rejected before any
                # mutation, so the epoch and the warm entry must survive
                assert shell.epoch == epoch_before, label
                assert cached.plan(dst, src) is warm, label
                continue
            assert shell.epoch == epoch_before + 1, label
            plan = cached.plan(dst, src)
            assert plan is not warm, f"{label}: stale entry served"
            assert cached.plan_cache.invalidations == inval_before + 1
            assert_plans_equal(plan, oracle.plan(dst, src), label)
            assert cached.plan(dst, src) is plan
            warm = plan
        assert cached.trace_counts["plan"] == 1

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_event_sequences_numpy_sweep(self, seed):
        rng = np.random.default_rng(seed)
        self.check_epoch_bump_property(
            int(rng.integers(0, 2 ** 16)),
            rng.integers(0, len(self.OPS), 4).tolist())

    if HAVE_HYPOTHESIS:
        @given(st.integers(0, 2 ** 16),
               st.lists(st.integers(0, 5), min_size=1, max_size=4))
        @settings(max_examples=10, deadline=None)
        def test_hypothesis_random_event_sequences(self, offer_seed, ops):
            self.check_epoch_bump_property(offer_seed, ops)

    def test_registers_override_and_traced_offers_bypass(self):
        """The epoch key only speaks for the BOUND register file, so an
        explicit ``registers=`` override skips the cache entirely; so do
        traced offers (an enclosing jit plans with tracers)."""
        shell = make_shell()
        shell.submit("a", [fp(2)], app_id=0)
        fabric = shell.fabric(plan_cache=True, capacity=8)
        dst, src = self.offers(fabric, shell)
        other = CrossbarRegisters.create(fabric.n_ports, capacity=8)

        fabric.plan(dst, src, registers=other)
        fabric.plan(dst, src, registers=other)
        assert fabric.plan_cache.stats()["plan_cache_entries"] == 0

        counts = jax.jit(lambda d, s: fabric.plan(d, s).counts)
        np.testing.assert_array_equal(np.asarray(counts(dst, src)),
                                      np.asarray(counts(dst, src)))
        stats = fabric.plan_cache.stats()
        assert stats["plan_cache_hits"] == 0
        assert stats["plan_cache_misses"] == 0

    def test_account_fast_path_matches_uncached(self):
        shell = make_shell()
        shell.submit("a", [fp(2), fp(2)], app_id=0)
        cached = shell.fabric(plan_cache=True, capacity=8)
        plain = shell.fabric(plan_cache=False, capacity=8)
        dst, src = self.offers(cached, shell)
        for _ in range(3):                     # miss, then memoized replays
            cached.account(cached.plan(dst, src))
            plain.account(plain.plan(dst, src))
        np.testing.assert_array_equal(cached.port_traffic,
                                      plain.port_traffic)
        assert cached.offered_packets == plain.offered_packets
        assert cached.granted_packets == plain.granted_packets
        # reset_accounting starts a fresh window but keeps entries warm
        cached.reset_accounting()
        assert cached.offered_packets == 0
        assert cached.plan_cache.stats()["plan_cache_entries"] == 1
        before = cached.plan_cache.stats()["plan_cache_hits"]
        cached.plan(dst, src)
        assert cached.plan_cache.stats()["plan_cache_hits"] == before + 1


# ----------------------------------------------------------------------
# cached data plane == uncached data plane, sanitizer armed
# ----------------------------------------------------------------------
class TestCachedDataPlaneBitIdentity:
    @staticmethod
    def routable_dst(shell, T, rng):
        """Offers that ``debug="strict"`` sanctions under the LIVE register
        file: each real packet goes to a port the host may reach (allowed,
        not reset), round-robin so no port bursts past capacity, plus a few
        ``-1`` padding rows (the sanctioned sentinel)."""
        regs = shell.registers
        host = shell.state.host_port
        ports = np.where(np.asarray(regs.allowed)[host]
                         & ~np.asarray(regs.reset))[0]
        assert ports.size, "no routable port under the live register file"
        dst = np.asarray([ports[i % ports.size] for i in range(T)], np.int32)
        dst[rng.random(T) < 0.25] = -1
        return jnp.asarray(dst)

    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_transfer_dispatch_combine_under_strict_debug(self, backend):
        """debug="strict" re-validates the memoized plan on every cached
        replay; outputs must stay bit-identical to the uncached fabric,
        on the miss tick, on hit ticks, and across an epoch flush."""
        shell = make_shell()
        shell.submit("a", [fp(2), fp(2)], app_id=0)
        cached = shell.fabric(backend=backend, plan_cache=True,
                              debug="strict", capacity=8)
        plain = shell.fabric(backend=backend, plan_cache=False,
                             debug="strict", capacity=8)
        rng = np.random.default_rng(7)
        T = 8
        dst = self.routable_dst(shell, T, rng)
        src = jnp.full((T,), shell.state.host_port, jnp.int32)
        w = jnp.asarray(rng.standard_normal(T), jnp.float32)

        def check(tag):
            x = jnp.asarray(rng.standard_normal((T, 16)), jnp.float32)
            yc, pc = cached.transfer(x, dst, src, weights=w)
            yp, pp = plain.transfer(x, dst, src, weights=w)
            np.testing.assert_array_equal(np.asarray(yc), np.asarray(yp),
                                          err_msg=f"{tag} transfer")
            assert_plans_equal(pc, pp, f"{tag} transfer")
            sc, pc2 = cached.dispatch(x, dst, src)
            sp, pp2 = plain.dispatch(x, dst, src)
            np.testing.assert_array_equal(np.asarray(sc), np.asarray(sp),
                                          err_msg=f"{tag} dispatch")
            np.testing.assert_array_equal(
                np.asarray(cached.combine(sc, pc2, weights=w)),
                np.asarray(plain.combine(sp, pp2, weights=w)),
                err_msg=f"{tag} combine")

        check("miss")
        check("hit")
        shell.post(FailRegion(rid=1))          # epoch flush mid-stream
        dst = self.routable_dst(shell, T, rng)  # re-offer on live ports
        check("post-invalidation")
        shell.post(Grow(tenant="a", n_regions=2))
        dst = self.routable_dst(shell, T, rng)
        check("post-heal")
        stats = cached.plan_cache.stats()
        assert stats["plan_cache_hits"] > 0
        assert stats["plan_cache_invalidations"] == 2

    def test_cache_never_costs_a_retrace(self):
        """The zero-retrace contract holds with the cache on: hits, misses
        and epoch flushes all reuse one compiled program per entry point."""
        shell = make_shell()
        shell.submit("a", [fp(2)], app_id=0)
        fabric = shell.fabric(plan_cache=True, capacity=8)
        rng = np.random.default_rng(3)
        T = 8
        src = jnp.full((T,), shell.state.host_port, jnp.int32)
        w = jnp.ones((T,), jnp.float32)
        for round_ in range(3):
            dst = jnp.asarray(rng.integers(-1, fabric.n_ports, T), jnp.int32)
            x = jnp.asarray(rng.standard_normal((T, 4)), jnp.float32)
            for _ in range(2):                 # miss tick + hit tick
                slabs, plan = fabric.dispatch(x, dst, src)
                fabric.combine(slabs, plan, weights=w)
                fabric.transfer(x, dst, src, weights=w)
            shell.post(FailRegion(rid=0) if round_ % 2 == 0
                       else Grow(tenant="a"))
        counts = fabric.trace_counts
        for key, n in counts.items():
            assert n <= 1, f"{key} retraced: {counts}"
        # the first dispatch is the only miss-path trace (it warms the
        # cache, so transfer/combine immediately land on the cached
        # entry points), and every cached entry point compiled exactly once
        assert counts.get("dispatch", 0) == 1
        assert counts.get("dispatch_cached", 0) == 1
        assert counts.get("combine_cached", 0) == 1
        assert counts.get("transfer_cached", 0) == 1


# ----------------------------------------------------------------------
# sharded backend: the persisted CombineRoute (forced 4-device topology)
# ----------------------------------------------------------------------
def run_with_devices(code: str, n_devices: int = 4,
                     timeout: int = 600) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_sharded_combine_route_replay_is_bit_identical_on_4_devices():
    """``build_route`` once per plan, ``combine(..., route=...)`` every
    tick: the persisted-route combine must match the route-free combine
    bit-for-bit, including on fresh slab data replayed under the same
    plan (the steady-state decode shape), with drops zeroed either way."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.registers import CrossbarRegisters
from repro.fabric.backends import ShardedBackend


from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("r",))
regs = CrossbarRegisters.create(4, capacity=6)
be = ShardedBackend("r")
C = 6
T, D = 32, 8                                 # 8 local packets per shard
rng = np.random.default_rng(0)
dst = jnp.asarray(rng.integers(-1, 4, T), jnp.int32)
x0 = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
x1 = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
w = jnp.asarray(rng.standard_normal(T), jnp.float32)

def ticks(x0, x1, dst, w):
    plan = be.plan(dst, jnp.zeros_like(dst), regs)
    route = be.build_route(plan, C)          # once per register epoch
    y0 = be.dispatch(x0, plan, regs, C)
    y1 = be.dispatch(x1, plan, regs, C)      # same plan, next tick's data
    return (be.combine(y0, plan, w),
            be.combine(y0, plan, w, route=route),
            be.combine(y1, plan, w),
            be.combine(y1, plan, w, route=route),
            plan.keep)

f = jax.shard_map(ticks, mesh=mesh,
              in_specs=(P("r"), P("r"), P("r"), P("r")),
              out_specs=(P("r"),) * 5, check_vma=False)
a0, r0, a1, r1, keep = (np.asarray(v) for v in f(x0, x1, dst, w))
np.testing.assert_array_equal(a0, r0)
np.testing.assert_array_equal(a1, r1)
assert a0.any() and a1.any()
assert not np.array_equal(a0, a1)            # fresh data actually flowed
np.testing.assert_allclose(a0[~keep], 0.0)   # drops zero under both modes
print("ROUTE_OK")
"""
    res = run_with_devices(code)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ROUTE_OK" in res.stdout


# ----------------------------------------------------------------------
# adversarial epoch storms (ISSUE 9): hostile offers x rapid Shell.post
# ----------------------------------------------------------------------
class TestAdversarialEpochStorms:
    """A ``dest_sprayer`` driving rapid ``Shell.post`` storms never gets
    a stale cache hit: after every applied reconfiguration the cached
    plan for the standing hostile offer is a fresh entry that agrees with
    the uncached oracle bit-for-bit, every sprayed packet stays masked
    under the new register file, and the whole storm costs zero
    retraces."""

    def hostile_offer(self, shell, atk, rng):
        """One seam-generated spray, aimed at the live topology."""
        from repro.manager.adversary import AttackView

        t = shell.state.find_tenant("b")
        ports = t.placed_ports if t is not None else ()
        view = AttackView(
            tick=0, app_id=1, name="b", host_port=shell.state.host_port,
            my_ports=ports, n_ports=shell.state.n_ports, capacity=8,
            healthy_rids=tuple(r.rid for r in shell.state.regions
                               if r.healthy),
            utilization=shell.utilization())
        actions = atk.step(view, rng)
        dsts = (actions[0].dsts if actions
                else (shell.state.n_ports + 1,) * 8)   # evicted: wild spray
        dst = jnp.asarray(dsts, jnp.int32)
        src = jnp.full(dst.shape, ports[0] if ports else 1, jnp.int32)
        return dst, src

    def check_spray_storm(self, seed, op_indices):
        from repro.manager.adversary import DestSprayer

        shell = make_shell()
        shell.submit("a", [fp(2), fp(2)], app_id=0)
        shell.submit("b", [fp(2)], app_id=1)
        cached = shell.fabric(plan_cache=True, capacity=8)
        oracle = shell.fabric(plan_cache=False, capacity=8)
        rng = np.random.default_rng(seed)
        atk = DestSprayer(burst=8)
        ops = [TestFabricPlanCache.OPS[i] for i in op_indices]

        dst, src = self.hostile_offer(shell, atk, rng)
        warm = cached.plan(dst, src)
        assert cached.plan(dst, src) is warm
        for label, op in ops:
            epoch_before = shell.epoch
            try:
                op(shell)
            except Exception:
                # rejected post: epoch unchanged, warm entry must survive
                assert shell.epoch == epoch_before, label
                assert cached.plan(dst, src) is warm, label
                continue
            # the standing hostile offer re-plans fresh under the new epoch
            plan = cached.plan(dst, src)
            assert plan is not warm, f"{label}: stale entry served"
            assert_plans_equal(plan, oracle.plan(dst, src), label)
            # a new spray aimed at the reconfigured topology agrees too,
            # and every sprayed packet is masked (never its own port, the
            # host, or a same-tenant destination)
            dst, src = self.hostile_offer(shell, atk, rng)
            warm = cached.plan(dst, src)
            assert_plans_equal(warm, oracle.plan(dst, src), label)
            assert not np.asarray(warm.keep).any(), label
            assert cached.plan(dst, src) is warm, label
        assert cached.trace_counts["plan"] == 1

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_spray_storm_numpy_sweep(self, seed):
        rng = np.random.default_rng(seed)
        self.check_spray_storm(
            seed, rng.integers(0, len(TestFabricPlanCache.OPS), 5).tolist())

    if HAVE_HYPOTHESIS:
        @given(st.integers(0, 2 ** 16),
               st.lists(st.integers(0, 5), min_size=1, max_size=5))
        @settings(max_examples=10, deadline=None)
        def test_spray_storm_hypothesis(self, seed, ops):
            self.check_spray_storm(seed, ops)
