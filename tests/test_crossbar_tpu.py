"""TPU-path crossbar: local exchange/combine, register-driven reconfig, and
the shard_map all-to-all path on a multi-device CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


from repro.core.arbiter import combine, dispatch, wrr_dispatch_plan
from repro.core.crossbar import (CrossbarInterconnect, combine_local,
                                 exchange_local, pairwise_dispatch_plan)
from repro.core.registers import CrossbarRegisters, ErrorCode


def regs4(capacity=32):
    return CrossbarRegisters.create(4, capacity=capacity)


class TestLocalExchange:
    def test_roundtrip_preserves_granted_packets(self):
        T, D = 64, 32
        ks = jax.random.split(jax.random.key(0), 2)
        x = jax.random.normal(ks[0], (T, D))
        dst = jax.random.randint(ks[1], (T,), 0, 4)
        src = jnp.zeros((T,), jnp.int32)
        slabs, plan = exchange_local(x, dst, src, regs4(), capacity=64)
        back = combine_local(slabs, plan)
        np.testing.assert_allclose(
            np.asarray(back),
            np.asarray(x * plan.keep[:, None].astype(x.dtype)), atol=1e-6)

    def test_slab_rows_hold_routed_packets(self):
        x = jnp.eye(8, dtype=jnp.float32)           # 8 distinguishable packets
        dst = jnp.asarray([0, 0, 1, 1, 2, 2, 3, 3])
        src = jnp.zeros((8,), jnp.int32)
        slabs, plan = exchange_local(x, dst, src, regs4(), capacity=4)
        slabs = np.asarray(slabs)
        for t in range(8):
            row = slabs[t // 2, t % 2]
            assert row[t] == 1.0 and row.sum() == 1.0

    def test_reconfigure_changes_routing_without_recompile(self):
        """The ERM path: same jitted fn, new register values re-route."""
        T, D = 32, 16
        x = jnp.ones((T, D))
        dst = jnp.full((T,), 2, jnp.int32)
        src = jnp.zeros((T,), jnp.int32)

        @jax.jit
        def route(x, dst, src, regs):
            plan = wrr_dispatch_plan(dst, src, regs)
            return dispatch(x, plan, 4, 32), plan.drops

        xbar = CrossbarInterconnect(regs=regs4(), capacity=32)
        slabs1, drops1 = route(x, dst, src, xbar.regs)
        assert float(slabs1[2].sum()) > 0

        xbar2 = xbar.reconfigure(
            allowed=xbar.regs.allowed.at[0, 2].set(False))
        slabs2, drops2 = route(x, dst, src, xbar2.regs)   # no retrace needed
        assert float(slabs2[2].sum()) == 0
        assert int(drops2[ErrorCode.INVALID_DEST]) == T
        assert int(xbar2.regs.version) == int(xbar.regs.version) + 1


class TestShardedExchange:
    """all_to_all crossbar under shard_map (needs >1 local device)."""

    @pytest.fixture(scope="class")
    def mesh(self):
        if jax.device_count() < 4:
            pytest.skip("needs 4 local devices (run under "
                        "XLA_FLAGS=--xla_force_host_platform_device_count)")
        from repro.launch.mesh import make_mesh
        return make_mesh((4,), ("region",))

    def test_exchange_sharded_routes_across_regions(self, mesh):
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from repro.core.crossbar import combine_sharded, exchange_sharded

        n, Tloc, D, cap = 4, 8, 16, 8
        regs = CrossbarRegisters.create(n, capacity=cap)
        # Region r sends all its packets to region (r+1) % n.
        x = jnp.arange(n * Tloc * D, dtype=jnp.float32).reshape(n * Tloc, D)
        dst_global = (jnp.repeat(jnp.arange(n), Tloc) + 1) % n

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P("region"), P("region")),
                 out_specs=(P("region"), P("region")))
        def run(xs, ds):
            recv, mask, keep, slot = exchange_sharded(
                xs, ds, regs, cap, "region")
            y = recv * 2.0                                 # "module compute"
            out = combine_sharded(y, ds, keep, slot,
                                  jnp.ones_like(ds, jnp.float32), cap,
                                  "region")
            return out, keep[None].astype(jnp.int32) * 0 + keep.astype(jnp.int32)[None]

        out, keep = run(x, dst_global)
        # Every packet was granted (capacity 8 == Tloc) and came back 2x.
        assert np.asarray(keep).all()
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2.0,
                                   atol=1e-5)

    def test_isolation_blocks_cross_tenant_regions(self, mesh):
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from repro.core.crossbar import exchange_sharded

        n, Tloc, D, cap = 4, 4, 8, 8
        allowed = jnp.zeros((n, n), bool)
        allowed = allowed.at[0, 1].set(True).at[1, 0].set(True)  # tenant A
        allowed = allowed.at[2, 3].set(True).at[3, 2].set(True)  # tenant B
        regs = CrossbarRegisters.create(n, capacity=cap).write(allowed=allowed)
        x = jnp.ones((n * Tloc, D))
        # Region 0 tries to reach region 3 (cross-tenant): must be dropped.
        dst = jnp.where(jnp.arange(n * Tloc) < Tloc, 3,
                        (jnp.repeat(jnp.arange(n), Tloc) + 1) % n)

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P("region"), P("region")),
                 out_specs=P("region"))
        def run(xs, ds):
            _, _, keep, _ = exchange_sharded(xs, ds, regs, cap, "region")
            return keep.astype(jnp.int32)

        keep = np.asarray(run(x, dst))
        assert not keep[:Tloc].any()          # region 0 -> 3 blocked
        assert keep[2 * Tloc:3 * Tloc].all()  # region 2 -> 3 allowed


class TestQuotaSemantics:
    def test_pairwise_quota_is_per_source(self):
        # quota[dst=0, src=1] = 2 packages; all other pairs unlimited.
        regs = regs4().write(
            quota=jnp.zeros((4, 4), jnp.int32).at[0, 1].set(2))
        dst = jnp.zeros((6,), jnp.int32)
        keep, slot, err = pairwise_dispatch_plan(dst, jnp.int32(1), regs,
                                                 capacity=32)
        assert int(keep.sum()) == 2
        assert int((err == ErrorCode.GRANT_TIMEOUT).sum()) == 4

    def test_moe_layer_enforces_capacity_and_isolation(self):
        from repro.models.config import MoEConfig
        from repro.models.moe import moe_apply, moe_defs
        from repro.models.common import init_params

        moe = MoEConfig(n_experts=4, top_k=2, capacity_factor=1.0)
        defs = moe_defs(32, 64, moe, "swiglu")
        params = init_params(defs, jax.random.key(0), jnp.float32)
        x = jax.random.normal(jax.random.key(1), (2, 32, 32))
        mask = jnp.asarray([True, True, True, False])
        y, stats = moe_apply(params, x, moe, "swiglu", group_size=64,
                             expert_mask=mask)
        assert y.shape == x.shape
        assert not bool(jnp.isnan(y).any())
        assert int(stats["iso_dropped"]) == 0     # masked experts get no routes
        assert float(stats["aux_loss"]) > 0
