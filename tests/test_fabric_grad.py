"""Differentiable scatter fabric: custom_vjp backward at gather cost.

Property coverage (numpy RNG sweeps, plus hypothesis when installed):

- grads of ``dispatch`` / ``combine`` bit-match BOTH autodiff through the
  dense one-hot formulations and the public ``*_bwd_ref`` oracles, on
  randomized register files (quotas, isolation, resets, capacities);
- dropped / masked packets receive an **exactly-zero** cotangent — by
  construction of the trash-row route, not by post-hoc masking;
- ``Fabric.transfer`` backprops on the reference and pallas backends and
  under ``kernel_mode="xla"``, including ``data_plane="kernel"``
  (regression: ``pallas_call`` has no transpose rule — ``jax.grad``
  through the kernel data plane used to raise);
- a plan-cache hit replays the **memoized** backward route: grads through
  the cached path are bit-identical to the cold path and the hit counter
  moves;
- the grad path is retrace-free across mid-training ``Shell.post``
  reconfigurations (the reconfigure-without-recompile claim extended to
  the backward pass);
- forced-4-device sharded transfer grads bit-match the reference backend
  (subprocess, shard_map over the all_to_all custom_vjp primitives);
- ``moe_apply`` grads through the fabric match the dense MoE baseline.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.arbiter import (combine, combine_addr, combine_at_bwd_ref,
                                combine_dense, dispatch, dispatch_at_bwd_ref,
                                dispatch_dense, flat_slot_addr,
                                wrr_dispatch_plan)
from repro.core.module import ModuleFootprint
from repro.core.registers import CrossbarRegisters
from repro.fabric import Fabric, PallasBackend
from repro.shell import FailRegion, Grow, Shell, Shrink, Submit

GB = 1 << 30
SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


def random_registers(rng, n, *, cap_max=20):
    return CrossbarRegisters(
        dest=jnp.arange(n, dtype=jnp.int32),
        allowed=jnp.asarray(rng.random((n, n)) > 0.25),
        quota=jnp.asarray(rng.integers(0, 6, (n, n)), jnp.int32),
        capacity=jnp.asarray(rng.integers(0, cap_max, (n,)), jnp.int32),
        reset=jnp.asarray(rng.random(n) > 0.85),
        error=jnp.zeros((n,), jnp.int32),
        version=jnp.zeros((), jnp.int32))


def random_plan(rng, T, n):
    dst = jnp.asarray(rng.integers(-1, n, T), jnp.int32)
    src = jnp.asarray(rng.integers(0, n, T), jnp.int32)
    return wrr_dispatch_plan(dst, src, random_registers(rng, n)), dst, src


def bit_equal(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=msg)


# ----------------------------------------------------------------------
# dispatch: scatter transposes to a gather over the same flat address
# ----------------------------------------------------------------------
class TestDispatchGrad:
    def check(self, seed, T, n, cap):
        rng = np.random.default_rng(seed)
        plan, _, _ = random_plan(rng, T, n)
        D = 8
        x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
        probe = jnp.asarray(rng.standard_normal((n, cap, D)), jnp.float32)

        d_x = jax.grad(lambda v: jnp.sum(dispatch(v, plan, n, cap) * probe))(x)
        d_dense = jax.grad(
            lambda v: jnp.sum(dispatch_dense(v, plan, n, cap) * probe))(x)
        bit_equal(d_x, d_dense, "scatter bwd vs dense-formulation autodiff")

        # the written backward rule == its dense one-hot oracle, bit for bit
        daddr = flat_slot_addr(plan, n, cap)
        _, vjp = jax.vjp(lambda v: dispatch(v, plan, n, cap), x)
        bit_equal(vjp(probe)[0], dispatch_at_bwd_ref(probe, daddr, n, cap),
                  "custom bwd vs dispatch_at_bwd_ref")

        # dropped packets (quota / capacity / reset / slab-overflow) get an
        # exactly-zero cotangent: they only ever read the zero trash row
        ok = np.asarray(plan.keep & (plan.slot < cap))
        assert not np.asarray(d_x)[~ok].any()
        # jit(grad(...)) lowers the same rule (residuals stay traceable)
        bit_equal(jax.jit(jax.grad(
            lambda v: jnp.sum(dispatch(v, plan, n, cap) * probe)))(x), d_x)

    def test_numpy_sweep(self):
        for seed in range(8):
            self.check(seed, T=40 + seed, n=2 + seed % 5, cap=1 + seed % 12)

    if HAVE_HYPOTHESIS:
        @given(st.integers(0, 2 ** 31 - 1), st.integers(1, 80),
               st.integers(2, 8), st.integers(1, 24))
        @settings(max_examples=25, deadline=None)
        def test_hypothesis_dispatch_grad_bit_equality(self, seed, T, n, cap):
            self.check(seed, T, n, cap)
    else:
        def test_hypothesis_dispatch_grad_bit_equality(self):
            pytest.importorskip("hypothesis")


# ----------------------------------------------------------------------
# combine: gather transposes to a scatter-add over the same route
# ----------------------------------------------------------------------
class TestCombineGrad:
    def check(self, seed, T, n, cap):
        rng = np.random.default_rng(seed)
        plan, _, _ = random_plan(rng, T, n)
        D = 8
        y = jnp.asarray(rng.standard_normal((n, cap, D)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(T), jnp.float32)
        probe = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)

        def loss(y, w):
            return jnp.sum(combine(y, plan, w) * probe)

        def loss_dense(y, w):
            return jnp.sum(combine_dense(y, plan, w) * probe)

        (d_y, d_w) = jax.grad(loss, argnums=(0, 1))(y, w)
        (dd_y, dd_w) = jax.grad(loss_dense, argnums=(0, 1))(y, w)
        bit_equal(d_y, dd_y, "gather bwd vs dense-formulation autodiff")
        # d_w is a row-dot reduction: same math, different f32 sum order
        # than the dense einsum — tight allclose, not bit.
        np.testing.assert_allclose(np.asarray(d_w), np.asarray(dd_w),
                                   rtol=1e-5, atol=1e-6)

        caddr, cmask = combine_addr(plan, n, cap)
        ref_y, ref_w = combine_at_bwd_ref(probe, y, caddr, cmask, w)
        bit_equal(d_y, ref_y, "custom bwd vs combine_at_bwd_ref")
        np.testing.assert_allclose(np.asarray(d_w), np.asarray(ref_w),
                                   rtol=1e-5, atol=1e-6)

        # masked packets: exactly-zero weight cotangent (trash-row route)
        assert not np.asarray(d_w)[~np.asarray(cmask)].any()

    def test_numpy_sweep(self):
        for seed in range(8):
            self.check(seed, T=40 + seed, n=2 + seed % 5, cap=1 + seed % 12)

    if HAVE_HYPOTHESIS:
        @given(st.integers(0, 2 ** 31 - 1), st.integers(1, 80),
               st.integers(2, 8), st.integers(1, 24))
        @settings(max_examples=25, deadline=None)
        def test_hypothesis_combine_grad_bit_equality(self, seed, T, n, cap):
            self.check(seed, T, n, cap)
    else:
        def test_hypothesis_combine_grad_bit_equality(self):
            pytest.importorskip("hypothesis")


# ----------------------------------------------------------------------
# Fabric.transfer: full round-trip backward across backends / modes
# ----------------------------------------------------------------------
def _transfer_grad(fabric, x, dst, src, w, probe):
    def loss(x, w):
        y, _ = fabric.transfer(x, dst, src, weights=w)
        return jnp.sum(y * probe)

    return jax.grad(loss, argnums=(0, 1))(x, w)


class TestTransferGrad:
    def setup_method(self, _):
        rng = np.random.default_rng(7)
        self.n, self.T, self.D, self.cap = 4, 32, 8, 8
        self.regs = CrossbarRegisters.create(self.n, capacity=self.cap)
        self.x = jnp.asarray(rng.standard_normal((self.T, self.D)),
                             jnp.float32)
        self.dst = jnp.asarray(rng.integers(0, self.n, self.T), jnp.int32)
        self.src = jnp.asarray(rng.integers(0, self.n, self.T), jnp.int32)
        self.w = jnp.asarray(rng.standard_normal(self.T), jnp.float32)
        self.probe = jnp.asarray(rng.standard_normal((self.T, self.D)),
                                 jnp.float32)

    def _fab(self, **kw):
        return Fabric(self.regs, capacity=self.cap, **kw)

    def grads(self, **kw):
        return _transfer_grad(self._fab(**kw), self.x, self.dst, self.src,
                              self.w, self.probe)

    def test_pallas_and_xla_mode_match_reference(self):
        ref_x, ref_w = self.grads(backend="reference")
        for kw in (dict(backend="pallas"),
                   dict(backend="pallas", kernel_mode="xla"),
                   dict(backend="reference", kernel_mode="xla")):
            d_x, d_w = self.grads(**kw)
            bit_equal(d_x, ref_x, f"d_x {kw}")
            np.testing.assert_allclose(np.asarray(d_w), np.asarray(ref_w),
                                       rtol=1e-5, atol=1e-6)

    def test_kernel_data_plane_grad_regression(self):
        """``pallas_call`` has no transpose rule; before the custom VJP,
        jax.grad through ``data_plane="kernel"`` raised.  Now the kernel
        forward carries an XLA address-routed backward and matches the
        shared-scatter path bit for bit."""
        ref_x, ref_w = self.grads(backend="reference")
        backend = PallasBackend(data_plane="kernel", interpret=True)
        d_x, d_w = _transfer_grad(self._fab(backend=backend), self.x,
                                  self.dst, self.src, self.w, self.probe)
        bit_equal(d_x, ref_x, "kernel data-plane d_x")
        np.testing.assert_allclose(np.asarray(d_w), np.asarray(ref_w),
                                   rtol=1e-5, atol=1e-6)

    def test_plan_cache_hit_replays_memoized_backward_route(self):
        """Steady state: the epoch-keyed cache serves ``daddr``/``caddr``
        to the forward AND the custom backward — a cache hit must not
        change a single gradient bit, and the backward must not re-plan."""
        cold_x, cold_w = self.grads(backend="reference")

        fab = self._fab(backend="reference", plan_cache=True)
        fab.transfer(self.x, self.dst, self.src, weights=self.w)  # warm
        assert fab.plan_cache.misses == 1 and fab.plan_cache.hits == 0
        hot_x, hot_w = _transfer_grad(fab, self.x, self.dst, self.src,
                                      self.w, self.probe)
        assert fab.plan_cache.hits >= 1, "grad path bypassed the cache"
        bit_equal(hot_x, cold_x, "cached-route d_x")
        bit_equal(hot_w, cold_w, "cached-route d_w")


class TestShellBoundGrad:
    def test_grad_path_is_retrace_free_across_shell_post(self):
        """Mid-training reconfiguration: ``Shell.post`` rewrites registers
        between optimizer steps; the compiled grad path must re-route with
        zero retraces (registers stay traced operands, the custom VJP
        closes over no concrete plan)."""
        def fp(gb):
            return ModuleFootprint(param_bytes=gb * GB,
                                   flops_per_token=1e9,
                                   activation_bytes_per_token=4096)

        from repro.core.elastic import Region
        shell = Shell([Region(rid=i, n_chips=16, hbm_bytes=16 * GB)
                       for i in range(4)])
        shell.submit("a", [fp(4), fp(4)], app_id=0)
        fabric = shell.fabric(backend="reference")
        n = fabric.n_ports
        T = 16
        rng = np.random.default_rng(3)
        dst = jnp.asarray(np.arange(T) % n, jnp.int32)
        src = jnp.full((T,), shell.state.host_port, jnp.int32)
        x = jnp.asarray(rng.standard_normal((T, 8)), jnp.float32)
        probe = jnp.asarray(rng.standard_normal((T, 8)), jnp.float32)

        def loss(x):
            y, _ = fabric.transfer(x, dst, src)
            return jnp.sum(y * probe)

        g0 = jax.grad(loss)(x)
        t0 = fabric.trace_count
        assert t0 == 1, fabric.trace_counts

        shell.post(Submit(tenant="b", footprints=(fp(2),), app_id=1))
        shell.post(Shrink(tenant="a", n_regions=1))
        shell.post(Grow(tenant="a", n_regions=2))
        shell.post(FailRegion(rid=2))

        g1 = jax.grad(loss)(x)
        assert fabric.trace_count == t0, \
            f"reconfiguration retraced the grad path: {fabric.trace_counts}"
        # port 3's region failed: its packets now carry zero cotangent
        failed = np.asarray(dst) == 3
        assert np.asarray(g0)[failed].any()
        assert not np.asarray(g1)[failed].any()


# ----------------------------------------------------------------------
# the MoE consumer: full layer backward through the crossbar
# ----------------------------------------------------------------------
class TestMoEGrad:
    def setup_method(self, _):
        from repro.models.common import init_params
        from repro.models.config import MoEConfig
        from repro.models.moe import moe_defs
        self.moe = MoEConfig(n_experts=4, top_k=2, capacity_factor=1.0)
        defs = moe_defs(32, 64, self.moe, "swiglu")
        self.params = init_params(defs, jax.random.key(0), jnp.float32)
        self.x = jax.random.normal(jax.random.key(1), (2, 32, 32))

    def _grad(self, impl, kernel_mode=None):
        from repro.models.moe import moe_apply

        def loss(params):
            kw = {"kernel_mode": kernel_mode} if kernel_mode else {}
            y, stats = moe_apply(params, self.x, self.moe, "swiglu",
                                 group_size=64, dispatch_impl=impl, **kw)
            return jnp.sum(y * y) + stats["aux_loss"]

        return jax.grad(loss)(self.params)

    @pytest.mark.parametrize("impl,mode", [
        ("reference", None), ("pallas", None),
        ("pallas", "xla"), ("pallas", "pallas_interpret"), ("gather", None)])
    def test_fabric_moe_grad_matches_dense_baseline(self, impl, mode):
        dense = self._grad("dense")
        got = self._grad(impl, mode)
        for k in dense:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(dense[k]),
                rtol=2e-4, atol=2e-5, err_msg=f"{impl}/{mode}/{k}")

    def test_jit_grad_is_retrace_stable(self):
        """The fabric trace counter must not move between repeated
        jit(grad) executions — the training-loop contract."""
        from repro.models.moe import expert_capacity, moe_apply, moe_fabric

        def loss(params):
            y, stats = moe_apply(params, self.x, self.moe, "swiglu",
                                 group_size=64, dispatch_impl="reference")
            return jnp.sum(y * y) + stats["aux_loss"]

        step = jax.jit(jax.grad(loss))
        step(self.params)
        fab = moe_fabric(self.moe.n_experts, expert_capacity(64, self.moe),
                         "reference")
        t0 = fab.trace_count
        step(self.params)
        assert fab.trace_count == t0, fab.trace_counts


# ----------------------------------------------------------------------
# sharded backend: all_to_all custom VJPs on a forced 4-device mesh
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_sharded_grad_matches_reference_on_forced_mesh():
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.registers import CrossbarRegisters
from repro.fabric import Fabric

mesh = Mesh(np.array(jax.devices()), ("x",))
regs = CrossbarRegisters.create(4, capacity=4)
fab = Fabric(regs, backend="sharded", axis_name="x", capacity=4)
ref = Fabric(regs, backend="reference", capacity=4)

rng = np.random.default_rng(11)
x = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
dst = jnp.asarray([0, 1, 2, 3] * 2)
src = jnp.repeat(jnp.arange(4, dtype=jnp.int32), 2)
w = jnp.asarray(rng.standard_normal(8), jnp.float32)
probe = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)

def body(r, x, w, d, s):
    y, _ = fab.transfer(x, d, s, weights=w, registers=r)
    return y

kw = dict(mesh=mesh, in_specs=(P(), P("x"), P("x"), P("x"), P("x")),
          out_specs=P("x"))
run = jax.shard_map(body, check_vma=False, **kw)

def loss(x, w, r=regs):
    return jnp.sum(run(r, x, w, dst, src) * probe)

d_x, d_w = jax.grad(loss, argnums=(0, 1))(x, w)

def loss_ref(x, w):
    y, _ = ref.transfer(x, dst, src, weights=w)
    return jnp.sum(y * probe)

r_x, r_w = jax.grad(loss_ref, argnums=(0, 1))(x, w)
assert np.array_equal(np.asarray(d_x), np.asarray(r_x)), "sharded d_x"
np.testing.assert_allclose(np.asarray(d_w), np.asarray(r_w),
                           rtol=1e-5, atol=1e-6)

# masked traffic: isolate source 0 to port 0 only -> its cross-port
# packets carry exactly-zero cotangent
iso = regs.with_isolation(0, [0])
d_x2 = jax.grad(lambda x: loss(x, w, iso))(x)
r_x2 = jax.grad(lambda x: jnp.sum(
    ref.transfer(x, dst, src, weights=w, registers=iso)[0] * probe))(x)
assert np.array_equal(np.asarray(d_x2), np.asarray(r_x2))
dropped = (np.asarray(src) == 0) & (np.asarray(dst) != 0)
assert dropped.any() and not np.asarray(d_x2)[dropped].any()
print("SHARDED-GRAD-OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "SHARDED-GRAD-OK" in proc.stdout
