"""Mesh-sharded MoE expert parallelism through the sharded fabric backend.

The forced-topology tests subprocess into
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the repo
convention: the main pytest process keeps its single device) and pin the
ISSUE acceptance criteria:

- ``moe_apply(dispatch_impl="sharded")`` inside the model's shard_map
  matches the dense baseline under ample capacity and the
  reference-backend oracle (``moe_apply_sharded_reference``) bit-for-bit
  on plans/drops when capacity is exceeded;
- the register file stays a traced argument: one ``Grow`` and one
  ``FailRegion`` posted through a live ``Shell`` re-route the next step
  with **zero** retraces (``moe_fabric(...).trace_count`` flat);
- drop accounting (``dropped`` / ``counts`` / ``remote_packets`` /
  ``local_packets``) is identical between the sharded run and the oracle.

Single-device tests cover the host-side plumbing: per-axis traffic into
``Signals``, ``Fabric.account``/``account_stats``, the defrag policy's
remote-fraction gate, and the ``registers=`` traced-argument override.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
SRC = str(REPO / "src")


def run_with_devices(code: str, n_devices: int = 4,
                     timeout: int = 600) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_sharded_moe_matches_dense_and_oracle_on_4_devices():
    """8 experts on a 4-shard mesh (2 experts per shard): ample capacity
    matches the dense baseline; tight capacity matches the single-device
    reference oracle exactly, including every drop counter."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.models.common import init_params
from repro.models.config import MoEConfig
from repro.models.moe import (moe_defs, moe_apply, expert_capacity,
                              moe_apply_sharded_reference,
                              moe_forward_sharded)

moe = MoEConfig(n_experts=8, top_k=2, capacity_factor=4.0)
d, dff = 32, 64
params = init_params(moe_defs(d, dff, moe, "swiglu"),
                     jax.random.key(0), jnp.float32)
B, S = 8, 16
x = jax.random.normal(jax.random.key(1), (B, S, d))
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("expert",))

# ample capacity: the sharded path reproduces the dense formulation
cap = expert_capacity(B * S, moe)
yd, sd = moe_apply(params, x, moe, "swiglu", group_size=B * S)
assert int(sd["dropped"]) == 0
ys, ss = moe_forward_sharded(params, x, moe, "swiglu", mesh=mesh,
                             capacity=cap)
np.testing.assert_allclose(np.asarray(ys), np.asarray(yd), atol=1e-5)
np.testing.assert_allclose(float(ss["aux_loss"]), float(sd["aux_loss"]),
                           rtol=1e-5)
assert int(ss["remote_packets"]) + int(ss["local_packets"]) \
    == int(ss["granted_packets"]) == B * S * moe.top_k

# tight capacity: drops + plans match the reference-backend oracle
ys2, ss2 = moe_forward_sharded(params, x, moe, "swiglu", mesh=mesh,
                               capacity=16)
yr2, sr2 = moe_apply_sharded_reference(params, x, moe, "swiglu",
                                       n_shards=4, capacity=16)
assert int(ss2["dropped"]) == int(sr2["dropped"]) > 0
for key in ("counts", "granted_packets", "offered_packets",
            "remote_packets", "local_packets", "iso_dropped"):
    np.testing.assert_array_equal(np.asarray(ss2[key]),
                                  np.asarray(sr2[key]), err_msg=key)
np.testing.assert_allclose(np.asarray(ys2), np.asarray(yr2), atol=1e-5)

# expert_mask = isolation row: masked experts receive nothing
mask = jnp.asarray([True] * 6 + [False] * 2)
ym, sm = moe_forward_sharded(params, x, moe, "swiglu", mesh=mesh,
                             capacity=cap, expert_mask=mask)
assert int(np.asarray(sm["counts"])[6:].sum()) == 0

# a port space the axis cannot partition evenly is rejected up front
from repro.fabric import ShardedBackend
from repro.core.registers import CrossbarRegisters
try:
    moe6 = MoEConfig(n_experts=6, top_k=2)
    p6 = init_params(moe_defs(d, dff, moe6, "swiglu"),
                     jax.random.key(0), jnp.float32)
    moe_forward_sharded(p6, x, moe6, "swiglu", mesh=mesh)
    raise SystemExit("expected ValueError for 6 ports on 4 shards")
except ValueError as e:
    assert "divisible" in str(e), e
print("SHARDED_MOE_OK")
"""
    res = run_with_devices(code)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SHARDED_MOE_OK" in res.stdout


def test_sharded_moe_zero_retrace_across_shell_events_on_4_devices():
    """The acceptance pin: a jitted shard_map step taking the shell's
    register file as a traced argument survives Grow + FailRegion with
    ``fabric.trace_count`` flat, re-routes, and still matches the
    oracle."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.core.elastic import Region
from repro.core.module import ModuleFootprint
from repro.models.common import init_params
from repro.models.config import MoEConfig
from repro.models.moe import (moe_defs, moe_fabric, moe_forward_sharded,
                              moe_apply_sharded_reference)
from repro.shell import FailRegion, Grow, Shell, Submit

GB = 1 << 30
fp = lambda: ModuleFootprint(param_bytes=GB, flops_per_token=1e9,
                             activation_bytes_per_token=4096)
# 3 regions + host port = 4 crossbar ports == 4 experts, 1 per shard.
shell = Shell([Region(rid=i, n_chips=8, hbm_bytes=8 * GB)
               for i in range(3)])
shell.post(Submit(tenant="moe", footprints=(fp(), fp()), app_id=0))

moe = MoEConfig(n_experts=4, top_k=2, capacity_factor=2.0)
d = 16
params = init_params(moe_defs(d, 32, moe, "swiglu"),
                     jax.random.key(0), jnp.float32)
x = jax.random.normal(jax.random.key(1), (4, 8, d))
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("expert",))
CAP = 64

step = jax.jit(lambda p, regs, xx: moe_forward_sharded(
    p, xx, moe, "swiglu", mesh=mesh, registers=regs, capacity=CAP))
y0, s0 = step(params, shell.registers, x)
jax.block_until_ready(y0)
fabric = moe_fabric(4, CAP, "sharded", "expert")
t0 = fabric.trace_count
assert t0 > 0

epoch0 = shell.epoch
shell.post(Grow(tenant="moe", n_regions=3))
shell.post(FailRegion(rid=1))            # port 2 held in reset
assert shell.epoch == epoch0 + 2
y1, s1 = step(params, shell.registers, x)
jax.block_until_ready(y1)
assert fabric.trace_count == t0, fabric.trace_counts
assert not np.allclose(np.asarray(y0), np.asarray(y1)), \\
    "reconfiguration must re-route traffic"

# the failed expert port makes no grants; counts/drops match the oracle
assert int(np.asarray(s1["counts"])[2]) == 0
yr, sr = moe_apply_sharded_reference(params, x, moe, "swiglu",
                                     n_shards=4,
                                     registers=shell.registers,
                                     capacity=CAP)
np.testing.assert_allclose(np.asarray(y1), np.asarray(yr), atol=1e-5)
assert int(s1["dropped"]) == int(sr["dropped"]) > 0
assert int(s1["iso_dropped"]) == int(sr["iso_dropped"]) > 0
print("ZERO_RETRACE_OK")
"""
    res = run_with_devices(code)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ZERO_RETRACE_OK" in res.stdout


# ----------------------------------------------------------------------
# single-device plumbing
# ----------------------------------------------------------------------
def test_sharded_dispatch_requires_divisible_expert_block():
    import jax
    import jax.numpy as jnp
    from repro.models.common import init_params
    from repro.models.config import MoEConfig
    from repro.models.moe import moe_apply, moe_defs

    moe = MoEConfig(n_experts=8, top_k=2)
    params = init_params(moe_defs(16, 32, moe, "swiglu"),
                         jax.random.key(0), jnp.float32)
    bad = dict(params, w_in=params["w_in"][:3])     # 3 does not divide 8
    x = jnp.zeros((2, 8, 16))
    with pytest.raises(ValueError, match="divide"):
        moe_apply(bad, x, moe, "swiglu", dispatch_impl="sharded")


def test_fabric_account_and_stats_counters():
    import jax.numpy as jnp

    from repro.core.registers import CrossbarRegisters
    from repro.fabric import Fabric

    regs = CrossbarRegisters.create(4, capacity=8)
    fabric = Fabric(regs, backend="reference", capacity=8)
    dst = jnp.asarray([0, 1, 1, -1], jnp.int32)
    src = jnp.asarray([0, 0, 1, 0], jnp.int32)
    plan = fabric.plan(dst, src)
    fabric.account(plan, src_shard=0, n_shards=4)
    assert fabric.offered_packets == 3          # padding row not offered
    assert fabric.granted_packets == 3
    assert fabric.port_traffic.tolist() == [1, 2, 0, 0]
    # src_shard 0 owns port 0 only (4 ports / 4 shards)
    assert fabric.local_packets == 1
    assert fabric.remote_packets == 2

    fabric.account_stats({"counts": jnp.asarray([0, 0, 5, 0]),
                          "offered_packets": 6, "granted_packets": 5,
                          "remote_packets": 4, "local_packets": 1})
    assert fabric.offered_packets == 9
    assert fabric.granted_packets == 8
    assert fabric.remote_packets == 6
    assert fabric.port_traffic.tolist() == [1, 2, 5, 0]


def test_remote_traffic_reaches_signals_and_gates_defrag():
    from repro.core.elastic import Region
    from repro.core.module import ModuleFootprint
    from repro.manager import TrafficAwareDefrag, assemble_signals
    from repro.shell import Shell

    GB = 1 << 30
    shell = Shell([Region(rid=i, n_chips=8, hbm_bytes=8 * GB)
                   for i in range(2)])
    shell.submit("a", [ModuleFootprint(GB, 1e9, 4096)], app_id=0)
    shell.submit("b", [ModuleFootprint(GB, 1e9, 4096)], app_id=1)
    shell.release("a")          # region 0 free, b placed at rid 1 -> frag

    class ShardedTrafficProbe:
        name = "fabric"

        def __init__(self):
            self.remote = 0

        def sample(self):
            return {"remote_packets": self.remote, "local_packets": 10}

    probe = ShardedTrafficProbe()
    sig = assemble_signals(shell, [probe], tick=0)
    assert sig.remote_traffic == 0 and sig.local_traffic == 10
    assert sig.remote_fraction == 0.0
    assert sig.fragmentation > 0.0

    gated = TrafficAwareDefrag(min_remote_fraction=0.5)
    assert list(gated.decide(sig, shell.state)) == []       # all-local
    open_ = TrafficAwareDefrag()
    assert len(list(open_.decide(sig, shell.state))) == 1   # ungated moves

    probe.remote = 90           # next window: 90 remote vs 0 local delta
    sig2 = assemble_signals(shell, [probe], tick=1, prev=sig)
    assert sig2.remote_traffic_delta == 90
    assert sig2.local_traffic_delta == 0
    assert sig2.remote_fraction == 1.0
    events = list(gated.decide(sig2, shell.state))
    assert len(events) == 1 and type(events[0]).__name__ == "Migrate"


def test_registers_override_reroutes_without_retrace():
    """The traced-argument entry: passing ``registers=`` steers routing by
    value through the already-compiled program (what shard_map bodies rely
    on one level up)."""
    import jax.numpy as jnp

    from repro.core.registers import CrossbarRegisters, ErrorCode
    from repro.fabric import Fabric

    base = CrossbarRegisters.create(2, capacity=4)
    fabric = Fabric(base, backend="reference", capacity=4)
    dst = jnp.asarray([1, 1], jnp.int32)
    src = jnp.asarray([0, 0], jnp.int32)
    p0 = fabric.plan(dst, src)
    assert int(p0.keep.sum()) == 2
    blocked = base.with_isolation(src=0, allowed_dsts=[0])
    p1 = fabric.plan(dst, src, registers=blocked)
    assert int(p1.keep.sum()) == 0
    assert (np.asarray(p1.error) == ErrorCode.INVALID_DEST).all()
    assert fabric.trace_counts["plan"] == 1     # same compiled program
    # the bound file is untouched by the override
    assert int(fabric.plan(dst, src).keep.sum()) == 2
