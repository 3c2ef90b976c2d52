"""Distributed paths under a forced multi-device CPU topology.

jax pins the device count at first init, so these tests launch pytest/python
subprocesses with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
The main test process keeps its single device (per the repo convention:
only the dry-run sees fake fleets).
"""
import os
import subprocess
import sys
from pathlib import Path

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


def test_sharded_crossbar_tests_pass_on_4_devices():
    """Re-runs the shard_map crossbar tests that skip under 1 device."""
    res = run_with_devices(
        "import pytest, sys;"
        "sys.exit(pytest.main(['-q', '-k', 'Sharded', "
        f"'{REPO / 'tests' / 'test_crossbar_tpu.py'}']))")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "2 passed" in res.stdout, res.stdout


def test_sharded_fabric_backend_plan_equivalent_on_4_devices():
    """The acceptance property, third backend: the all_to_all sharded
    fabric produces the dense oracle's DispatchPlan (keep/slot/error/
    counts) on randomized registers, and its transfer round-trips."""
    code = """
import functools, numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.registers import CrossbarRegisters
from repro.fabric import Fabric

n, Tloc, D, cap = 4, 12, 8, 16
from repro.launch.mesh import make_mesh
mesh = make_mesh((n,), ("region",))
for seed in range(4):
    rng = np.random.default_rng(seed)
    regs = CrossbarRegisters(
        dest=jnp.arange(n, dtype=jnp.int32),
        allowed=jnp.asarray(rng.random((n, n)) > 0.25),
        quota=jnp.asarray(rng.integers(0, 5, (n, n)), jnp.int32),
        capacity=jnp.asarray(rng.integers(2, 14, (n,)), jnp.int32),
        reset=jnp.asarray(rng.random(n) > 0.85),
        error=jnp.zeros((n,), jnp.int32),
        version=jnp.zeros((), jnp.int32))
    dst = jnp.asarray(rng.integers(-1, n, n * Tloc), jnp.int32)
    src = jnp.asarray(np.repeat(np.arange(n), Tloc), jnp.int32)
    x = jnp.asarray(rng.standard_normal((n * Tloc, D)), jnp.float32)
    fs = Fabric(regs, backend="sharded", capacity=cap, axis_name="region")
    fr = Fabric(regs, backend="reference", capacity=cap)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P("region"), P("region"), P("region")),
                       out_specs=(P("region"), P("region"), P("region"),
                                  P("region"), P(), P()))
    def run(xs, ds, ss):
        y, plan = fs.transfer(xs, ds, ss, apply_fn=lambda slab: slab * 2.0)
        return y, plan.keep, plan.slot, plan.error, plan.counts, plan.drops

    y, keep, slot, err, counts, drops = run(x, dst, src)
    oracle = fr.plan(dst, src)
    yr, _ = fr.transfer(x, dst, src, apply_fn=lambda s: s * 2.0)
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(oracle.keep))
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(oracle.slot))
    np.testing.assert_array_equal(np.asarray(err), np.asarray(oracle.error))
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(oracle.counts))
    np.testing.assert_array_equal(np.asarray(drops), np.asarray(oracle.drops))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5)
print("SHARDED_FABRIC_OK")
"""
    res = run_with_devices(code)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SHARDED_FABRIC_OK" in res.stdout


def test_train_step_lowers_on_4_device_mesh():
    """build_step lowers + compiles on a (2 data x 2 model) mesh; the
    gradient all-reduce and TP collectives must partition cleanly."""
    code = """
import jax, jax.numpy as jnp
import dataclasses
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_step, lower_step
from repro.models.config import ShapeConfig

mesh = make_mesh((2, 2), ("data", "model"))
cfg = get_config("tinyllama_1_1b", smoke=True)
shape = ShapeConfig("tiny_train", 64, 4, "train")
bundle = build_step(cfg, shape, mesh, multi_pod=False)
lowered = lower_step(bundle, mesh)
compiled = lowered.compile()
text = compiled.as_text()
assert "all-reduce" in text, "expected DP gradient all-reduce"
ca = compiled.cost_analysis()
print("LOWER_OK", ca["flops"] > 0)
"""
    res = run_with_devices(code)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "LOWER_OK True" in res.stdout


def test_moe_train_step_lowers_with_expert_parallel_collectives():
    code = """
import jax
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_step, lower_step
from repro.models.config import ShapeConfig

mesh = make_mesh((2, 2), ("data", "model"))
cfg = get_config("mixtral_8x7b", smoke=True)
shape = ShapeConfig("tiny_train", 64, 4, "train")
bundle = build_step(cfg, shape, mesh, multi_pod=False)
compiled = lower_step(bundle, mesh).compile()
print("LOWER_OK")
"""
    res = run_with_devices(code)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "LOWER_OK" in res.stdout


def test_decode_step_lowers_and_runs_on_4_devices():
    """End-to-end numeric decode on a sharded mesh (not just lowering)."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.lm import build_model

mesh = make_mesh((2, 2), ("data", "model"))
cfg = get_config("granite_3_2b", smoke=True)
model = build_model(cfg)
params = model.init(jax.random.key(0))
state = model.init_decode_state(4, 32)
batch = {"tokens": jnp.zeros((4, 1), jnp.int32)}
with jax.set_mesh(mesh):
    logits, state2 = jax.jit(model.decode_step)(params, state, batch)
assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
assert int(state2.pos) == 1
print("DECODE_OK")
"""
    res = run_with_devices(code)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "DECODE_OK" in res.stdout


def test_data_pipeline_shards_partition_global_batch():
    """Shard feeds are disjoint and cover the global batch exactly."""
    code = """
import numpy as np
from repro.data.pipeline import synthetic_batch

full = synthetic_batch(7, 3, 0, 1, 16, 32, 1000)
parts = [synthetic_batch(7, 3, s, 4, 16, 32, 1000) for s in range(4)]
stacked = np.concatenate([p["tokens"] for p in parts], axis=0)
np.testing.assert_array_equal(stacked, full["tokens"])
print("SHARDS_OK")
"""
    res = run_with_devices(code, n_devices=1)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SHARDS_OK" in res.stdout
