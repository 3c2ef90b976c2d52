"""The checkify sanitizer behind ``Fabric(debug=...)`` / REPRO_FABRIC_DEBUG.

ISSUE 6 acceptance criteria, negative path first:

- a tenant spraying invalid destinations and an over-capacity burst
  *raise* under ``Fabric(debug=True)`` on all three backends;
- the same traffic in normal mode is provably masked: plans, drop
  accounting and outputs are bit-identical to the debug-off build (and to
  the dense oracles), and dropped packets carry their Table III error
  codes instead of exceptions;
- ``debug="sanitize"`` (the REPRO_FABRIC_DEBUG=1 level) never raises on
  hostile traffic — only on data-plane bugs and NaN — so exporting the
  env var over the whole test suite stays green;
- in-trace callers opt in explicitly and functionalize the checks
  themselves (``checkify.checkify`` around the outer jit; ``shard_map``
  bodies with ``check_vma=False``).
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import checkify

from repro.core import arbiter
from repro.core.registers import CrossbarRegisters
from repro.fabric import DEBUG_ENV_VAR, Fabric

REPO = Path(__file__).resolve().parent.parent
SRC = str(REPO / "src")

N, CAP, D = 4, 4, 8
BACKENDS = ["reference", "pallas"]


def _regs():
    return CrossbarRegisters.create(N, capacity=CAP)


def _traffic():
    x = jnp.arange(6 * D, dtype=jnp.float32).reshape(6, D)
    dst = jnp.asarray([0, 1, 2, 3, 0, 1])
    src = jnp.zeros(6, jnp.int32)
    return x, dst, src


@pytest.mark.parametrize("backend", BACKENDS)
def test_spray_raises_under_strict_debug(backend):
    fab = Fabric(_regs(), backend=backend, capacity=CAP, debug=True)
    x, dst, src = _traffic()
    spray = dst.at[2].set(17)                     # out-of-range destination
    with pytest.raises(checkify.JaxRuntimeError,
                       match="invalid destination"):
        fab.plan(spray, src)
    with pytest.raises(checkify.JaxRuntimeError,
                       match="invalid destination"):
        fab.transfer(x, spray, src)


@pytest.mark.parametrize("backend", BACKENDS)
def test_isolation_spray_raises_under_strict_debug(backend):
    regs = _regs().with_isolation(0, [0, 1])      # src 0 may not reach 2/3
    fab = Fabric(regs, backend=backend, capacity=CAP, debug=True)
    x, dst, src = _traffic()                      # dst includes 2 and 3
    with pytest.raises(checkify.JaxRuntimeError,
                       match="invalid destination"):
        fab.plan(dst, src)


@pytest.mark.parametrize("backend", BACKENDS)
def test_burst_raises_under_strict_debug(backend):
    fab = Fabric(_regs(), backend=backend, capacity=CAP, debug=True)
    burst = jnp.zeros(3 * CAP, jnp.int32)         # 12 packets at port 0
    src = jnp.zeros(3 * CAP, jnp.int32)
    with pytest.raises(checkify.JaxRuntimeError,
                       match="over-capacity burst"):
        fab.plan(burst, src)


@pytest.mark.parametrize("backend", BACKENDS)
def test_clean_traffic_passes_and_is_bit_identical(backend):
    x, dst, src = _traffic()
    plain = Fabric(_regs(), backend=backend, capacity=CAP)
    dbg = Fabric(_regs(), backend=backend, capacity=CAP, debug=True)
    y0, p0 = plain.transfer(x, dst, src)
    y1, p1 = dbg.transfer(x, dst, src)            # must not raise
    assert np.array_equal(np.asarray(y0), np.asarray(y1))
    for field in ("keep", "slot", "error", "counts", "drops"):
        assert np.array_equal(np.asarray(getattr(p0, field)),
                              np.asarray(getattr(p1, field))), field


@pytest.mark.parametrize("backend", BACKENDS)
def test_sanitize_masks_hostile_traffic_like_normal_mode(backend):
    """The sanitize level is the provably-masked path: sprays and bursts
    drop with their error codes, bit-identical to debug-off and to the
    dense oracle — no exception."""
    x, dst, src = _traffic()
    spray = dst.at[2].set(17)
    plain = Fabric(_regs(), backend=backend, capacity=CAP)
    san = Fabric(_regs(), backend=backend, capacity=CAP, debug="sanitize")
    for hostile in (spray, jnp.zeros(3 * CAP, jnp.int32)):
        srcs = jnp.zeros(hostile.shape, jnp.int32)
        xs = jnp.ones((hostile.shape[0], D), jnp.float32)
        p0 = plain.plan(hostile, srcs)
        p1 = san.plan(hostile, srcs)
        for field in ("keep", "slot", "error", "counts", "drops"):
            assert np.array_equal(np.asarray(getattr(p0, field)),
                                  np.asarray(getattr(p1, field))), field
        slabs0, _ = plain.dispatch(xs, hostile, srcs)
        dense = arbiter.dispatch_dense(xs, p0, N, CAP)
        assert np.array_equal(np.asarray(slabs0), np.asarray(dense))
        assert int(p1.drops.sum()) == hostile.shape[0]  # every row accounted


@pytest.mark.parametrize("backend", BACKENDS)
def test_nan_slab_raises_at_both_levels(backend):
    x, dst, src = _traffic()
    xn = x.at[0, 0].set(jnp.nan)
    for level in ("sanitize", "strict"):
        fab = Fabric(_regs(), backend=backend, capacity=CAP, debug=level)
        with pytest.raises(checkify.JaxRuntimeError, match="NaN"):
            fab.dispatch(xn, dst, src)


def test_combine_smaller_slab_raises():
    """A slab smaller than what the plan granted into is a silent drop in
    normal mode; the sanitizer surfaces it."""
    x, dst, src = _traffic()
    fab = Fabric(_regs(), backend="reference", capacity=CAP, debug=True)
    # explicit debug=False: under REPRO_FABRIC_DEBUG=1 (the CI debug
    # shard) a default fabric runs sanitize checks, and the truncated
    # slab below violates a sanitize-level invariant by design.
    plain = Fabric(_regs(), backend="reference", capacity=CAP, debug=False)
    slabs, plan = plain.dispatch(x, dst, src)
    small = slabs[:, :1]                          # C=1 < granted slot 1
    with pytest.raises(checkify.JaxRuntimeError, match="combine"):
        fab.combine(small, plan)
    # normal mode: masked, and bit-identical to the dense oracle
    w = jnp.ones(dst.shape, x.dtype)
    y = plain.combine(small, plan, w)
    y_dense = arbiter.combine_dense(small, plan, w)
    assert np.array_equal(np.asarray(y), np.asarray(y_dense))


def test_env_hook_resolves_to_sanitize(monkeypatch):
    monkeypatch.setenv(DEBUG_ENV_VAR, "1")
    fab = Fabric(_regs(), backend="reference", capacity=CAP)
    assert fab.debug == "sanitize" and not fab._debug_explicit
    x, dst, src = _traffic()
    spray = dst.at[2].set(17)
    p = fab.plan(spray, src)                      # masked, not raised
    assert int(p.drops[1]) == 1
    with pytest.raises(checkify.JaxRuntimeError, match="NaN"):
        fab.dispatch(x.at[0, 0].set(jnp.nan), dst, src)


def test_env_hook_strict(monkeypatch):
    monkeypatch.setenv(DEBUG_ENV_VAR, "strict")
    fab = Fabric(_regs(), backend="reference", capacity=CAP)
    assert fab.debug == "strict"
    _, dst, src = _traffic()
    with pytest.raises(checkify.JaxRuntimeError,
                       match="invalid destination"):
        fab.plan(dst.at[2].set(17), src)


def test_env_hook_never_touches_in_trace_programs(monkeypatch):
    """Env-sourced debug must not inject bare checks into programs that
    did not opt in — an outer jit with no checkify wrapper stays valid."""
    monkeypatch.setenv(DEBUG_ENV_VAR, "1")
    fab = Fabric(_regs(), backend="reference", capacity=CAP)
    _, dst, src = _traffic()

    @jax.jit
    def prog(regs, d, s):
        return fab.plan(d, s, registers=regs).drops

    drops = prog(_regs(), dst.at[2].set(17), src)
    assert int(np.asarray(drops)[1]) == 1


def test_explicit_debug_off_ignores_env(monkeypatch):
    monkeypatch.setenv(DEBUG_ENV_VAR, "strict")
    fab = Fabric(_regs(), backend="reference", capacity=CAP, debug=False)
    assert fab.debug is False
    _, dst, src = _traffic()
    fab.plan(dst.at[2].set(17), src)              # no raise


def test_in_trace_explicit_debug_with_caller_checkify():
    fab = Fabric(_regs(), backend="reference", capacity=CAP, debug=True)
    x, dst, src = _traffic()

    def prog(regs, xx, d, s):
        y, plan = fab.transfer(xx, d, s, registers=regs)
        return y, plan.drops

    run = checkify.checkify(jax.jit(prog))
    err, _ = run(_regs(), x, dst, src)
    assert err.get() is None
    err, _ = run(_regs(), x, dst.at[2].set(17), src)
    assert err.get() is not None and "invalid destination" in err.get()


def test_debug_mode_keeps_single_trace():
    """The retrace pin survives debug mode: reconfiguring register values
    between checked calls compiles nothing new."""
    fab = Fabric(_regs(), backend="reference", capacity=CAP, debug=True)
    x, dst, src = _traffic()
    fab.transfer(x, dst, src)
    regs2 = _regs().with_quota(dst=1, src=0, packages=1)
    fab.transfer(x, dst, src, registers=regs2)
    assert fab.trace_counts["transfer"] == 1


def test_sharded_debug_on_forced_mesh():
    """All three ISSUE fault paths on the sharded backend, inside
    jax.shard_map(check_vma=False) under an outer checkify."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import checkify
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.registers import CrossbarRegisters
from repro.fabric import Fabric

mesh = Mesh(np.array(jax.devices()), ("x",))
regs = CrossbarRegisters.create(4, capacity=4)
fab = Fabric(regs, backend="sharded", axis_name="x", capacity=4, debug=True)
plain = Fabric(regs, backend="sharded", axis_name="x", capacity=4)

def body(r, x, d, s):
    y, plan = fab.transfer(x, d, s, registers=r)
    return y, plan.drops

def body_plain(r, x, d, s):
    y, plan = plain.transfer(x, d, s, registers=r)
    return y, plan.drops

kw = dict(mesh=mesh, in_specs=(P(), P("x"), P("x"), P("x")),
          out_specs=(P("x"), P()))
run = checkify.checkify(jax.jit(jax.shard_map(body, check_vma=False, **kw)))
run_plain = jax.jit(jax.shard_map(body_plain, **kw))

x = jnp.arange(8 * 8, dtype=jnp.float32).reshape(8, 8)
dst = jnp.asarray([0, 1, 2, 3] * 2)
src = jnp.repeat(jnp.arange(4, dtype=jnp.int32), 2)

err, (y, drops) = run(regs, x, dst, src)
assert err.get() is None, err.get()
y0, drops0 = run_plain(regs, x, dst, src)
assert np.array_equal(np.asarray(y), np.asarray(y0))
assert np.array_equal(np.asarray(drops), np.asarray(drops0))

err, _ = run(regs, x, dst.at[3].set(11), src)         # spray
assert err.get() and "invalid destination" in err.get(), err.get()

err, _ = run(regs, x, jnp.zeros(8, jnp.int32), src)   # burst: 8 > cap 4
assert err.get() and "over-capacity burst" in err.get(), err.get()

iso = regs.with_isolation(0, [0])                     # shard 0 -> port 0 only
err, _ = run(iso, x, dst, src)
assert err.get() and "invalid destination" in err.get(), err.get()
print("SHARDED-DEBUG-OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop(DEBUG_ENV_VAR, None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "SHARDED-DEBUG-OK" in proc.stdout


def test_sharded_dest_sprayer_strict_vs_masked_on_forced_mesh():
    """ISSUE 9 satellite: seam-generated ``dest_sprayer`` traffic on the
    sharded backend raises under ``debug="strict"`` but is masked
    bit-identically to the debug-off build in normal mode, with every
    sprayed packet accounted as a drop."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import checkify
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.registers import CrossbarRegisters
from repro.fabric import Fabric
from repro.manager.adversary import AttackView, DestSprayer

mesh = Mesh(np.array(jax.devices()), ("x",))
regs = (CrossbarRegisters.create(4, capacity=4)
        .with_isolation(1, [0, 1])
        .with_isolation(2, [0, 2, 3])
        .with_isolation(3, [0, 2, 3]))
strict = Fabric(regs, backend="sharded", axis_name="x", capacity=4,
                debug=True)
plain = Fabric(regs, backend="sharded", axis_name="x", capacity=4,
               debug=False)

rng = np.random.default_rng(1)
view = AttackView(tick=0, app_id=7, name="mal", host_port=0, my_ports=(1,),
                  n_ports=4, capacity=4, healthy_rids=(0, 1, 2),
                  utilization=0.9)
(action,) = DestSprayer(burst=2).step(view, rng)

honest = jnp.asarray([0, 0, 1, 1, 2, 2, 3, 3], jnp.int32)
spray = honest.at[2].set(int(action.dsts[0])).at[3].set(int(action.dsts[1]))
src = jnp.repeat(jnp.arange(4, dtype=jnp.int32), 2)
x = jnp.arange(8 * 8, dtype=jnp.float32).reshape(8, 8)

def body(fab):
    def inner(r, xx, d, s):
        y, plan = fab.transfer(xx, d, s, registers=r)
        return y, plan.keep, plan.error, plan.drops
    return inner

kw = dict(mesh=mesh, in_specs=(P(), P("x"), P("x"), P("x")),
          out_specs=(P("x"), P("x"), P("x"), P()))
run_strict = checkify.checkify(
    jax.jit(jax.shard_map(body(strict), check_vma=False, **kw)))
run_plain = jax.jit(jax.shard_map(body(plain), **kw))

err, _ = run_strict(regs, x, honest, src)
assert err.get() is None, err.get()          # clean traffic passes strict

err, _ = run_strict(regs, x, spray, src)     # the sprayer raises
assert err.get() and "invalid destination" in err.get(), err.get()

# normal mode: masked, bit-identical under a second debug-off build
plain2 = Fabric(regs, backend="sharded", axis_name="x", capacity=4,
                debug=False)
run_plain2 = jax.jit(jax.shard_map(body(plain2), **kw))
y0, keep0, err0, drops0 = run_plain(regs, x, spray, src)
y1, keep1, err1, drops1 = run_plain2(regs, x, spray, src)
for a, b in ((y0, y1), (keep0, keep1), (err0, err1), (drops0, drops1)):
    assert np.array_equal(np.asarray(a), np.asarray(b))
keep = np.asarray(keep0)
assert not keep[2:4].any()                   # both sprayed packets masked
assert (np.asarray(err0)[2:4] == 1).all()    # INVALID_DEST
assert keep[[0, 1, 4, 5, 6, 7]].all()        # honest grants untouched
drops = np.asarray(drops0)
assert int(drops[1]) == 2                    # both sprays in the
                                             # INVALID_DEST bucket
assert int(drops.sum()) == 8                 # every row accounted
assert np.allclose(np.asarray(y0)[2:4], 0.0) # attacker reads zeros
print("SHARDED-SPRAYER-OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop(DEBUG_ENV_VAR, None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "SHARDED-SPRAYER-OK" in proc.stdout
