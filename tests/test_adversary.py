"""Isolation guarantees under hostile tenants (``repro.manager.adversary``).

The paper's security claims, property-tested as a *system* (ISSUE 9):

- **masking**: invalid Wishbone requests — out-of-range or foreign
  destinations — are dropped at the crossbar master port.  A tenant can
  never read another tenant's slots: sprayed packets land in no victim
  slab row and combine to zeros, on every backend.
- **WRR bandwidth isolation**: each source only ever consumes its
  allocated share.  Masked packets consume no arbiter rank and no slot,
  so an honest tenant's grants under attack are *exactly* (epsilon = 0)
  what they are in the quiet baseline; a quota-capped attacker gets
  exactly its quota and nothing more.
- **attribution**: masked/dropped packets are charged to the originating
  source port (``Fabric.account(plan, src)``), pinned against a
  per-packet recomputation from the reference plan, cached and uncached.
- **costs only the attacker**: in every attack scenario without induced
  region faults, the host port (all honest serving traffic) accrues zero
  masked packets and zero lost grants.
- **zero retrace**: ``fabric_retraces == 1`` through every attack mix —
  hostile traffic rides the same compiled plan as honest traffic.

Scenario properties run hypothesis-driven over seeds x attacker mixes
(with a numpy sweep fallback); the sharded backend is covered on a forced
4-device topology in a subprocess.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import arbiter
from repro.core.module import ModuleFootprint
from repro.core.registers import CrossbarRegisters, ErrorCode
from repro.fabric import Fabric
from repro.manager import (ATTACKER_KINDS, Attacker, AttackView,
                           CascadeFailer, DestSprayer, DropRetrier,
                           FailAction, FairShare, NoisyNeighbor,
                           RequestAction, Signals, SprayAction,
                           TenantSignals, TrafficAwareDefrag, abuse_scores,
                           adversarial_policy, build_spec, get_attacker,
                           register_attacker, run_scenario)
from repro.manager.adversary import _ATTACKERS
from repro.shell.shell import Shell

GB = 1 << 30
REPO = Path(__file__).resolve().parent.parent
SRC = str(REPO / "src")

N, CAP, D = 4, 4, 8
HOST = 0
BACKENDS = ["reference", "pallas"]
INVALID = int(ErrorCode.INVALID_DEST)


def fp(gb=1):
    return ModuleFootprint(param_bytes=gb * GB, flops_per_token=1e9,
                           activation_bytes_per_token=4096)


def make_shell(n=4):
    from repro.core.elastic import Region
    return Shell([Region(rid=i, n_chips=16, hbm_bytes=16 * GB)
                  for i in range(n)])


def tenant_regs():
    """Two tenants on a 4-port fabric: A owns port 1, B owns ports 2/3
    (port 0 is the host bridge, reachable by everyone)."""
    return (CrossbarRegisters.create(N, capacity=CAP)
            .with_isolation(1, [0, 1])
            .with_isolation(2, [0, 2, 3])
            .with_isolation(3, [0, 2, 3]))


def make_view(**kw):
    base = dict(tick=0, app_id=7, name="mal", host_port=HOST,
                my_ports=(1,), n_ports=N, capacity=CAP,
                healthy_rids=(0, 1, 2), utilization=0.9)
    base.update(kw)
    return AttackView(**base)


# ----------------------------------------------------------------------
# the seam: registry + built-in attacker behaviors
# ----------------------------------------------------------------------
class TestAttackerSeam:
    def test_registry_carries_the_four_hostile_kinds(self):
        assert {"noisy_neighbor", "dest_sprayer", "drop_retrier",
                "cascade_failer"} <= set(ATTACKER_KINDS)
        for kind in ATTACKER_KINDS:
            assert isinstance(get_attacker(kind), Attacker)
        with pytest.raises(KeyError, match="unknown attacker"):
            get_attacker("nope")
        inst = DestSprayer(burst=3)
        assert get_attacker(inst) is inst           # pass-through

    def test_register_attacker_decorator(self):
        @register_attacker
        class Lurker(Attacker):
            name = "test_lurker"

            def step(self, view, rng):
                return []
        try:
            assert isinstance(get_attacker("test_lurker"), Lurker)
        finally:
            _ATTACKERS.pop("test_lurker", None)

    def test_dest_sprayer_emits_only_invalid_or_foreign(self):
        rng = np.random.default_rng(0)
        atk = DestSprayer(burst=16)
        for _ in range(8):
            (action,) = atk.step(make_view(), rng)
            assert isinstance(action, SprayAction)
            for d in action.dsts:
                assert d >= 0                       # never padding
                assert d != HOST                    # never the legal bridge
                assert d != 1                       # never its own port
                assert d in (2, 3) or d >= N        # foreign or wild
        assert atk.step(make_view(my_ports=()), rng) == []

    def test_noisy_neighbor_saturates_its_own_port(self):
        rng = np.random.default_rng(0)
        actions = NoisyNeighbor(requests_per_tick=3).step(make_view(), rng)
        reqs = [a for a in actions if isinstance(a, RequestAction)]
        sprays = [a for a in actions if isinstance(a, SprayAction)]
        assert len(reqs) == 3 and len(sprays) == 1
        assert sprays[0].dsts == (1,) * CAP         # full legal burst

    def test_drop_retrier_escalates_with_feedback_and_caps(self):
        rng = np.random.default_rng(0)
        atk = DropRetrier(base_burst=4, cap=10)
        (a0,) = atk.step(make_view(my_dropped=0), rng)
        assert len(a0.dsts) == 4
        (a1,) = atk.step(make_view(my_dropped=5), rng)  # 5 fresh drops
        assert len(a1.dsts) == 9
        (a2,) = atk.step(make_view(my_dropped=100), rng)
        assert len(a2.dsts) == 10                   # capped

    def test_cascade_failer_threshold_and_cooldown(self):
        rng = np.random.default_rng(0)
        atk = CascadeFailer(threshold=0.5, cooldown=3)
        assert atk.step(make_view(tick=0, utilization=0.2), rng) == []
        (hit,) = atk.step(make_view(tick=1), rng)
        assert isinstance(hit, FailAction) and hit.rid in (0, 1, 2)
        assert atk.step(make_view(tick=2), rng) == []   # cooling down
        assert atk.step(make_view(tick=4), rng) != []


# ----------------------------------------------------------------------
# fabric-level: masking + WRR isolation, exact
# ----------------------------------------------------------------------
class TestFabricIsolation:
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_spray_never_reaches_victim_slots(self, backend):
        """A sprays B's ports: every sprayed packet is masked with
        INVALID_DEST, B's slabs hold only B's payloads, and combine hands
        the attacker zeros — it cannot read a thing."""
        fab = Fabric(tenant_regs(), backend=backend, capacity=CAP)
        dst = jnp.asarray([2, 3, 2, 3, 2, 2, 3, 3], jnp.int32)
        src = jnp.asarray([1, 1, 1, 1, 2, 2, 3, 3], jnp.int32)
        x = jnp.concatenate([jnp.full((4, D), 999.0),
                             jnp.arange(4 * D, dtype=jnp.float32)
                             .reshape(4, D) + 1.0])
        y, plan = fab.transfer(x, dst, src)
        err = np.asarray(plan.error)
        keep = np.asarray(plan.keep)
        assert (err[:4] == INVALID).all() and not keep[:4].any()
        assert keep[4:].all()
        np.testing.assert_array_equal(np.asarray(plan.counts), [0, 0, 2, 2])
        slabs, _ = fab.dispatch(x, dst, src)
        assert not (np.asarray(slabs) == 999.0).any()
        dense = arbiter.dispatch_dense(x, plan, N, CAP)
        np.testing.assert_array_equal(np.asarray(slabs), np.asarray(dense))
        y = np.asarray(y)
        assert (y[:4] == 0.0).all()                 # attacker reads zeros
        np.testing.assert_array_equal(y[4:], np.asarray(x[4:]))

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_honest_grants_exact_under_masked_saturation(self, backend):
        """epsilon = 0: interleave a masked spray with an honest
        capacity-filling burst at one destination — the honest packets'
        slot ranks are bit-identical to the quiet (honest-only) plan."""
        fab = Fabric(tenant_regs(), backend=backend, capacity=CAP)
        dst = jnp.full(8, 2, jnp.int32)
        src = jnp.asarray([1, 2, 1, 2, 1, 2, 1, 2], jnp.int32)
        noisy = fab.plan(dst, src)
        quiet = fab.plan(jnp.full(4, 2, jnp.int32),
                         jnp.full(4, 2, jnp.int32))
        victim = np.arange(1, 8, 2)                 # honest positions
        keep = np.asarray(noisy.keep)
        assert keep[victim].all() and not keep[::2].any()
        np.testing.assert_array_equal(np.asarray(noisy.slot)[victim],
                                      np.asarray(quiet.slot))
        assert int(np.asarray(noisy.counts)[2]) == CAP

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_quota_capped_attacker_gets_exactly_its_share(self, backend):
        """With a WRR quota of 1 package on (src 1 -> dst 2), a 4-packet
        burst from the attacker grants exactly 1; the honest tenant's 3
        packets all grant — each source consumes its allocation only."""
        regs = CrossbarRegisters.create(N, capacity=CAP).with_quota(
            dst=2, src=1, packages=1)
        fab = Fabric(regs, backend=backend, capacity=CAP)
        dst = jnp.full(7, 2, jnp.int32)
        src = jnp.asarray([1, 1, 1, 1, 2, 2, 2], jnp.int32)
        plan = fab.plan(dst, src)
        keep = np.asarray(plan.keep)
        assert int(keep[:4].sum()) == 1             # the quota, exactly
        assert keep[4:].all()                       # honest untouched
        err = np.asarray(plan.error)
        assert (err[:4][~keep[:4]]
                == int(ErrorCode.GRANT_TIMEOUT)).all()


# ----------------------------------------------------------------------
# per-source attribution (the ISSUE's account() fix), oracle-pinned
# ----------------------------------------------------------------------
class TestSourceAttribution:
    @staticmethod
    def expected(plan, src, n_ports):
        dst = np.asarray(plan.dst)
        err = np.asarray(plan.error)
        keep = np.asarray(plan.keep).astype(bool)
        src = np.asarray(src)
        masked = np.zeros(n_ports, np.int64)
        dropped = np.zeros(n_ports, np.int64)
        for i in range(dst.shape[0]):               # per-packet oracle
            if dst[i] < 0:
                continue
            if err[i] == INVALID:
                masked[src[i]] += 1
            if not keep[i]:
                dropped[src[i]] += 1
        return masked, dropped

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_account_charges_the_originating_port(self, backend):
        rng = np.random.default_rng(7)
        fab = Fabric(tenant_regs(), backend=backend, capacity=CAP)
        for trial in range(4):
            dst = jnp.asarray(rng.integers(-1, N + 2, 16), jnp.int32)
            src = jnp.asarray(rng.integers(0, N, 16), jnp.int32)
            plan = fab.plan(dst, src)
            fab.reset_accounting()
            fab.account(plan, src)
            masked, dropped = self.expected(plan, src, N)
            np.testing.assert_array_equal(fab.masked_by_src, masked,
                                          err_msg=f"trial {trial} masked")
            np.testing.assert_array_equal(fab.dropped_by_src, dropped,
                                          err_msg=f"trial {trial} dropped")

    def test_cached_fast_path_matches_uncached_attribution(self):
        """The memoized account() replay accrues the same per-source
        vectors as the uncached path — hostile offers included."""
        shell = make_shell()
        shell.submit("a", [fp(2), fp(2)], app_id=0)
        cached = shell.fabric(plan_cache=True, capacity=8)
        plain = shell.fabric(plan_cache=False, capacity=8)
        rng = np.random.default_rng(3)
        dst = jnp.asarray(rng.integers(-1, cached.n_ports + 3, 12),
                          jnp.int32)
        src = jnp.asarray(rng.integers(0, cached.n_ports, 12), jnp.int32)
        for _ in range(3):                          # miss, then cache hits
            cached.account(cached.plan(dst, src))   # src via cache entry
            plain.account(plain.plan(dst, src), src)
        np.testing.assert_array_equal(cached.masked_by_src,
                                      plain.masked_by_src)
        np.testing.assert_array_equal(cached.dropped_by_src,
                                      plain.dropped_by_src)
        masked1, dropped1 = self.expected(plain.plan(dst, src), src,
                                          plain.n_ports)
        np.testing.assert_array_equal(cached.masked_by_src, 3 * masked1)
        np.testing.assert_array_equal(cached.dropped_by_src, 3 * dropped1)
        cached.reset_accounting()
        assert int(cached.masked_by_src.sum()) == 0
        assert int(cached.dropped_by_src.sum()) == 0


# ----------------------------------------------------------------------
# policy hooks: abuse evidence shifts shares and move ordering
# ----------------------------------------------------------------------
def _signals(tenants, *, healthy=4, port_traffic_delta=()):
    return Signals(tick=8, epoch=1, tenants=tuple(tenants),
                   free_regions=0, healthy_regions=healthy,
                   total_regions=healthy, fragmentation=1.0,
                   port_traffic_delta=tuple(port_traffic_delta))


class TestAbusePenaltyHooks:
    def test_abuse_scores_lists_offenders_only(self):
        sig = _signals([TenantSignals("a", 0, 4, 2),
                        TenantSignals("b", 1, 4, 2, masked_requests=10)])
        assert abuse_scores(sig) == {"b": 10}

    def test_fair_share_penalizes_abuser_not_victim(self):
        sig = _signals([TenantSignals("a", 0, 4, 0),
                        TenantSignals("b", 1, 4, 0, masked_requests=10)])
        quiet = FairShare().share(sig, None)
        punitive = FairShare(abuse_penalty=1.0).share(sig, None)
        assert quiet == {"a": 2, "b": 2}
        assert punitive["b"] < punitive["a"]
        # abuse costs only the abuser: the clean tenant never drops
        # below its quiet share
        assert punitive["a"] >= quiet["a"]
        assert punitive["a"] + punitive["b"] == 4   # capacity still fills

    def test_defrag_disrupts_the_abuser_first(self):
        shell = make_shell(4)
        shell.submit("a", [fp(2)], app_id=0)        # rid 0
        shell.submit("b", [fp(2)], app_id=1)        # rid 1 -> port 2
        shell.submit("c", [fp(2)], app_id=2)        # rid 2 -> port 3
        shell.release("a")                          # rid 0 free
        tenants = [TenantSignals("b", 1, 1, 1),
                   TenantSignals("c", 2, 1, 1, masked_requests=3)]
        sig = _signals(tenants, port_traffic_delta=(0, 0, 0, 5, 0))
        cold = TrafficAwareDefrag(max_moves=1).decide(sig, shell.state)
        assert cold and cold[0].tenant == "b"       # b is coldest (0 < 5)
        punitive = TrafficAwareDefrag(
            max_moves=1, abuse_penalty=10.0).decide(sig, shell.state)
        assert punitive and punitive[0].tenant == "c"

    def test_granted_share_ratio(self):
        sig = _signals([
            TenantSignals("a", 0, 2, 2, granted_traffic=30),
            TenantSignals("b", 1, 2, 2, granted_traffic=10),
            TenantSignals("idle", 2, 2, 2, granted_traffic=0)])
        assert sig.granted_share_ratio("a") == pytest.approx(1.5)
        assert sig.granted_share_ratio("b") == pytest.approx(0.5)
        assert sig.granted_share_ratio("idle") == 0.0
        assert sig.granted_share_ratio("a", {"a": 3.0, "b": 1.0}) \
            == pytest.approx(1.0)
        assert sig.granted_share_ratio("ghost") == 0.0


# ----------------------------------------------------------------------
# scenario-level properties: seeds x attacker mixes
# ----------------------------------------------------------------------
MIXES = [
    ("dest_sprayer",),
    ("noisy_neighbor", "dest_sprayer"),
    ("drop_retrier", "dest_sprayer"),
    ("noisy_neighbor", "dest_sprayer", "drop_retrier", "cascade_failer"),
]


def check_isolation_properties(seed, mix):
    spec = build_spec("adversarial", ticks=20, seed=seed, attackers=mix)
    res = run_scenario(spec, seed=seed, ticks=20,
                       policy=adversarial_policy())
    last = res.trace[-1]
    masked = last["masked_by_src"]
    dropped = last["dropped_by_src"]
    # zero-retrace through every attack scenario
    assert res.fabric_retraces == 1, (seed, mix)
    assert all(r["fabric_traces"] == 1 for r in res.trace)
    if "dest_sprayer" in mix:
        # the sprayer's packets were masked and charged to *its* ports
        assert sum(masked[1:]) > 0, (seed, mix)
    if "cascade_failer" not in mix:
        # invalid requests cost only the attacker's own budget: honest
        # serving traffic (all host-port-sourced) accrues zero masked
        # packets and loses zero grants, under every attack
        assert masked[HOST] == 0, (seed, mix)
        assert dropped[HOST] == masked[HOST], (seed, mix)
    # the system still serves: honest tenants complete work under attack
    assert res.completions > 0, (seed, mix)
    return res


@pytest.mark.parametrize("seed,mix", [(0, MIXES[0]), (1, MIXES[1]),
                                      (2, MIXES[3])])
def test_isolation_properties_numpy_sweep(seed, mix):
    check_isolation_properties(seed, mix)


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 40), st.sampled_from(MIXES))
    @settings(max_examples=6, deadline=None)
    def test_isolation_properties_hypothesis(seed, mix):
        check_isolation_properties(seed, mix)


def test_signals_attribute_masking_to_the_sprayer():
    """The manager's view of the attack: some decision window shows the
    sprayer tenant with masked_requests > 0 while honest tenants stay at
    zero throughout."""
    res = check_isolation_properties(5, ("dest_sprayer",))
    mal = "mal0_dest_sprayer"
    saw_abuse = False
    for d in res.decisions:
        ts = d.signals.tenant(mal)
        if ts is not None and ts.masked_requests > 0:
            saw_abuse = True
        for honest in ("alpha", "beta"):
            h = d.signals.tenant(honest)
            assert h is None or h.masked_requests == 0
    assert saw_abuse
    assert abuse_scores(res.decisions[-1].signals).keys() <= {mal}


def test_quiet_twin_sees_identical_honest_workload(tmp_path):
    """attackers=() is the paired baseline: the honest request stream is
    byte-identical between the attack run and its quiet twin (attackers
    are the only extra rng consumers)."""
    from repro.manager import RecordedWorkload

    def honest_rows(path):
        return [(r["tick"], r["app_id"], r["prompt"], r["max_new"])
                for r in RecordedWorkload.load(path).rows
                if r["op"] == "request" and r["app_id"] < 10]

    attack = tmp_path / "attack.jsonl"
    quiet = tmp_path / "quiet.jsonl"
    run_scenario(build_spec("adversarial", ticks=16, seed=9),
                 seed=9, ticks=16, policy=adversarial_policy(),
                 record_path=attack)
    run_scenario(build_spec("adversarial", ticks=16, seed=9, attackers=()),
                 seed=9, ticks=16, policy=adversarial_policy(),
                 record_path=quiet)
    rows_a, rows_q = honest_rows(attack), honest_rows(quiet)
    assert rows_a == rows_q and rows_a   # identical and non-empty


def test_attack_replay_is_bit_identical(tmp_path):
    """Recorded adversarial runs replay exactly: the spray rows re-apply
    through the same entry point and the trace matches bit-for-bit."""
    from repro.manager import RecordedWorkload

    path = tmp_path / "attack.jsonl"
    res = run_scenario(build_spec("adversarial", ticks=16, seed=4),
                       seed=4, ticks=16, policy=adversarial_policy(),
                       record_path=path)
    replayed = run_scenario(RecordedWorkload.load(path),
                            policy=adversarial_policy())
    assert replayed.trace == res.trace
    assert replayed.fabric_retraces == 1


# ----------------------------------------------------------------------
# sharded backend on the forced 4-device topology
# ----------------------------------------------------------------------
def test_sharded_masking_parity_with_reference():
    """Seam-generated spray traffic on the sharded backend: masked with
    the same per-packet verdicts and counts as the reference plan."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.registers import CrossbarRegisters
from repro.fabric import Fabric
from repro.manager.adversary import AttackView, DestSprayer

mesh = Mesh(np.array(jax.devices()), ("x",))
regs = (CrossbarRegisters.create(4, capacity=4)
        .with_isolation(1, [0, 1])
        .with_isolation(2, [0, 2, 3])
        .with_isolation(3, [0, 2, 3]))
sharded = Fabric(regs, backend="sharded", axis_name="x", capacity=4)
ref = Fabric(regs, backend="reference", capacity=4)

rng = np.random.default_rng(0)
view = AttackView(tick=0, app_id=7, name="mal", host_port=0, my_ports=(1,),
                  n_ports=4, capacity=4, healthy_rids=(0, 1, 2),
                  utilization=0.9)
(action,) = DestSprayer(burst=2).step(view, rng)   # shard 1's hostile pair

# shard i sources from port i: honest everywhere except shard 1's spray
dst = jnp.asarray([0, 0, action.dsts[0], action.dsts[1], 2, 2, 3, 3],
                  jnp.int32)
src = jnp.repeat(jnp.arange(4, dtype=jnp.int32), 2)

def body(r, d, s):
    plan = sharded.plan(d, s, registers=r)
    return plan.keep, plan.error, plan.counts, plan.drops

run = jax.jit(jax.shard_map(body, mesh=mesh,
                        in_specs=(P(), P("x"), P("x")),
                        out_specs=(P("x"), P("x"), P(), P())))
keep, err, counts, drops = run(regs, dst, src)
p0 = ref.plan(dst, src)
assert np.array_equal(np.asarray(keep), np.asarray(p0.keep))
assert np.array_equal(np.asarray(err), np.asarray(p0.error))
assert np.array_equal(np.asarray(counts), np.asarray(p0.counts))
assert np.array_equal(np.asarray(drops), np.asarray(p0.drops))
assert not np.asarray(keep)[2:4].any()             # spray fully masked
assert (np.asarray(err)[2:4] == 1).all()           # INVALID_DEST
assert np.asarray(keep)[[0, 1, 4, 5, 6, 7]].all()  # honest all granted
print("SHARDED-ADVERSARY-OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "SHARDED-ADVERSARY-OK" in proc.stdout
