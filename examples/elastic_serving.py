"""Elastic multi-tenant serving through the unified ``repro.shell`` API.

The paper's §IV-A lifecycle, rebuilt on the event-driven shell: one
``Shell`` owns the region pool, the live (delta-patched) register file and
the event log; the heartbeat monitor posts fault events instead of being
polled; and an ``ElasticServer`` serves *overlapping* multi-tenant request
streams with continuous batching — new requests are admitted into freed
decode slots while earlier ones are still mid-stream, with admission routed
by ``app_id`` through the shell's register file.

Control-plane script: submit A and B -> the **resource manager** rebalances
them (no manual ``Shrink``: a ``Manager`` tick reads telemetry and posts
the events itself) -> a region fails via stale heartbeat (module demoted,
port held in reset) -> heal (promoted back) -> A releases.  After every
event the delta-synthesised register file is checked bit-identical to a
full rebuild (``shell.verify``).

    PYTHONPATH=src python examples/elastic_serving.py
    PYTHONPATH=src python examples/elastic_serving.py --steady-state

``--steady-state`` runs the decode fast-path demo instead: a thousand
seeded streams decode through the server's epoch-keyed fabric plan cache
(``repro.fabric.cache``), a mid-run ``FailRegion`` invalidates it, and the
hit/miss/invalidation counters are read back through ``Fabric.probe()``.
"""
import argparse

import numpy as np

from repro.configs import get_config
from repro.core.module import ModuleFootprint
from repro.manager import FairShare, Manager
from repro.runtime.ft import HeartbeatMonitor
from repro.shell import ON_SERVER, Shell, Submit
from repro.shell.server import ElasticServer, StreamRequest

GB = 1 << 30


def show(shell, title):
    print(f"\n-- {title}")
    for t in sorted(shell.state.tenants, key=lambda t: t.name):
        pretty = ["host" if p == ON_SERVER else f"R{p}" for p in t.placement]
        print(f"   {t.name}: {pretty}")
    regs = shell.registers
    last = shell.log[-1].plan if shell.log else None
    delta = f", last delta: {last.delta.n_entries} entries" if last else ""
    print(f"   utilization={shell.utilization():.2f}  "
          f"epoch={shell.epoch}{delta}")
    print(f"   registers: dest={np.asarray(regs.dest).tolist()} "
          f"reset={np.asarray(regs.reset).astype(int).tolist()}")
    shell.verify()          # delta-patched file == full rebuild, invariants


def main():
    from repro.core.elastic import Region
    shell = Shell([Region(rid=i, n_chips=64, hbm_bytes=16 * GB)
                   for i in range(4)], policy="first_fit")
    # Region ids derive live from the shell's pool — no static list to
    # go stale when the pool reconfigures.
    monitor = HeartbeatMonitor(timeout_s=10.0, shell=shell)

    fp = lambda gb: ModuleFootprint(param_bytes=gb * GB,
                                    flops_per_token=2e9,
                                    activation_bytes_per_token=8192)

    shell.post(Submit(tenant="tenant_a", footprints=(fp(4), fp(4), fp(4)),
                      app_id=0))
    shell.post(Submit(tenant="tenant_b", footprints=(fp(2), fp(2)),
                      app_id=1))
    show(shell, "after admission (B partially on-server)")

    # --- data plane: both tenants stream requests through one server.
    server = ElasticServer(shell, n_slots=2)
    server.register_model(0, get_config("tinyllama_1_1b", smoke=True),
                          max_len=64)
    server.register_model(1, get_config("qwen2_5_3b", smoke=True),
                          max_len=64)
    for start, max_new in ((2, 4), (5, 6)):
        server.submit(StreamRequest(app_id=1,
                                    prompt=np.arange(start, dtype=np.int32),
                                    max_new=max_new))
    server.step()           # both admitted, decoding begins
    print(f"\n   serving: {server.active_count} active, "
          f"{server.queued_count} queued (tick {server.tick})")

    # Continuous batching: tenant A's stream arrives MID-DECODE and is
    # admitted as soon as a slot rotates — no wave barrier.
    server.submit(StreamRequest(app_id=0,
                                prompt=np.arange(3, dtype=np.int32),
                                max_new=3))
    server.submit(StreamRequest(app_id=1,
                                prompt=np.arange(4, dtype=np.int32),
                                max_new=2))
    comps = server.run()
    print("   completions (rid, app, entry_port, admitted->finished tick):")
    for c in sorted(comps, key=lambda c: c.rid):
        print(f"     #{c.rid} app{c.app_id} port{c.entry_port} "
              f"t{c.admitted_tick}->t{c.finished_tick}  tokens={c.tokens}")
    overlapped = [c for c in comps if 0 < c.admitted_tick]
    print(f"   {len(overlapped)} request(s) admitted while earlier "
          f"requests were still decoding")
    # The data plane: every tick's slot->port packets were planned through
    # the server's shell-bound fabric under the LIVE register file.
    print(f"   per-port fabric grants: {server.port_traffic.tolist()}  "
          f"(fabric retraces: {server.fabric.trace_count})")

    # --- elasticity, closed-loop: no manual Shrink/Grow.  The resource
    # manager samples telemetry (queue/slots/traffic via the server's
    # probe) and FairShare computes the weighted max-min allocation:
    # 4 healthy regions, A requests 3, B requests 2 -> 2 + 2, so the
    # manager posts Shrink(A, 2) and Grow(B, 2) itself (§IV-A promote
    # path, driven from Signals alone).
    manager = Manager(shell, policy=FairShare(), probes=[server.probe()])
    decision = manager.tick()
    print(f"\n   manager decided: {list(decision.kinds())} from "
          f"free={decision.signals.free_regions}, "
          f"requested/granted="
          f"{[(t.name, t.requested, t.granted) for t in decision.signals.tenants]}")
    show(shell, "manager rebalanced: A -> 2 regions, B's waiter promoted")

    # --- failure: region 2 misses heartbeats; the monitor POSTS the event.
    for healthy in (0, 1, 3):
        monitor.beat(healthy)
    monitor.last_beat[2] -= 100.0            # simulate stale heartbeat
    failed = monitor.sweep()
    show(shell, f"region {failed} failed -> demote to host, port reset")

    # B still serves (degraded placement, same program).
    server.submit(StreamRequest(app_id=1, prompt=np.arange(3, dtype=np.int32),
                                max_new=3))
    (comp,) = server.run()
    print(f"   B serves after failure: {comp.tokens} "
          f"(entry port {comp.entry_port})")

    # --- heal: the region returns, the waiter is promoted back.
    monitor.heal(2)
    show(shell, "region healed -> promoted back")

    # --- release: A departs; the pool drains to B alone.
    shell.release("tenant_a")
    show(shell, "A released")

    # --- reconfiguration cost model (the ICAP analogue).
    cost = shell.reconfig_cost_s(fp(4))
    print(f"\n   region reprogram cost for a 4 GB module: {cost:.2f} s "
          f"(restore at HBM bw + dispatch)")
    print(f"   event log: "
          f"{[(type(e.event).__name__, [a.kind for a in e.plan.actions]) for e in shell.log]}")


def steady_state():
    """The serving fast path: cached decode ticks + probe-read hit rate."""
    from repro.core.elastic import Region
    from repro.serve import (ReconfigEvent, SeededEngine, ServeHarness,
                             front_loaded_arrivals)

    shell = Shell([Region(rid=i, n_chips=64, hbm_bytes=16 * GB)
                   for i in range(4)], policy="first_fit")
    fp = ModuleFootprint(param_bytes=4 * GB, flops_per_token=2e9,
                         activation_bytes_per_token=8192)
    shell.post(Submit(tenant="svc", footprints=(fp, fp), app_id=0))

    # 1024 streams through 256 concurrent slots; the plan cache (on by
    # default) memoizes each steady tick's plan under the register epoch.
    server = ElasticServer(shell, n_slots=256)
    server.register_engine(0, SeededEngine(seed=42))
    probe = server.fabric.probe()           # Fabric.probe(): cache counters
    arrivals = front_loaded_arrivals(1024, seed=42, max_new=24)
    reconfigs = [ReconfigEvent(30, lambda sh: sh.fail_region(3),
                               "fail R3 mid-decode")]
    report = ServeHarness(server, arrivals, reconfigs=reconfigs).run()

    ch = probe.sample()
    print("-- steady-state decode fast path")
    print(f"   {report.n_streams} streams, {report.n_slots} slots, "
          f"{report.ticks} ticks ({report.steady_ticks} pure-decode), "
          f"{report.tokens} tokens @ {report.tokens_per_s:,.0f} tok/s")
    print(f"   decode tick p50/p99: {report.steady_tick_p50_us:.0f}/"
          f"{report.steady_tick_p99_us:.0f} us   admission p50/p99: "
          f"{report.admission_p50_ticks:.0f}/"
          f"{report.admission_p99_ticks:.0f} ticks")
    print(f"   plan cache via Fabric.probe(): "
          f"{ch['plan_cache_hits']} hits / "
          f"{ch['plan_cache_misses']} misses "
          f"(hit rate {report.plan_cache_hit_rate:.1%}), "
          f"{ch['plan_cache_invalidations']} invalidation(s) from the "
          f"mid-run FailRegion")
    print(f"   fabric retraces: {ch['fabric_traces']} — the epoch bump "
          f"invalidated cache entries, never the compiled program")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steady-state", action="store_true",
                    help="run the cached-decode fast-path demo instead of "
                         "the full lifecycle script")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    steady_state() if args.steady_state else main()
