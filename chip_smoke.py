"""Chip smoke test: the elastic shell's main path on a TPU, end to end.

    python chip_smoke.py             # one chip: kernel + serve phases
    python chip_smoke.py --chips 4   # four chips: expert-parallel phase only

One chip.  The crossbar's Pallas kernels run compiled (``KernelMode.PALLAS``)
at a decode-sized and a prefill-sized offer and must match their references
bit for bit; then a 4-region ``Shell`` serves two full-width tenants
(TinyLlama-1.1B and Mixtral-8x7B cut to 2 layers) through
``ElasticServer(fabric_backend="pallas")``, with a ``Manager`` tick, a
``FailRegion`` and a heal between decode ticks.

Four chips.  ``moe_forward_sharded`` at Mixtral-8x7B expert width over a
4-chip ``("expert",)`` mesh, steered by a live ``Shell``'s register file
across a ``FailRegion``, against ``moe_apply_sharded_reference`` on one
chip.

Weights are random, drawn from a seed.  Every phase raises on a wrong
result.  The script needs a TPU: anywhere else it exits non-zero before
printing a result.  The last line of a passing run is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GB = 1 << 30
N_PORTS = 8                    # crossbar ports in the kernel phase
D_MODEL = 4096                 # packet width in the kernel phase
KERNEL_OFFERS = (8, 256, 8192)  # packets: decode-sized ... 4096 tokens x top-2
MIXTRAL_LAYERS = 2             # depth cut: 32 layers are ~94 GB in bf16
# bf16 keeps 8 mantissa bits; dispatch paths that round in a different
# order may differ by a few ulps, which stays well inside 2 % of the range.
BF16_REL_TOL = 2e-2


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def _rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def device_phase(n_chips: int):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{d0.platform!r}")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, found "
                         f"{len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


class CompileLog:
    """Backend compile seconds per program, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = defaultdict(float)
        self.count = defaultdict(int)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.seconds[name] += duration
            self.count[name] += 1

    def report(self, title: str) -> None:
        """Print seconds per program name (``/n`` when n programs share
        it), largest first, then start a new window."""
        rows = sorted(self.seconds.items(), key=lambda kv: -kv[1])
        total = sum(self.seconds.values())
        print(f"compile seconds ({title}): total={total:.1f} "
              + " ".join(f"{k}={v:.2f}/{self.count[k]}"
                         for k, v in rows[:12]), flush=True)
        self.seconds.clear()
        self.count.clear()


# ----------------------------------------------------------------------
# one chip: kernels
# ----------------------------------------------------------------------
def _kernel_registers(rng, n: int, capacity: int, T: int):
    """Isolation masks with holes and quotas that bind at offer ``T``."""
    import jax.numpy as jnp

    from repro.core.registers import CrossbarRegisters

    allowed = rng.random((n, n)) > 0.2
    quota = rng.integers(0, max(2, T // (n * n)) + 1, (n, n))
    return CrossbarRegisters.create(n, capacity=capacity).write(
        allowed=jnp.asarray(allowed), quota=jnp.asarray(quota, jnp.int32))


def kernel_custom_calls(T: int, n_ports: int, capacity: int,
                        d_model: int) -> dict:
    """Whether each crossbar kernel compiles to a Mosaic custom call."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.crossbar_dispatch import kernel as K

    block_t = min(256, max(8, T))
    i32 = jax.ShapeDtypeStruct((T,), jnp.int32)
    regs = jax.ShapeDtypeStruct((n_ports, n_ports), jnp.int32)
    lowered = {
        "plan_multi": K.plan_multi_call.lower(
            i32, i32, regs, regs, n_ports=n_ports, block_t=block_t),
        "scatter": K.scatter_call.lower(
            jax.ShapeDtypeStruct((T, d_model), jnp.bfloat16), i32, i32, i32,
            n_ports=n_ports, capacity=capacity, block_t=block_t),
        "combine": K.combine_call.lower(
            jax.ShapeDtypeStruct((n_ports, capacity, d_model), jnp.bfloat16),
            i32, i32, i32, jax.ShapeDtypeStruct((T,), jnp.float32),
            block_t=block_t),
    }
    return {k: "tpu_custom_call" in v.compile().as_text()
            for k, v in lowered.items()}


def kernel_phase(offers=KERNEL_OFFERS, n_ports: int = N_PORTS,
                 d_model: int = D_MODEL, seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import arbiter
    from repro.fabric import Fabric
    from repro.kernels.crossbar_dispatch.ops import _plan_multi

    for T in offers:
        rng = np.random.default_rng(seed + T)
        # 1.25x the mean load per port, in whole sublane tiles of 8
        capacity = 8 * max(1, -(-5 * T // (32 * n_ports)))
        regs = _kernel_registers(rng, n_ports, capacity, T)
        dst = jnp.asarray(rng.integers(-1, n_ports, T), jnp.int32)
        src = jnp.asarray(rng.integers(0, n_ports, T), jnp.int32)
        allowed_sd = regs.allowed.astype(jnp.int32)
        quota_sd = regs.quota.T

        # fused plan kernel vs its blockwise scan reference
        kern = _plan_multi(dst, src, allowed_sd, quota_sd, interpret=False)
        ref = _plan_multi(dst, src, allowed_sd, quota_sd, force_ref=True)
        for name, k, r in zip(("keep", "rank", "err", "granted"), kern, ref):
            _check(np.array_equal(np.asarray(k), np.asarray(r)),
                   f"plan_multi {name} differs from ref at T={T}")

        # the backend: compiled plan + kernel data plane vs core/arbiter
        fab = Fabric(regs, backend="pallas", data_plane="kernel",
                     kernel_mode="pallas", capacity=capacity)
        x = jax.random.normal(jax.random.key(seed + T), (T, d_model),
                              jnp.bfloat16)
        w = jnp.asarray(rng.random(T), jnp.float32)
        slabs, plan = fab.dispatch(x, dst, src)
        out = fab.combine(slabs, plan, weights=w)
        oracle = arbiter.wrr_dispatch_plan(dst, src, regs)
        for name in ("keep", "slot", "error", "counts", "drops"):
            _check(np.array_equal(np.asarray(getattr(plan, name)),
                                  np.asarray(getattr(oracle, name))),
                   f"pallas plan {name} differs from the oracle at T={T}")
        ref_slabs = arbiter.dispatch(x, oracle, n_ports, capacity)
        ref_out = arbiter.combine(ref_slabs, oracle, w).astype(out.dtype)
        _check(np.array_equal(np.asarray(slabs), np.asarray(ref_slabs)),
               f"scatter kernel differs from arbiter.dispatch at T={T}")
        _check(np.array_equal(np.asarray(out), np.asarray(ref_out)),
               f"combine kernel differs from arbiter.combine at T={T}")

        custom = kernel_custom_calls(T, n_ports, capacity, d_model)
        _check(all(custom.values()), f"missing tpu_custom_call: {custom}")
        print(f"kernels T={T} D={d_model} C={capacity}: plans bit-equal to "
              f"ref.py (packets per code OK/INVALID_DEST/GRANT_TIMEOUT/"
              f"ACK_TIMEOUT={np.asarray(oracle.drops).tolist()}), "
              f"scatter/combine "
              f"bit-equal to core/arbiter, tpu_custom_call={custom}",
              flush=True)


# ----------------------------------------------------------------------
# one chip: the serving path
# ----------------------------------------------------------------------
def tenant_configs(mixtral_layers: int = MIXTRAL_LAYERS):
    """TinyLlama-1.1B as published; Mixtral-8x7B at full width with its
    depth cut and its MoE routed through the Pallas fabric."""
    from repro.configs.mixtral_8x7b import FULL as MIXTRAL
    from repro.configs.tinyllama_1_1b import FULL as TINYLLAMA

    mixtral = dataclasses.replace(
        MIXTRAL, n_layers=mixtral_layers,
        moe=dataclasses.replace(MIXTRAL.moe, dispatch="pallas"))
    return TINYLLAMA, mixtral


def serve_phase(tiny_cfg, mix_cfg, *, requests_per_tenant: int = 4,
                prompt_lens=(16, 64), max_new: int = 16, max_len: int = 128,
                seed: int = 0, compile_log: CompileLog = None) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.elastic import Region
    from repro.core.module import ModuleFootprint
    from repro.manager import Manager
    from repro.models.lm import build_model
    from repro.models.moe import expert_capacity, moe_fabric
    from repro.shell import FailRegion, HealRegion, Shell, Submit
    from repro.shell.server import ElasticServer, StreamRequest

    fp = ModuleFootprint(param_bytes=GB, flops_per_token=2e9,
                         activation_bytes_per_token=8192)
    shell = Shell([Region(rid=i, n_chips=1, hbm_bytes=16 * GB)
                   for i in range(4)])
    shell.post(Submit(tenant="tinyllama", footprints=(fp,), app_id=0))
    shell.post(Submit(tenant="mixtral", footprints=(fp, fp), app_id=1))
    server = ElasticServer(shell, n_slots=2 * requests_per_tenant,
                           fabric_backend="pallas")
    cfgs = {0: tiny_cfg, 1: mix_cfg}
    for app_id, cfg in cfgs.items():
        t = time.perf_counter()
        server.register_model(app_id, cfg, max_len=max_len,
                              seed=seed + app_id)
        jax.block_until_ready(server.engine(app_id).params)
        print(f"tenant app{app_id} {cfg.name}: "
              f"{build_model(cfg).n_params() / 1e9:.3f}B params, "
              f"init {time.perf_counter() - t:.1f}s", flush=True)

    rng = np.random.default_rng(seed)
    prompts = {}
    for i in range(requests_per_tenant):
        for app_id, cfg in cfgs.items():
            prompt = rng.integers(0, cfg.vocab, prompt_lens[i % 2],
                                  dtype=np.int32)
            rid = server.submit(StreamRequest(app_id=app_id, prompt=prompt,
                                              max_new=max_new))
            prompts[rid] = prompt

    moe = moe_fabric(mix_cfg.moe.n_experts,
                     expert_capacity(1, mix_cfg.moe), "pallas",
                     kernel_mode=mix_cfg.moe.kernel_mode)
    t = time.perf_counter()
    server.step()                 # every request admitted, first decode
    print(f"first tick (prefill + decode, compiles included): "
          f"{time.perf_counter() - t:.1f}s", flush=True)
    if compile_log is not None:
        compile_log.report("serve warm-up")
    moe_traces = moe.trace_count
    _check(moe_traces > 0, "Mixtral's MoE did not route through the fabric")

    decision = Manager(shell, probes=[server.probe()]).tick()
    server.step()
    victim = next((r for r in shell.placement_of("mixtral") if r >= 0), 0)
    shell.post(FailRegion(rid=victim))
    server.step()
    shell.post(HealRegion(rid=victim))
    t = time.perf_counter()
    server.run()
    print(f"control plane between ticks: manager={list(decision.kinds())} "
          f"FailRegion({victim}) HealRegion({victim}); epoch={shell.epoch}; "
          f"rest of the run {time.perf_counter() - t:.1f}s", flush=True)

    done = {0: 0, 1: 0}
    for c in server.completions:
        _check(len(c.tokens) == max_new, f"request {c.rid} cut short")
        done[c.app_id] += 1
    _check(done == {0: requests_per_tenant, 1: requests_per_tenant},
           f"completions per tenant {done}")
    _check(server.fabric.trace_count == 1,
           f"server fabric traced {server.fabric.trace_count} times")
    _check(moe.trace_count == moe_traces,
           f"MoE fabric retraced: {moe_traces} -> {moe.trace_count}")
    print(f"completions per tenant: tinyllama={done[0]} mixtral={done[1]} "
          f"({max_new} tokens each); server fabric trace_count="
          f"{server.fabric.trace_count}; MoE fabric trace_count="
          f"{moe.trace_count} before and after FailRegion/heal; port "
          f"grants={server.port_traffic.tolist()}", flush=True)

    # Mixtral's first decode step through the pallas fabric vs gather
    engine = server.engine(1)
    gather = build_model(dataclasses.replace(
        mix_cfg, moe=dataclasses.replace(mix_cfg.moe, dispatch="gather")))
    state = engine.model.init_decode_state(1, max_len)
    batch = {"tokens": jnp.asarray(prompts[1][:1][None], jnp.int32)}
    lp, _ = jax.jit(engine.model.decode_step)(engine.params, state, batch)
    lg, _ = jax.jit(gather.decode_step)(engine.params, state, batch)
    err = _rel_err(lp, lg)
    _check(bool(np.isfinite(np.asarray(lp, np.float32)).all()),
           "non-finite Mixtral logits")
    _check(err <= BF16_REL_TOL,
           f"pallas vs gather logits differ by {err:.3g} of their range")
    print(f"mixtral first decode step, dispatch=pallas vs gather: max|diff| "
          f"= {err:.3g} of max|logit| (tolerance {BF16_REL_TOL})",
          flush=True)
    return done


# ----------------------------------------------------------------------
# four chips: expert parallelism
# ----------------------------------------------------------------------
def expert_parallel_phase(*, d_model: int = 4096, d_ff: int = 14336,
                          n_experts: int = 8, batch: int = 8,
                          seq: int = 512, n_chips: int = 4,
                          seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.elastic import Region
    from repro.core.module import ModuleFootprint
    from repro.launch.mesh import make_mesh
    from repro.models.common import init_params
    from repro.models.config import MoEConfig
    from repro.models.moe import (expert_capacity, moe_apply_sharded_reference,
                                  moe_defs, moe_fabric, moe_forward_sharded)
    from repro.shell import FailRegion, Shell, Submit

    moe = MoEConfig(n_experts=n_experts, top_k=2)
    mesh = make_mesh((n_chips,), ("expert",))
    cap = expert_capacity(batch * seq, moe)
    # n_experts crossbar ports = host port + (n_experts - 1) regions.
    fp = ModuleFootprint(param_bytes=GB, flops_per_token=2e9,
                         activation_bytes_per_token=8192)
    shell = Shell([Region(rid=i, n_chips=1, hbm_bytes=16 * GB)
                   for i in range(n_experts - 1)], capacity=cap)
    shell.post(Submit(tenant="moe", footprints=(fp,) * (n_experts - 1),
                      app_id=0))

    defs = moe_defs(d_model, d_ff, moe, "swiglu")
    spread = {"w_router": NamedSharding(mesh, P()),
              "w_in": NamedSharding(mesh, P("expert")),
              "w_out": NamedSharding(mesh, P("expert"))}
    params = jax.jit(lambda k: init_params(defs, k, jnp.bfloat16),
                     out_shardings=spread)(jax.random.key(seed))
    x = jax.random.normal(jax.random.key(seed + 1), (batch, seq, d_model),
                          jnp.bfloat16)
    for name in ("w_in", "w_out"):
        shards = sorted((s.device.id, s.data.shape[0], s.data.nbytes)
                        for s in params[name].addressable_shards)
        total = sum(b for _, _, b in shards)
        print(f"{name} {tuple(params[name].shape)}: per chip "
              + ", ".join(f"chip{d}: {e} experts {b / GB:.3f} GiB "
                          f"({b / total:.0%})" for d, e, b in shards),
              flush=True)
        _check(len(shards) == n_chips
               and all(e == n_experts // n_chips for _, e, _ in shards),
               f"{name} is not spread a quarter per chip: {shards}")

    step = jax.jit(lambda p, regs, xx: moe_forward_sharded(
        p, xx, moe, "swiglu", mesh=mesh, registers=regs, capacity=cap))
    one_chip = jax.devices()[0]
    params_1 = jax.device_put(params, one_chip)
    x_1 = jax.device_put(x, one_chip)
    ref = jax.jit(lambda p, regs, xx: moe_apply_sharded_reference(
        p, xx, moe, "swiglu", n_shards=n_chips, registers=regs,
        capacity=cap))
    fabric = moe_fabric(n_experts, cap, "sharded", "expert")

    traces = None
    for label in ("step 0", "step 1 after FailRegion"):
        regs = shell.registers
        t = time.perf_counter()
        y, stats = jax.block_until_ready(step(params, regs, x))
        dt = time.perf_counter() - t
        yr, sr = ref(params_1, regs, x_1)
        for key in ("counts", "dropped", "granted_packets", "iso_dropped",
                    "remote_packets", "local_packets"):
            _check(np.array_equal(np.asarray(stats[key]),
                                  np.asarray(sr[key])),
                   f"{label}: {key} differs from the one-chip reference")
        err = _rel_err(y, yr)
        _check(err <= BF16_REL_TOL, f"{label}: output differs by {err:.3g}")
        if traces is None:
            traces = fabric.trace_count
            shell.post(FailRegion(rid=1))
        print(f"expert parallel {label}: {batch * seq} tokens, E={n_experts}"
              f", C={cap}, d={d_model}, d_ff={d_ff}: dropped="
              f"{int(stats['dropped'])} remote={int(stats['remote_packets'])}"
              f" local={int(stats['local_packets'])}; max|y - ref| = "
              f"{err:.3g} of max|ref| (tolerance {BF16_REL_TOL}); counters "
              f"equal to the one-chip reference; host clock {dt:.3f}s "
              f"(step 0 includes compile)", flush=True)
    _check(fabric.trace_count == traces,
           f"sharded step retraced across FailRegion: {traces} -> "
           f"{fabric.trace_count}")
    print(f"sharded fabric trace_count={fabric.trace_count} before and after "
          f"FailRegion (no retrace)", flush=True)


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the expert-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    device = device_phase(args.chips)
    compile_log = CompileLog()
    t0 = time.perf_counter()
    if args.chips == 4:
        expert_parallel_phase(n_chips=4, seed=args.seed)
        compile_log.report("expert parallel")
    else:
        kernel_phase(seed=args.seed)
        compile_log.report("kernels")
        tiny, mixtral = tenant_configs()
        print(f"mixtral-8x7b cut: n_layers 32 -> {mixtral.n_layers} "
              f"(full width d={mixtral.d_model}, d_ff={mixtral.d_ff}, "
              f"{mixtral.moe.n_experts} experts top-{mixtral.moe.top_k}, "
              f"SWA {mixtral.attn_window}; moe.dispatch="
              f"{mixtral.moe.dispatch!r})", flush=True)
        serve_phase(tiny, mixtral, seed=args.seed, compile_log=compile_log)
        compile_log.report("serve, after warm-up")
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use (chip 0): {stats.get('peak_bytes_in_use')}; "
          f"wall {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
