"""End-to-end driver: train a ~100M-parameter MoE LM for a few hundred steps.

The MoE layer routes tokens to experts through the paper's crossbar
mechanism: the WRR package quota is the expert capacity, the isolation mask
restricts which experts this tenant may use, and drop statistics surface the
paper's error codes. Training runs the full production substrate — data
pipeline (prefetching), AdamW + cosine schedule, async checkpointing,
step watchdog — and asserts the loss actually falls.

The run is registered as a tenant on a ``repro.shell.Shell``: the step
watchdog is attached to the shell, so a blown deadline surfaces as a
``WatchdogTimeout`` event on the shell's log instead of needing the caller
to poll ``loop.watchdog.events``.

    PYTHONPATH=src python examples/moe_training.py [--steps 300]

``--sharded`` instead demos **mesh expert parallelism**: the process
re-execs itself onto a forced multi-device CPU topology (``--devices``,
default 4) and runs the MoE layer through the sharded fabric backend
inside a shard_map — experts partitioned across the mesh axis, tokens
crossing it via the global-WRR all_to_all, and a live ``Shell`` rewriting
the register file between jitted steps with zero retraces.

    PYTHONPATH=src python examples/moe_training.py --sharded
"""
import argparse
import dataclasses
import os
import sys
import time
from pathlib import Path

_DEMO_ENV = "REPRO_MOE_SHARDED_DEMO"

if "--sharded" in sys.argv and _DEMO_ENV not in os.environ:
    # jax pins the device count at first init, so the sharded demo re-execs
    # with the forced topology in place before anything imports jax.
    n = "4"
    for i, arg in enumerate(sys.argv):
        if arg == "--devices" and i + 1 < len(sys.argv):
            n = sys.argv[i + 1]
        elif arg.startswith("--devices="):
            n = arg.split("=", 1)[1]
    env = dict(os.environ, **{_DEMO_ENV: "1"})
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n}")
    os.execve(sys.executable, [sys.executable] + sys.argv, env)

from repro.configs import get_config
from repro.core.elastic import Region
from repro.core.module import ModuleFootprint
from repro.models.config import ModelConfig, MoEConfig
from repro.models.lm import build_model
from repro.runtime.train import TrainLoop, TrainLoopConfig
from repro.shell import Shell, Submit

# ~100M-param MoE: 8 layers, d=512, 8 experts (top-2), d_ff=1408.
MOE_100M = ModelConfig(
    name="moe-100m", family="moe", n_layers=8, d_model=512,
    n_heads=8, n_kv_heads=4, d_ff=1408, vocab=32000,
    attn_window=1024, moe=MoEConfig(n_experts=8, top_k=2),
    remat="nothing")


def sharded_demo(n_devices: int) -> None:
    """Expert parallelism on a mesh: MoE dispatch == sharded crossbar."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_mesh
    from repro.models.common import init_params
    from repro.models.moe import (moe_defs, moe_fabric, moe_forward_sharded)
    from repro.shell import FailRegion, Grow, Shell

    assert jax.device_count() == n_devices, "re-exec did not take"
    E = n_devices                       # 1 expert port per shard
    moe = MoEConfig(n_experts=E, top_k=2, capacity_factor=2.0)
    d = 64
    params = init_params(moe_defs(d, 128, moe, "swiglu"),
                         jax.random.key(0), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (n_devices * 2, 32, d))
    mesh = make_mesh((n_devices,), ("expert",))
    CAP = 256

    # Control plane: E crossbar ports = host + (E-1) regions; the MoE's
    # experts ride the shell's own register file.
    GB = 1 << 30
    shell = Shell([Region(rid=i, n_chips=8, hbm_bytes=8 * GB)
                   for i in range(E - 1)], capacity=CAP)
    shell.submit("moe", [ModuleFootprint(GB, 1e9, 4096)] * (E - 1),
                 app_id=0)
    fabric = moe_fabric(E, CAP, "sharded", "expert")

    step = jax.jit(lambda p, regs, xx: moe_forward_sharded(
        p, xx, moe, "swiglu", mesh=mesh, registers=regs, capacity=CAP))

    print(f"== sharded MoE: {E} experts across {n_devices} devices ==")
    y, stats = step(params, shell.registers, x)
    jax.block_until_ready(y)
    fabric.account_stats(stats)
    t0 = fabric.trace_count
    print(f"   step 0: granted={int(stats['granted_packets'])} "
          f"remote={int(stats['remote_packets'])} "
          f"local={int(stats['local_packets'])} traces={t0}")

    shell.post(FailRegion(rid=0))        # expert port 1 held in reset
    y, stats = step(params, shell.registers, x)
    jax.block_until_ready(y)
    fabric.account_stats(stats)
    counts = np.asarray(stats["counts"])
    print(f"   after FailRegion(0): expert-port grants={counts.tolist()} "
          f"dropped={int(stats['dropped'])} traces={fabric.trace_count}")

    shell.post(Grow(tenant="moe"))       # no-op grow (already full) + heal
    shell.heal_region(0)
    y, stats = step(params, shell.registers, x)
    jax.block_until_ready(y)
    fabric.account_stats(stats)
    print(f"   after HealRegion(0): dropped={int(stats['dropped'])} "
          f"traces={fabric.trace_count}")
    assert fabric.trace_count == t0, "reconfiguration must not retrace"
    print(f"   register epochs seen: {shell.epoch + 1}, retraces: {t0} "
          f"(zero per reconfiguration)")
    print(f"   cumulative fabric counters: offered="
          f"{fabric.offered_packets} granted={fabric.granted_packets} "
          f"remote={fabric.remote_packets} local={fabric.local_packets}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/elastix_moe_ckpt")
    ap.add_argument("--sharded", action="store_true",
                    help="run the mesh expert-parallelism demo instead of "
                         "the training loop (re-execs with a forced "
                         "multi-device CPU topology)")
    ap.add_argument("--devices", type=int, default=4)
    args = ap.parse_args()

    if args.sharded:
        sharded_demo(args.devices)
        return

    model = build_model(MOE_100M)
    print(f"model: {MOE_100M.name}  params={model.n_params()/1e6:.1f}M "
          f"({MOE_100M.moe.n_experts} experts, top-{MOE_100M.moe.top_k})")

    # Control plane: the training job is a tenant on the elastic shell; the
    # step watchdog posts WatchdogTimeout events here (no polling).
    GB = 1 << 30
    shell = Shell([Region(rid=i, n_chips=16, hbm_bytes=8 * GB)
                   for i in range(2)])
    shell.post(Submit(
        tenant="moe-train",
        footprints=(ModuleFootprint(
            param_bytes=model.n_params() * 4, flops_per_token=6e9,
            activation_bytes_per_token=MOE_100M.d_model * 4),),
        app_id=0))
    print(f"shell: tenant 'moe-train' placed at "
          f"{shell.placement_of('moe-train')}")

    run = TrainLoopConfig(steps=args.steps, global_batch=args.batch,
                          seq_len=args.seq, lr=6e-4, warmup=30,
                          ckpt_every=100, log_every=10, seed=0)
    t0 = time.time()
    loop = TrainLoop(MOE_100M, run, ckpt_dir=Path(args.ckpt),
                     on_log=lambda r: print(
                         f"  step {r['step']:4d}  loss {r['loss']:.4f}  "
                         f"({r['step_s']:.2f}s)"),
                     shell=shell)
    hist = loop.run_loop()
    dt = time.time() - t0

    first = hist[0]["loss"]
    last = min(h["loss"] for h in hist[-3:])
    tok_s = args.steps * args.batch * args.seq / dt
    print(f"\nloss {first:.4f} -> {last:.4f} over {args.steps} steps "
          f"({dt:.0f}s, {tok_s:,.0f} tok/s on CPU)")
    assert last < first - 0.3, "training did not converge"
    print("checkpoints:", sorted(p.name for p in Path(args.ckpt).iterdir()))
    timeouts = [e for e in shell.log
                if type(e.event).__name__ == "WatchdogTimeout"]
    print(f"shell log: {len(shell.log)} events "
          f"({len(timeouts)} watchdog timeouts)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
