"""MoE dispatch-implementation bench: dense vs gather vs fabric backends
vs mesh-sharded expert parallelism, over a T x experts grid.

Rows land in the machine-readable ``BENCH_moe.json`` trajectory (written
by ``benchmarks/run.py``), so dispatch-path regressions show up PR over
PR.  Everything runs in this process when it has 4 devices; on a CPU
host with one, the ``sharded`` rows run in a child with a forced 4-device
CPU topology (jax pins the device count at first init).  CPU wall time:
the trajectory tracks *relative* dispatch cost, not chip performance.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

from benchmarks.timing import time_us

# Small grid — this doubles as the CI smoke bench.
SHAPES = [(256, 4), (512, 8)]            # (T tokens, n_experts)
D, D_FF = 32, 64
TOP_K = 2
CAPACITY_FACTOR = 2.0                    # ample: all impls agree exactly
IMPLS = ["dense", "gather", "reference", "pallas"]
GRAD_SHAPE = (512, 8)                    # (T, E) for the train-grad rows
GRAD_IMPLS = ["gather", "dense", "reference", "pallas"]
N_SHARDS = 4


def _sharded_rows_here() -> List[dict]:
    """Time ``moe_forward_sharded`` over this process's first
    ``N_SHARDS`` devices."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh
    from repro.models.common import init_params
    from repro.models.config import MoEConfig
    from repro.models.moe import (expert_capacity, moe_defs,
                                  moe_forward_sharded)

    rows = []
    for T, E in SHAPES:
        moe = MoEConfig(n_experts=E, top_k=TOP_K,
                        capacity_factor=CAPACITY_FACTOR)
        params = init_params(moe_defs(D, D_FF, moe, "swiglu"),
                             jax.random.key(0), jnp.float32)
        B = N_SHARDS * 2
        x = jax.random.normal(jax.random.key(1), (B, T // B, D))
        mesh = make_mesh((N_SHARDS,), ("expert",))
        cap = expert_capacity(T, moe)
        fn = jax.jit(lambda p, xx, moe=moe, mesh=mesh, cap=cap:
                     moe_forward_sharded(p, xx, moe, "swiglu", mesh=mesh,
                                         capacity=cap))
        us = time_us(fn, params, x)
        y, stats = fn(params, x)
        rows.append({
            "impl": "sharded", "T": T, "E": E, "d": D,
            "forward_us": round(us, 1),
            "tokens_per_s": round(T / (us * 1e-6)),
            "dropped": int(stats["dropped"]),
            "remote_packets": int(stats["remote_packets"]),
            "local_packets": int(stats["local_packets"]),
        })
    return rows


def _sharded_rows() -> Tuple[List[dict], str]:
    """The sharded impl's rows, one process per chip.

    With ``N_SHARDS`` devices in this process the rows are timed here.
    Under ``JAX_PLATFORMS=cpu`` with fewer, a child process with a forced
    ``N_SHARDS``-device host topology times them (jax pins the device
    count at first init); a child that fails raises.  On an accelerator
    with fewer devices there is no sharded row: the parent holds the
    chip, so no child could reach it."""
    import jax

    if jax.device_count() >= N_SHARDS:
        return _sharded_rows_here(), (
            f"{N_SHARDS} of {jax.device_count()} "
            f"{jax.devices()[0].platform} devices (in process)")
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return [], (f"not measured: needs {N_SHARDS} devices, "
                    f"{jax.device_count()} present")
    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count"
                        f"={N_SHARDS}")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(repo / "src"), str(repo), env.get("PYTHONPATH", "")])
    code = ("import json\n"
            "from benchmarks.moe_bench import _sharded_rows_here\n"
            "print(json.dumps(_sharded_rows_here()))\n")
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                         capture_output=True, text=True, timeout=600)
    if res.returncode != 0:
        raise RuntimeError(f"sharded MoE bench child failed "
                           f"(rc={res.returncode}): {res.stderr[-2000:]}")
    rows = json.loads(res.stdout.strip().splitlines()[-1])
    return rows, f"forced {N_SHARDS}-device CPU topology (subprocess)"


def _train_grad_rows() -> List[dict]:
    """Backward-pass rows: one optimizer-style grad per dispatch impl at
    ``GRAD_SHAPE``.  The fabric-routed grad rides the custom VJP (backward
    replays the flat ``dst*C+slot`` address route), so it must price like
    the inline-gather grad, not like the dense one-hot grad — the CI gate
    reads ``vs_gather_grad`` within this file (machine-neutral) and pins
    ``bwd_dense_routing_bytes == 0``: the compiled backward HLO contains
    no [T*k, E*C]-sized routing intermediate (the dense rows show the
    detector firing on the formulation that does materialize one)."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.launch.roofline import dense_routing_bytes
    from repro.models.common import init_params
    from repro.models.config import MoEConfig
    from repro.models.moe import expert_capacity, moe_apply, moe_defs

    T, E = GRAD_SHAPE
    moe = MoEConfig(n_experts=E, top_k=TOP_K,
                    capacity_factor=CAPACITY_FACTOR)
    params = init_params(moe_defs(D, D_FF, moe, "swiglu"),
                         jax.random.key(0), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (8, T // 8, D))
    cap = expert_capacity(T, moe)

    def loss(p, xx, impl):
        y, stats = moe_apply(p, xx, moe, "swiglu", group_size=T,
                             dispatch_impl=impl)
        return jnp.sum(y * y) + stats["aux_loss"]

    rows: List[dict] = []
    base = None
    for impl in GRAD_IMPLS:
        fwd = jax.jit(functools.partial(
            lambda p, xx, i: loss(p, xx, i), i=impl))
        fn = jax.jit(functools.partial(
            lambda p, xx, i: jax.grad(loss)(p, xx, i), i=impl))
        fwd_us = time_us(fwd, params, x)
        us = time_us(fn, params, x)
        hlo = fn.lower(params, x).compile().as_text()
        grads = jax.tree.leaves(fn(params, x))
        if base is None:
            base = grads                 # first impl (gather) is the probe
        agrees = all(np.allclose(np.asarray(g), np.asarray(b),
                                 rtol=2e-4, atol=2e-5)
                     for g, b in zip(grads, base))
        rows.append({
            "mode": "train_grad", "impl": impl, "T": T, "E": E, "d": D,
            "forward_loss_us": round(fwd_us, 1),
            "grad_us": round(us, 1),
            "tokens_per_s": round(T / (us * 1e-6)),
            # packet count is T * top_k: each token is routed k times
            "bwd_dense_routing_bytes": dense_routing_bytes(
                hlo, T * TOP_K, E * cap),
            "grad_agrees": agrees,
        })
    gfloor = next(r["grad_us"] for r in rows if r["impl"] == "gather")
    ffloor = next(r["forward_loss_us"] for r in rows
                  if r["impl"] == "gather")
    for r in rows:
        r["vs_gather_grad"] = round(r["grad_us"] / gfloor, 3)
        r["vs_gather_fwd"] = round(r["forward_loss_us"] / ffloor, 3)
        # The gated claim: whatever forward overhead an impl carries
        # (WRR plan arbitration, interpret-mode kernels), its *backward*
        # adds none on top — grad ratio stays within the forward ratio.
        r["bwd_overhead"] = round(r["vs_gather_grad"]
                                  / max(r["vs_gather_fwd"], 1e-9), 3)
    return rows


def bench_moe() -> Tuple[List[dict], Dict[str, str]]:
    import jax
    import jax.numpy as jnp

    from repro.models.common import init_params
    from repro.models.config import MoEConfig
    from repro.models.moe import moe_apply, moe_defs

    rows: List[dict] = []
    for T, E in SHAPES:
        moe = MoEConfig(n_experts=E, top_k=TOP_K,
                        capacity_factor=CAPACITY_FACTOR)
        params = init_params(moe_defs(D, D_FF, moe, "swiglu"),
                             jax.random.key(0), jnp.float32)
        x = jax.random.normal(jax.random.key(1), (8, T // 8, D))
        base = None
        for impl in IMPLS:
            fn = jax.jit(lambda p, xx, i=impl: moe_apply(
                p, xx, moe, "swiglu", group_size=T, dispatch_impl=i))
            us = time_us(fn, params, x)
            y, stats = fn(params, x)
            y = np.asarray(y)
            if base is None:
                base = y
            rows.append({
                "impl": impl, "T": T, "E": E, "d": D,
                "forward_us": round(us, 1),
                "tokens_per_s": round(T / (us * 1e-6)),
                "dropped": int(stats["dropped"]),
                "agrees_dense": bool(np.allclose(y, base, atol=2e-4)),
            })
    sharded, sharded_note = _sharded_rows()
    rows.extend(sharded)
    rows.extend(_train_grad_rows())
    # Gather-relative cost per (T, E): the inline gather baseline is the
    # floor a fabric-routed impl should approach — the CI gate reads this.
    gather_us = {(r["T"], r["E"]): r["forward_us"] for r in rows
                 if r["impl"] == "gather" and "forward_us" in r}
    for r in rows:
        floor = gather_us.get((r["T"], r["E"]))
        if floor and "forward_us" in r:
            r["vs_gather"] = round(r["forward_us"] / floor, 2)
    claims = {
        "note": ("CPU wall time (pallas in interpret mode); ample "
                 "capacity so every impl routes identically"),
        "timing": "warmup + median of 5 device-synced samples",
        "vs_gather": ("forward_us relative to the inline gather baseline "
                      "at the same (T, E)"),
        "train_grad": ("one jit(grad(loss)) step per dispatch impl at "
                       f"(T, E)={GRAD_SHAPE}; the fabric-routed grad rides "
                       "the custom VJP so bwd_overhead (grad-vs-gather "
                       "normalized by the impl's own forward-vs-gather) "
                       "must stay near 1.0 and bwd_dense_routing_bytes at "
                       "0 (no dense [T*k, E*C] routing tensor in the "
                       "backward HLO) — gated by "
                       "tools/check_bench_regression.py --moe-json"),
        "device_count": str(jax.device_count()),
        "sharded": sharded_note,
    }
    return rows, claims
