"""Quickstart: the paper's mechanism in five minutes.

1. Build a crossbar register file (Table III).
2. Route packets through ``repro.fabric.Fabric`` — the quota-arbitrated,
   isolation-checked dispatch behind one API, with the backend (dense
   reference oracle vs blockwise Pallas kernels) a constructor argument.
3. Reconfigure bandwidth at runtime by rewriting registers — no recompile
   (``fabric.trace_count`` proves it).
4. Run the paper's own three modules (multiplier -> Hamming encoder ->
   decoder) through the Pallas kernels, end to end, bit-exactly.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.registers import CrossbarRegisters, ErrorCode
from repro.fabric import Fabric
from repro.kernels.hamming.ops import (hamming_decode, hamming_encode,
                                       multiply_const)


def main():
    # ------------------------------------------------------------------
    print("== 1. A 4-port crossbar register file (Table III) ==")
    regs = CrossbarRegisters.create(n_ports=4, capacity=16)
    # Tenant isolation: port 1 may only talk to ports 1 and 2 (one-hot AND).
    regs = regs.with_isolation(src=1, allowed_dsts=[1, 2])
    # Bandwidth allocation: master 0 may send at most 4 packages to slave 2.
    regs = regs.with_quota(dst=2, src=0, packages=4)
    print(f"   version={int(regs.version)} (each ERM write bumps it)")

    # ------------------------------------------------------------------
    print("== 2. One data-plane API, pluggable backends ==")
    T, D = 32, 8
    x = jnp.arange(T * D, dtype=jnp.float32).reshape(T, D)
    dst = jnp.asarray([2] * 8 + [3] * 8 + [2] * 8 + [0] * 8, jnp.int32)
    src = jnp.asarray([0] * 16 + [1] * 16, jnp.int32)
    live = {"regs": regs}
    fabric = Fabric(lambda: live["regs"], backend="reference", capacity=16)
    plan = fabric.plan(dst, src)
    drops = np.asarray(plan.drops)
    print(f"   granted={int(plan.keep.sum())}/{T}  "
          f"errors: INVALID_DEST={drops[ErrorCode.INVALID_DEST]} "
          f"GRANT_TIMEOUT={drops[ErrorCode.GRANT_TIMEOUT]}")
    # src 0 -> dst 2 is quota-limited to 4; src 1 -> dst 3 violates isolation.
    kernels = Fabric(lambda: live["regs"], backend="pallas", capacity=16)
    same = bool((kernels.plan(dst, src).slot == plan.slot).all())
    print(f"   pallas backend plan-identical: {same}")

    # ------------------------------------------------------------------
    print("== 3. Reconfigure at runtime (the ERM write path) ==")
    double = lambda slabs: slabs * 2.0                        # noqa: E731
    fabric.transfer(x, dst, src, apply_fn=double)             # compile once
    traces = fabric.trace_counts["transfer"]
    live["regs"] = regs.with_quota(dst=2, src=0, packages=0)  # 0 = unlimited
    y, plan2 = fabric.transfer(x, dst, src, apply_fn=double)  # same program
    print(f"   after quota lift: granted={int(plan2.keep.sum())}/{T}  "
          f"(transfer retraces during reconfig: "
          f"{fabric.trace_counts['transfer'] - traces})")

    # the fused round-trip returned module results in packet order
    ok = bool(jnp.allclose(y, x * 2.0 * plan2.keep[:, None]))
    print(f"   transfer round-trip exact: {ok}")

    # ------------------------------------------------------------------
    print("== 4. The paper's module chain on the Pallas kernels ==")
    data = np.random.default_rng(0).integers(
        0, 1 << 26, size=4096, dtype=np.uint32)           # 16 KB (§V-C)
    out = multiply_const(jnp.asarray(data), 3)
    out = hamming_encode(out)
    decoded, corrected = hamming_decode(out)
    expect = (data.astype(np.uint64) * 3).astype(np.uint32) \
        & np.uint32((1 << 26) - 1)
    print(f"   16 KB through multiply->encode->decode: "
          f"bit-exact={bool(np.array_equal(np.asarray(decoded), expect))}, "
          f"spurious corrections={int(np.asarray(corrected).sum())}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
