"""Reproduce the paper's experiments end to end (Fig 5, §V-D, §V-E, Fig 6).

Runs the calibrated full-system model, prints each reproduced number next to
the paper's, and cross-checks the data path against the Pallas kernels.

    PYTHONPATH=src python examples/paper_usecase.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core.hw.area import AreaModel
from repro.core.hw.crossbar import (CrossbarSim, MasterRequest,
                                    best_case_time_to_grant,
                                    request_completion_cc,
                                    worst_case_completion_cc,
                                    worst_case_time_to_grant)
from repro.core.hw.system import (ElasticUseCase, PAPER_CASE1_MS,
                                  PAPER_CASE3_MS)
from repro.kernels.hamming.ops import (hamming_decode, hamming_encode,
                                       multiply_const)


def main():
    print("== Fig 5: elasticity use case (16 KB, 3 modules) ==")
    uc = ElasticUseCase()
    fig5 = uc.figure5()
    print(f"   case 1 (mult on FPGA):        {fig5[1]:6.2f} ms   "
          f"(paper: {PAPER_CASE1_MS})")
    print(f"   case 2 (+encoder):            {fig5[2]:6.2f} ms   "
          f"(paper: between)")
    print(f"   case 3 (all three on FPGA):   {fig5[3]:6.2f} ms   "
          f"(paper: {PAPER_CASE3_MS})")

    print("\n== §V-D: dynamic bandwidth allocation (quota 16 -> 128) ==")
    bw = uc.bandwidth_table()
    print(f"   1 accelerator: {100*bw[1]:.2f}%  (paper: 5.24%)")
    print(f"   3 accelerators: {100*bw[3]:.2f}%  (paper: 6%)")
    print(f"   calibration residuals: "
          f"{ {k: round(v, 4) for k, v in uc.calibration_residuals.items()} }")

    print("\n== §V-E: communication overhead ==")
    print(f"   best-case time-to-grant:      {best_case_time_to_grant()} cc "
          f"(paper: 4)")
    print(f"   completion, 8 packages:       {request_completion_cc(8)} cc "
          f"(paper: 13)")
    print(f"   worst-case grant, 3 masters:  {worst_case_time_to_grant(3)} cc"
          f" (paper: 28)")
    print(f"   worst-case completion:        {worst_case_completion_cc(3)} cc"
          f" (paper: 37)")

    sim = CrossbarSim()
    for m in (0, 1, 2):
        sim.submit(MasterRequest(cycle=0, master=m, dst_onehot=0b1000,
                                 n_words=8))
    results = sim.run()
    print(f"   cycle-sim check: grants={sorted(r.time_to_grant for r in results)}"
          f" completions={sorted(r.completion_latency for r in results)}")

    print("\n== Fig 6: worst-case latency vs contending PR regions ==")
    curve = AreaModel.worst_case_latency_curve(8)
    print("   " + "  ".join(f"{n}:{cc}cc" for n, cc in curve.items()))

    print("\n== Table II claims ==")
    m = AreaModel()
    print(f"   LUT saving vs NoC:  {100*m.lut_saving_vs_noc():.1f}% "
          f"(paper: 61%)")
    print(f"   FF saving vs NoC:   {100*m.ff_saving_vs_noc():.1f}% "
          f"(paper: 95%)")
    print(f"   power vs NoC:       {m.power_ratio_vs_noc():.0f}x "
          f"(paper: 80x)")
    print(f"   completion saving vs NoC (4-router path): "
          f"{100*m.latency_saving_vs_noc(4):.1f}% (paper headline: 69%)")

    print("\n== data-path cross-check: cycle sim vs Pallas kernels ==")
    res = uc.run_case(3)
    data = np.random.default_rng(0).integers(0, 1 << 26, size=uc.n_words,
                                             dtype=np.uint32)
    x = multiply_const(jnp.asarray(data), uc.constant)
    x = hamming_encode(x)
    x, _ = hamming_decode(x)
    print(f"   identical output: "
          f"{bool(np.array_equal(np.asarray(x), res.output))}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
