"""The readings a cell's correctness limits are set from, on the chip.

    python3 -m bench.calibrate --workload <cell> --seeds 1 2 ... \\
        [--control-seeds 1 2 3] [--seconds 2] [--router]

For each seed, in one process: the cell's timed path (the same compiled
programs, weights, traffic and sample as a run, for a short window), then
the plain reference.  On the control seeds the control (the reference in
float8, in the program's place) is compared with the same reference.
With ``--router`` (a cell with experts) the program's router logits of
the sample are read too (``bench.router_probe``), and how far they lie
from the reference's, which is what ``tie_margin`` is set from.  Prints
one JSON line per seed, then a summary: for each compared number, the
lower reading (the largest program reading) and the upper reading (the
smallest control reading).  The benchmark's own runs never run the
control or the probe.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench import run, spec, traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--router", action="store_true")
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    run.require_chips(cell.chips)
    run.enable_compile_cache()
    probe = None
    if args.router:
        from bench.router_probe import RouterProbe
        probe = RouterProbe()
    driver = traffic.driver(cell.traffic["kind"])
    limits = cell.check["limits"]
    program = {k: [] for k in limits}
    control = {k: [] for k in limits}
    shifts = []
    for line in driver.calibrate(cell, args.seeds, set(args.control_seeds),
                                 args.seconds, probe):
        for k in limits:
            program[k].append(line["program"][k])
            if "control" in line:
                control[k].append(line["control"][k])
        if "router" in line:
            shifts.append(line["router"]["shift"])
        print(json.dumps(line), flush=True)
    summary = {"workload": cell.name, "seeds": len(args.seeds),
               "numbers": {k: {"lower": max(program[k]),
                               "upper": min(control[k], default=None),
                               "limit": limits[k]} for k in limits}}
    if shifts:
        summary["router_shift"] = max(shifts)
        summary["tie_margin"] = cell.check["tie_margin"]
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
