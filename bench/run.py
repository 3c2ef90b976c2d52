"""Run one benchmark cell once and print its result as one JSON line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled window.  Both check the timed path's
output against the plain reference and print each number compared beside
its limit, as the last lines on standard error and under ``checks``, the
last key of the result.  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                      # noqa: E402
import contextlib                    # noqa: E402
import gc                            # noqa: E402
import glob                          # noqa: E402
import json                          # noqa: E402
import math                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402
import tempfile                      # noqa: E402
from pathlib import Path             # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

from bench import spec               # noqa: E402
from bench import trace as tr        # noqa: E402
from bench import traffic            # noqa: E402
from bench.peaks import peaks_for    # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_SECONDS = 4.0      # longest profiled window, unless the mix sets one


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    whatever the environment names, so that two checkouts share nothing;
    the environment variable is pointed there too, for program code that
    reads it.  Every program is cached, however short its compile."""
    import jax

    path = str(CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(chips: int) -> dict:
    """The devices JAX found; exits without a result unless they are at
    least ``chips`` TPU chips of a kind the peak table knows."""
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found "
                         f"{devs[0].platform!r} ({kind})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    peaks_for(kind)
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs)}


class Clock:
    """Set-up time since process start, compilations inside the window,
    and the devices' peak memory."""

    def __init__(self, t0: float):
        import jax

        self.t0 = t0
        self.in_window = False
        self.window_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.in_window and event.endswith("backend_compile_duration"):
            self.window_compiles += 1

    def setup_done(self) -> float:
        return time.perf_counter() - self.t0

    def end_setup(self) -> float:
        """Set-up's seconds, after a full collection whose survivors (the
        weights, traced programs, the traffic) are frozen out of later
        ones: a collection pass over them took seconds and could fall
        inside the window."""
        gc.collect()
        gc.freeze()
        return self.setup_done()

    @contextlib.contextmanager
    def window(self):
        self.in_window = True
        try:
            yield
        finally:
            self.in_window = False

    @staticmethod
    def memory_peak():
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        peaks = [s["peak_bytes_in_use"] for s in stats
                 if "peak_bytes_in_use" in s]
        return max(peaks) if peaks else None


def per_layer(cell, out: dict, record: dict, device_kind: str) -> tuple:
    """(metrics, trace summary) of a traced run."""
    readers = {m["name"]: spec.metric_reader(m["name"])
               for m in cell.per_layer}
    paths = {}
    for r in readers.values():
        paths.update(getattr(r, "PATHS", {}))
    summary = tr.summarize(record, paths)
    ctx = dict(out, trace=summary, peaks=peaks_for(device_kind),
               chips=cell.chips, cell=cell)
    units = {m["name"]: m["unit"] for m in cell.per_layer}
    metrics = {}
    for name, reader in readers.items():
        value = reader.read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    return metrics, summary


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             device: dict, t0: float = T_START, step_wrapper=None) -> dict:
    """One run of ``cell``: the result object (without printing it)."""
    driver = traffic.driver(cell.traffic["kind"])
    clock = Clock(t0)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            seconds = min(seconds, cell.traffic.get("trace_seconds",
                                                    TRACE_SECONDS))
        out = driver.run(cell, seed, seconds, trace=trace, clock=clock,
                         step_wrapper=step_wrapper, trace_dir=trace_dir)
        record = None
        if trace:
            found = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
            if not found:
                raise RuntimeError("the profiler wrote no trace")
            record = tr.load(found[0], out["hlo"] or ())
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"setup_s={out['setup_s']:.3f} steps={out['steps']} "
        f"window_s={out['window_s']:.3f} compiles_in_window="
        f"{clock.window_compiles} memory_peak_bytes="
        f"{out['memory_peak_bytes']} {out.get('notes', {})}")
    dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        metrics, summary = per_layer(cell, out, record, device["kind"])
        dev.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result.update(metrics=metrics, device=dev,
                      breakdown=summary["breakdown"])
    else:
        values = dict(out["e2e"], setup_s=out["setup_s"])
        result.update(metrics={m["name"]: {"value": values[m["name"]],
                                           "unit": m["unit"]}
                               for m in cell.end_to_end},
                      device=dev)
    result["checks"] = {k: {"value": v if math.isfinite(v) else None,
                            "limit": lim}
                        for k, (v, lim) in out["checks"].items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    device = require_chips(cell.chips)
    log(f"compile cache: {enable_compile_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device=device)
    for name, c in result["checks"].items():
        log(f"check {name}: value={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
