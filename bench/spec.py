"""Cells, found by name: ``BENCHMARK.json`` at the root of the checkout,
``bench/configs``, ``bench/traffic``, ``bench/cells``, ``bench/metrics``."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]        # bench/configs/<config>.json
    traffic: Dict[str, Any]       # bench/traffic/<mix>.json
    check: Dict[str, Any]         # bench/cells/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def model(self) -> Dict[str, Any]:
        """The program's model configuration, as run."""
        return self.config["model"]


def _read(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    return _read(ROOT / "BENCHMARK.json")


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Dict[str, Any] = None) -> Cell:
    from bench import traffic

    bench = bench or load_benchmark()
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    return Cell(name=name, chips=wl["chips"],
                config=_read(ROOT / conf["file"]),
                traffic=traffic.load(wl["traffic"]),
                check=_read(BENCH_DIR / "cells" / f"{name}.json"),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def metric_reader(name: str):
    """The module ``bench/metrics/<name>.py`` (names may hold dots).  A
    quantity split by the end-to-end metric it moves, ``<quantity>.<part>``,
    shares the reader ``bench/metrics/<quantity>.py`` unless it has one of
    its own."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.is_file():
        path = BENCH_DIR / "metrics" / f"{name.split('.')[0]}.py"
    mod_name = "bench.metrics." + path.stem.replace(".", "__")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(model: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration's model block."""
    from repro.models.config import ModelConfig, MoEConfig

    kw = dict(model)
    if kw.get("moe"):
        kw["moe"] = MoEConfig(**kw["moe"])
    return ModelConfig(**kw)
