"""Cells, found by name: ``BENCHMARK.json`` at the root of the checkout,
``bench/configs``, ``bench/arch``, ``bench/traffic``, ``bench/cells``,
``bench/metrics``."""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re
import typing
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
ARCH_DIR = BENCH_DIR / "arch"
_NAME = re.compile(r"^[A-Za-z0-9_]+$")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]        # bench/configs/<config>.json
    arch: ModuleType              # bench/arch/<config's arch>.py
    traffic: Dict[str, Any]       # bench/traffic/<mix>.json
    check: Dict[str, Any]         # bench/cells/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def model(self) -> Dict[str, Any]:
        """The program's model configuration, as run."""
        return self.config["model"]

    def weights(self, seed: int):
        """The model's weights for ``seed``: the architecture's layout,
        drawn on the device in the served dtype."""
        from bench import weights

        return weights.generate(self.arch.layout(self.model), seed,
                                self.model.get("dtype", "bfloat16"))


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
    config = _read(ROOT / conf["file"])
    if "arch" not in config:
        raise ValueError(f"configuration {conf['file']} names no "
                         f"architecture (\"arch\"); known: "
                         f"{architectures()}")
    return Cell(name=name, chips=wl["chips"], config=config,
                arch=architecture(config["arch"]),
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
    return _module(path, "bench.metrics." + path.stem.replace(".", "__"))


def _module(path: Path, mod_name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def architectures() -> List[str]:
    """The names of the architectures ``bench/arch`` holds."""
    return sorted(p.stem for p in ARCH_DIR.glob("*.py")
                  if p.stem != "__init__")


def architecture(name: str) -> ModuleType:
    """The module ``bench/arch/<name>.py`` (its contract:
    ``bench/arch/__init__.py``)."""
    path = ARCH_DIR / f"{name}.py"
    if (not isinstance(name, str) or not _NAME.match(name)
            or name == "__init__" or not path.is_file()):
        raise ValueError(f"no architecture bench/arch/{name}.py; known: "
                         f"{architectures()}")
    return _arch_module(path)


@functools.lru_cache(maxsize=None)
def _arch_module(path: Path) -> ModuleType:
    return _module(path, f"bench.arch.{path.stem}")


def model_config(model: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration's model block."""
    from repro.models.config import ModelConfig

    return build(ModelConfig, model)


def build(cls, kw: Dict[str, Any]):
    """The dataclass ``cls`` from a JSON object: each nested object
    becomes the dataclass its field names (``MoEConfig`` for
    ``Optional[MoEConfig]``), and each list a tuple."""
    hints = typing.get_type_hints(cls)
    return cls(**{k: _field(hints.get(k), v, k) for k, v in kw.items()})


def _field(hint, value, key: str):
    if isinstance(value, list):
        return tuple(_field(hint, v, key) for v in value)
    if not isinstance(value, dict):
        return value
    cls = _dataclass_in(hint)
    if cls is None:
        raise ValueError(f"{key!r} holds an object, but its field is not "
                         f"a dataclass: {hint}")
    return build(cls, value)


def _dataclass_in(hint):
    """The dataclass a type hint names, also inside ``Optional`` or
    ``Tuple``; None if it names none."""
    if dataclasses.is_dataclass(hint):
        return hint
    for arg in typing.get_args(hint):
        found = _dataclass_in(arg)
        if found is not None:
            return found
    return None
