"""Traffic mixes, found by name.

A mix is a data file of parameters, ``bench/traffic/<mix>.json``.  Its
``kind`` names the driver that reads it, ``bench/drivers/<kind>.py``:
the driver checks the mix (``validate``), generates its requests from the
seed and drives the timed path.  Token ids come from a distribution found
by name, ``bench/tokens/<dist>.py``: the mix's ``"tokens"`` is the name,
or an object whose ``"dist"`` is the name and whose other keys are the
distribution's parameters; "uniform" when left out.  A new kind of
traffic, or a new distribution, is a new file; nothing here changes.
"""
from __future__ import annotations

import functools
import importlib
import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
TRAFFIC_DIR = BENCH_DIR / "traffic"
TOKENS_DIR = BENCH_DIR / "tokens"
_NAME = re.compile(r"^[A-Za-z0-9_]+$")


def load(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        mix = json.load(f)
    validate(mix)
    return mix


def driver(kind: str):
    """The module ``bench/drivers/<kind>.py``."""
    if not isinstance(kind, str) or not _NAME.match(kind):
        raise ValueError(f"a traffic mix needs a kind naming its driver: "
                         f"{kind!r}")
    try:
        return importlib.import_module(f"bench.drivers.{kind}")
    except ModuleNotFoundError as e:
        if e.name != f"bench.drivers.{kind}":
            raise
        raise ValueError(f"no driver bench/drivers/{kind}.py for traffic "
                         f"of kind {kind!r}") from None


def validate(mix: dict) -> None:
    """The mix's driver checks it, and its token distribution exists."""
    driver(mix.get("kind")).validate(mix)
    tokens(mix)


def tokens(mix: dict):
    """(module ``bench/tokens/<dist>.py``, its parameters) of a mix."""
    spec = mix.get("tokens", "uniform")
    params = {} if isinstance(spec, str) else dict(spec)
    name = spec if isinstance(spec, str) else params.pop("dist", None)
    mod = _token_module(name)
    getattr(mod, "validate", lambda p: None)(params)
    return mod, params


@functools.lru_cache(maxsize=None)
def _token_module(name):
    path = TOKENS_DIR / f"{name}.py"
    if not isinstance(name, str) or not _NAME.match(name) or not path.is_file():
        raise ValueError(f"no token distribution bench/tokens/{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench.tokens.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rng(seed: int, *stream: int):
    """A host generator for ``seed`` (any size) and a stream of indices."""
    import numpy as np
    return np.random.default_rng([seed % 2**64, *stream])
