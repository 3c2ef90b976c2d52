"""BENCHMARK.json and the files it names keep to the benchmark contract."""
import json
import math
import re
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
# published key -> the program's name for it
HF_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads",
           "intermediate_size": "d_ff", "vocab_size": "vocab",
           "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
           "tie_word_embeddings": "tied_embeddings", "torch_dtype": "dtype"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])


def test_run_seconds_fit_a_full_check():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert 1 <= BENCH["run_seconds"] <= 51 and total <= 43200


def test_names_units_and_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["traffic"]]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert all(NAME.match(n) for n in names)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_is_found_by_name(cell):
    c = spec.load_cell(cell, BENCH)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(spec.metric_reader(m["name"]).read)
    assert c.check["limits"] and all(
        v > 0 for v in c.check["limits"].values())
    spec.model_config(c.model).validate()


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_what_is_run(conf):
    f = json.loads((ROOT / conf["file"]).read_text())
    assert conf["file"].startswith("bench/configs/")
    assert f["source"] == conf["source"] and f["reduced"] == conf["reduced"]
    for key in conf["reduced"]:
        assert f[key] != f["published"][key]
        assert not key.endswith(("_dim", "_rank", "_size"))
    m = f["model"]
    for hf, ours in HF_KEYS.items():
        assert f[hf] == m[ours], hf
    if f.get("num_local_experts"):
        assert m["moe"]["n_experts"] == f["num_local_experts"]
        assert m["moe"]["top_k"] == f["num_experts_per_tok"]
    if not f.get("use_sliding_window", f["sliding_window"] is not None):
        assert m["attn_window"] is None
    assert (f["model_type"] == "qwen2") == m["qkv_bias"]
    assert (spec.ARCH_DIR / f"{f['arch']}.py").is_file()
    assert f["arch"] in spec.architectures()


def test_architectures_are_found_by_name(monkeypatch, tmp_path):
    """A new architecture is a new file ``bench/arch/<arch>.py`` that a
    configuration names: the cell draws its layout."""
    import numpy as np

    (tmp_path / "toy.py").write_text(
        "def layout(model):\n"
        "    return {'w': ((2, model['d_model']), 'normal', 1.0),\n"
        "            'g': ((model['d_model'],), 'gain', 0.0)}\n")
    conf = dict(json.loads((ROOT / "bench/configs/qwen2_5_3b.json")
                           .read_text()), arch="toy")
    (tmp_path / "toy.json").write_text(json.dumps(conf))
    bench = dict(BENCH, configs=[dict(c, file=str(tmp_path / "toy.json"))
                                 for c in BENCH["configs"]])
    monkeypatch.setattr(spec, "ARCH_DIR", tmp_path)
    assert spec.architectures() == ["toy"]
    cell = spec.load_cell("qwen2_5_3b.prefill_8k", bench)
    assert cell.arch is spec.architecture("toy")
    params = cell.weights(3)
    assert params["w"].shape == (2, 2048) and params["w"].dtype == "bfloat16"
    assert (np.asarray(params["g"], np.float32) == 1.0).all()


def test_an_unknown_or_missing_architecture_is_refused(tmp_path):
    conf = json.loads((ROOT / "bench/configs/qwen2_5_3b.json").read_text())
    for name, arch in [("unknown", "no_such_arch"), ("init", "__init__"),
                       ("path", "../spec")]:
        (tmp_path / f"{name}.json").write_text(json.dumps(dict(conf,
                                                               arch=arch)))
    del conf["arch"]
    (tmp_path / "missing.json").write_text(json.dumps(conf))
    for name in ("unknown", "init", "path", "missing"):
        bench = dict(BENCH, configs=[dict(c, file=str(tmp_path /
                                                      f"{name}.json"))
                                     for c in BENCH["configs"]])
        with pytest.raises(ValueError, match="known: .*'decoder'"):
            spec.load_cell("qwen2_5_3b.prefill_8k", bench)


def test_model_config_builds_every_block():
    """Each nested block of the model becomes its field's dataclass, and
    each JSON list a tuple, with no edit for a new block."""
    import dataclasses
    from typing import Optional, Tuple

    from repro.models.config import HybridConfig, MoEConfig

    cfg = spec.model_config({
        "name": "hybrid", "family": "hybrid", "n_layers": 3,
        "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "d_ff": 128,
        "vocab": 512, "moe": None,
        "hybrid": {"pattern_rec": 2, "lru_width": 96, "attn_window": 32}})
    cfg.validate()
    assert cfg.hybrid == HybridConfig(pattern_rec=2, lru_width=96,
                                      attn_window=32)
    assert cfg.moe is None
    assert spec.model_config(dict(
        json.loads((ROOT / "bench/configs/mixtral_8x7b_2l.json")
                   .read_text())["model"])).moe == MoEConfig(
        n_experts=8, top_k=2, capacity_factor=1.25, dispatch="pallas")

    @dataclasses.dataclass(frozen=True)
    class Outer:
        blocks: Tuple[HybridConfig, ...]
        sizes: Optional[Tuple[int, ...]] = None

    out = spec.build(Outer, {"blocks": [{"pattern_rec": 1}, {}],
                             "sizes": [1, 2]})
    assert out == Outer(blocks=(HybridConfig(pattern_rec=1),
                                HybridConfig()), sizes=(1, 2))
    with pytest.raises(ValueError, match="not a dataclass"):
        spec.build(Outer, {"blocks": [], "sizes": {"a": 1}})


def test_readers_stay_silent_without_data():
    for m in BENCH["per_layer"]:
        assert spec.metric_reader(m["name"]).read({}) is None


def test_reader_values_on_a_summary():
    from bench.peaks import peaks_for

    ctx = {"steps": 10, "window_s": 2.0, "flops_per_step": 13.2e12,
           "fabric_bytes_per_step": 0.8e9, "chips": 1,
           "stall_s": 0.5, "token_flops": 1e12,
           "peaks": peaks_for("TPU v5 lite"), "fabric_scatter": True,
           "trace": {"busy_s": 1.9, "window_s": 2.0,
                     "path_s": {"fabric": 0.15, "unnamed_scatter": 0.04}}}
    read = lambda n: spec.metric_reader(n).read(ctx)
    assert read("prefill_mfu") == pytest.approx(100 * 66e12 / 197e12)
    assert read("device_idle.prefill") == pytest.approx(5.0)
    assert read("fabric_share.prefill") == pytest.approx(10.0)
    assert read("fabric_roofline.prefill") == pytest.approx(
        100 * 8e9 / 819e9 / 0.19)
    assert read("prefill_stall_share") == pytest.approx(25.0)
    assert read("decode_mfu") == pytest.approx(100 * 0.5e12 / 197e12)
    assert read("device_idle.serve") == pytest.approx(5.0)
    assert read("fabric_share.serve") == pytest.approx(10.0)
    for m in BENCH["per_layer"]:        # a reader with no inputs is silent
        v = read(m["name"])
        assert v is None or 0 < v < 100, m["name"]
    ctx["fabric_scatter"] = False       # serving: no unnamed scatter
    assert read("fabric_share.serve") == pytest.approx(100 * 0.15 / 1.9)


def test_a_split_quantity_shares_its_reader():
    assert (spec.metric_reader("fabric_share.prefill").read.__code__.co_code
            == spec.metric_reader("fabric_share.serve").read.__code__.co_code)
    assert "roofline" in spec.metric_reader(
        "fabric_roofline.prefill").__doc__


def test_unknown_device_kind_is_an_error():
    from bench.peaks import peaks_for

    with pytest.raises(ValueError):
        peaks_for("cpu")
