"""Admission of a prompt group by one parallel causal prefill that fills the
KV cache (``DenseLM.prefill_cache``), against the token-by-token replay
through ``decode_step`` it replaces, and the engine's choice between them."""
import dataclasses
import glob

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.elastic import Region
from repro.core.module import ModuleFootprint
from repro.models import moe as moe_mod
from repro.shell import Shell
from repro.shell.server import ElasticServer, ModelEngine, StreamRequest

GB = 1 << 30


def _mixtral(backend: str):
    """The tiny Mixtral (window 32) in float32, its experts behind the
    crossbar fabric's ``backend``."""
    cfg = get_config("mixtral_8x7b", smoke=True)
    return dataclasses.replace(cfg, dtype="float32", moe=dataclasses.replace(
        cfg.moe, dispatch=backend))


def _prompts(B, S, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return list(rng.integers(0, vocab, (B, S), np.int32))


def _replay(engine, prompts):
    """The replay's first tokens and per-slot states, and its batched
    state."""
    B, S = len(prompts), len(prompts[0])
    logits, state = engine._prefill_fn(B, S)(engine.params, np.stack(prompts))
    toks, _ = engine._greedy(logits)
    return list(zip(toks, engine._split_state(state, B))), logits, state


def _decode(engine, tok, state, n=3):
    out = []
    for _ in range(n):
        tok, state = engine.decode(tok, state)
        out.append(tok)
    return out


def _assert_close(got, want, tol=1e-5):
    """Agreement to ``tol`` of the tensor's scale: float32 attention over a
    whole prompt and over a cache round differently, by about 1e-6 of the
    largest entry."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    scale = max(1.0, float(np.abs(want).max()))
    assert float(np.abs(got - want).max()) <= tol * scale


def _assert_admissions_agree(engine, prompts):
    B, S = len(prompts), len(prompts[0])
    dropped_before = engine.admission_dropped
    parallel = engine.prefill_batch(prompts)
    assert engine.admission_replays == 0
    assert engine.admission_dropped == dropped_before == 0
    replayed, r_logits, r_state = _replay(engine, prompts)

    p_logits, p_state, dropped = engine._prefill_fns[(B, S)](
        engine.params, np.stack(prompts))
    assert int(dropped) == 0
    _assert_close(p_logits, r_logits)
    assert int(p_state.pos) == int(r_state.pos) == S
    np.testing.assert_array_equal(p_state.kv_pos, r_state.kv_pos)
    _assert_close(p_state.kv_k, r_state.kv_k)
    _assert_close(p_state.kv_v, r_state.kv_v)

    for (tok_p, st_p), (tok_r, st_r) in zip(parallel, replayed):
        assert tok_p == tok_r
        assert _decode(engine, tok_p, st_p) == _decode(engine, tok_r, st_r)


@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_parallel_admission_matches_the_replay(backend, B):
    cfg = _mixtral(backend)
    engine = ModelEngine(cfg, max_len=48, seed=0)
    _assert_admissions_agree(engine, _prompts(B, 12, cfg.vocab))


def _router_biased(engine):
    """Every token's first choice is expert 0: the embedding's first
    coordinate dominates each normed hidden state, and every layer's router
    reads it as a strong vote for expert 0."""
    p = engine.params
    embed = p["embed"].at[:, 0].add(100.0)
    w_router = p["layers"]["moe"]["w_router"].at[:, 0, 0].set(10.0)
    layers = dict(p["layers"], moe=dict(p["layers"]["moe"],
                                        w_router=w_router))
    engine.params = dict(p, embed=embed, layers=layers)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_admission_stays_dropless_where_the_capacity_rule_overflows(backend):
    cfg = _mixtral(backend)
    engine = ModelEngine(cfg, max_len=48, seed=0)
    _router_biased(engine)
    prompts = _prompts(2, 24, cfg.vocab, seed=1)
    # under the configuration's rule (1.25) a group of 24 tokens gives an
    # expert 16 slots, and expert 0 is offered 24 packets in each layer
    assert moe_mod.expert_capacity(24, cfg.moe) == 16
    _, _, ruled = jax.jit(lambda p, t: engine.model.prefill_cache(
        p, t, engine.max_len))(engine.params, np.stack(prompts))
    assert int(ruled) == 2 * 2 * (24 - 16)          # groups x layers x over
    _assert_admissions_agree(engine, prompts)


def _shell():
    shell = Shell([Region(rid=i, n_chips=1, hbm_bytes=16 * GB)
                   for i in range(2)])
    shell.submit("lm", [ModuleFootprint(GB, 1e9, 4096)], app_id=0)
    return shell


def _serve(engine, prompts, max_new=2):
    server = ElasticServer(_shell(), n_slots=len(prompts))
    server.register_engine(0, engine)
    for prompt in prompts:
        server.submit(StreamRequest(app_id=0, prompt=prompt,
                                    max_new=max_new))
    server.run()
    return server


@pytest.mark.parametrize("name,S,mode", [
    ("tinyllama_1_1b", 6, "parallel"),        # plain DenseLM
    ("mixtral_8x7b", 12, "parallel"),         # windowed, prompt fits
    ("mixtral_8x7b", 40, "replay"),           # windowed, S > window 32
    ("mamba2_780m", 6, "replay"),             # SSMLM: no cache to fill
])
def test_engine_admits_in_parallel_only_where_the_model_allows(name, S,
                                                               mode):
    cfg = get_config(name, smoke=True)
    engine = ModelEngine(cfg, max_len=64, seed=0)
    prompts = _prompts(2, S, cfg.vocab)
    server = _serve(engine, prompts)
    replays = 2 if mode == "replay" else 0
    assert engine.admission_replays == server.admission_replays == replays
    assert server.probe().sample()["admission_replays"] == replays
    assert len(server.completions) == 2
    assert engine.admission_dropped == 0
    assert list(engine._prefill_fns) == [(2, S)]
    fn = engine._prefill_fns[(2, S)]
    out = fn(engine.params, np.stack(prompts))
    assert out[0].shape == (2, cfg.vocab_padded)
    assert (fn is engine._prefill_fn(2, S)) == (mode == "replay")


def test_the_replay_program_calls_the_expert_layer_once_a_position(
        monkeypatch):
    cfg = _mixtral("reference")
    engine = ModelEngine(cfg, max_len=32, seed=0)
    calls = []
    program = moe_mod.moe_apply

    def counted(params, x, moe, act, **kw):
        jax.debug.callback(lambda: calls.append(1), ordered=True)
        return program(params, x, moe, act, **kw)

    monkeypatch.setattr(moe_mod, "moe_apply", counted)
    B, S = 2, 5
    jax.block_until_ready(engine._prefill_fn(B, S)(
        engine.params, np.stack(_prompts(B, S, cfg.vocab))))
    jax.effects_barrier()
    assert len(calls) == S * cfg.n_layers


def _prefill_spans(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    return [dict(ev.stats) for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name == "engine.prefill"]


def test_engine_prefill_span_names_the_mode(tmp_path):
    cfg = get_config("mixtral_8x7b", smoke=True)
    engine = ModelEngine(cfg, max_len=64, seed=0)
    short, long_ = _prompts(3, 8, cfg.vocab), _prompts(1, 40, cfg.vocab)
    engine.prefill_batch(short)                    # compile outside the trace
    engine.prefill_batch(long_)
    with jax.profiler.trace(str(tmp_path)):
        engine.prefill_batch(short)
        engine.prefill_batch(long_)
    spans = _prefill_spans(tmp_path)
    assert [(s["mode"], s["B"], s["S"]) for s in spans] == [
        ("parallel", 3, 8), ("replay", 1, 40)]
