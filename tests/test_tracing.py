"""The program's own trace: host spans of the server and engine, the
``engine_syncs`` counter, and the op-name scopes of attention and the
expert FFN."""
import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.elastic import Region
from repro.core.module import ModuleFootprint
from repro.models.lm import build_model
from repro.shell import Shell
from repro.shell.server import ElasticServer, ModelEngine, StreamRequest

GB = 1 << 30
PROGRAM_SPANS = ("server.", "engine.")


def _shell():
    shell = Shell([Region(rid=i, n_chips=1, hbm_bytes=16 * GB)
                   for i in range(2)])
    shell.submit("lm", [ModuleFootprint(GB, 1e9, 4096)], app_id=0)
    return shell


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("tinyllama_1_1b", smoke=True)
    return ModelEngine(cfg, max_len=16, seed=0)


def _spans(trace_dir):
    """{name: [(start, end, stats)]} of the program's host spans."""
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_SPANS):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return out


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_server_and_engine_spans_nest_and_carry_request_ids(engine,
                                                            tmp_path):
    server = ElasticServer(_shell(), n_slots=2)
    server.register_engine(0, engine)
    rids = [server.submit(StreamRequest(
        app_id=0, prompt=np.array([3, 1, 4], np.int32), max_new=3))
        for _ in range(2)]
    server.step()                                  # compile outside the trace
    server.reset()
    rids = [server.submit(StreamRequest(
        app_id=0, prompt=np.array([2, 7, 1], np.int32), max_new=4))
        for _ in range(2)]
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            server.step()
    spans = _spans(tmp_path)

    ticks = spans["server.tick"]
    assert [s[2]["tick"] for s in ticks] == [0, 1, 2]
    (admit,) = spans["server.admit"]               # the queue empties at once
    assert admit[2]["admitted"] == 2 and _inside(admit, ticks[0])
    (prefill,) = spans["server.prefill"]
    assert _inside(prefill, admit)
    assert prefill[2]["B"] == 2 and prefill[2]["S"] == 3
    assert prefill[2]["app"] == 0
    assert str(prefill[2]["rids"]).split(";") == [str(r) for r in rids]
    syncs = spans["engine.sync"]
    assert sum(_inside(s, prefill) for s in syncs) == 1
    (split,) = spans["engine.split"]
    assert _inside(split, prefill) and split[2]["B"] == 2
    # two slots decode on each of the three ticks; each read syncs once
    decodes = spans["server.decode"]
    assert sorted(d[2]["rid"] for d in decodes) == sorted(rids * 3)
    assert all(any(_inside(s, d) for s in syncs) for d in decodes)
    assert len(syncs) == 1 + len(decodes)
    routes = spans["server.route"]
    assert len(routes) == 3
    assert all(any(_inside(r, t) for t in ticks) for r in routes)


def test_engine_syncs_count_each_token_read(engine):
    server = ElasticServer(_shell(), n_slots=2)
    server.register_engine(0, engine)
    before = engine.engine_syncs
    for _ in range(2):
        server.submit(StreamRequest(app_id=0,
                                    prompt=np.array([3, 1, 4], np.int32),
                                    max_new=4))
    server.step()                       # one batched admission, two decodes
    assert engine.engine_syncs - before == 1 + 2
    assert server.engine_syncs == engine.engine_syncs
    assert server.probe().sample()["engine_syncs"] == engine.engine_syncs
    server.run()                        # three more ticks of two decodes,
    assert engine.engine_syncs - before == 1 + 2 + 2 + 2   # the last none


def test_engines_without_a_counter_read_zero_syncs():
    class Echo:
        def prefill(self, prompt):
            return 1, None

        def decode(self, tok, state):
            return tok, state

    server = ElasticServer(_shell(), n_slots=1)
    server.register_engine(0, Echo())
    server.submit(StreamRequest(app_id=0, prompt=np.zeros(2, np.int32),
                                max_new=2))
    server.run()
    assert server.probe().sample()["engine_syncs"] == 0


def _op_names(compiled_text):
    return re.findall(r'op_name="([^"]*)"', compiled_text)


@pytest.fixture(scope="module")
def moe_programs():
    """Compiled HLO text of a tiny MoE prefill and decode step, routed
    through the crossbar fabric as the served Mixtral is."""
    cfg = get_config("mixtral_8x7b", smoke=True)
    cfg = dataclasses.replace(cfg, attn_window=None, moe=dataclasses.replace(
        cfg.moe, dispatch="pallas"))
    model = build_model(cfg)
    params = model.param_shapes()
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    prefill = jax.jit(model.prefill).lower(
        params, {"tokens": tokens}).compile().as_text()
    state = jax.eval_shape(lambda: model.init_decode_state(1, 16))
    decode = jax.jit(model.decode_step).lower(
        params, state,
        {"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32)}).compile()
    return {"prefill": prefill, "decode": decode.as_text()}


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_attention_and_expert_ffn_carry_their_scopes(moe_programs, program):
    names = _op_names(moe_programs[program])
    ffn = [n for n in names if "moe.expert_ffn" in n]
    attn = [n for n in names if "attn.core" in n]
    assert any("dot_general" in n for n in ffn)
    assert any("dot_general" in n for n in attn)
    # the scopes hold the expert matmuls and attention, never the fabric's
    # routing or each other
    assert not any("_dispatch_impl" in n or "_combine_impl" in n
                   for n in ffn + attn)
    assert not set(ffn) & set(attn)


def test_every_moe_path_runs_the_scoped_expert_ffn():
    from repro.models.moe import moe_apply

    cfg = get_config("mixtral_8x7b", smoke=True)
    params = build_model(cfg).init(jax.random.key(0))
    moe_params = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model),
                          moe_params["w_in"].dtype)
    outs = {}
    for impl in ("gather", "reference", "pallas"):
        fn = jax.jit(lambda p, x: moe_apply(p, x, cfg.moe, cfg.mlp_act,
                                            group_size=16,
                                            dispatch_impl=impl)[0])
        text = fn.lower(moe_params, x).compile().as_text()
        assert "moe.expert_ffn" in text, impl
        outs[impl] = np.asarray(fn(moe_params, x))
    np.testing.assert_allclose(outs["gather"], outs["reference"],
                               rtol=1e-5, atol=1e-5)
