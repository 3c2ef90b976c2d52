"""The readers of the program's own op-name scopes, and what the
program's host spans leave of the benchmark's trace reduction."""
import dataclasses
import glob
import re

import numpy as np
import pytest

from bench import spec
from bench import trace as tr

SCOPED = ("expert_ffn_share.prefill", "expert_ffn_share.serve",
          "attention_share.prefill")


def _paths(names):
    out = {}
    for n in names:
        out.update(spec.metric_reader(n).PATHS)
    return out


@pytest.mark.parametrize("name", SCOPED)
def test_scope_readers_are_silent_without_their_ops(name):
    read = spec.metric_reader(name).read
    assert read({}) is None
    assert read({"trace": {"busy_s": 2.0, "window_s": 2.0,
                           "path_s": {"fabric": 0.1}}}) is None
    assert read({"trace": {"busy_s": 2.0, "window_s": 2.0,
                           "path_s": {"expert_ffn": 0.0,
                                      "attention": 0.0}}}) is None


def test_scope_readers_on_a_summary():
    ctx = {"trace": {"busy_s": 2.0, "window_s": 2.1,
                     "path_s": {"expert_ffn": 1.3, "attention": 0.25}}}
    read = lambda n: spec.metric_reader(n).read(ctx)
    assert read("expert_ffn_share.prefill") == pytest.approx(65.0)
    assert read("expert_ffn_share.serve") == pytest.approx(65.0)
    assert read("attention_share.prefill") == pytest.approx(12.5)


def test_scope_paths_pick_their_ops_and_no_fabric_op():
    paths = _paths(SCOPED)
    ops = [
        ["fusion.1", 0, 30, "jit(s)/while/body/closed_call/"
         "vmap(moe.expert_ffn)/vmap(ecd,edf->ecf)/dot_general", "fusion",
         "jit_s"],
        ["fusion.2", 30, 10, "jit(s)/while/body/attn.core/while/body/"
         "bqkgd,bskd->bkgqs/dot_general", "fusion", "jit_s"],
        ["fusion.3", 40, 5, "jit(s)/while/body/closed_call/"
         "vmap(jit(_dispatch_impl))/fabric.dispatch/scatter-add", "fusion",
         "jit_s"],
        ["fusion.4", 45, 5, "jit(s)/while/body/bsd,de->bse/dot_general",
         "fusion", "jit_s"],
    ]
    assert tr.path_ns(ops, paths["expert_ffn"], 0, 50) == 30
    assert tr.path_ns(ops, paths["attention"], 0, 50) == 10
    assert tr.path_ns(ops, tr.FABRIC, 0, 50) == 5
    assert not re.search(tr.FABRIC, "moe.expert_ffn/attn.core")


def test_compiled_moe_step_scopes_never_match_the_fabric():
    """A tiny MoE prefill through the crossbar, compiled: the expert FFN
    and attention ops carry their scopes, and none of them is counted as
    the fabric's."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models.lm import build_model

    cfg = get_config("mixtral_8x7b", smoke=True)
    cfg = dataclasses.replace(cfg, attn_window=None, moe=dataclasses.replace(
        cfg.moe, dispatch="pallas"))
    model = build_model(cfg)
    text = jax.jit(model.prefill).lower(
        model.param_shapes(),
        {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
    ).compile().as_text()
    paths = set(tr.hlo_op_paths(text).values())
    for key, pattern in _paths(SCOPED).items():
        mine = [p for p in paths if re.search(pattern, p)]
        assert mine, key
        assert not [p for p in mine if re.search(tr.FABRIC, p)], key
    assert [p for p in paths if re.search(tr.FABRIC, p)]


def test_program_spans_leave_the_window_to_the_benchmark(tmp_path):
    """The server's own spans land on the host plane beside the
    benchmark's; the reduction keeps only ``bench.*`` spans, so the window
    and its idle gaps are the benchmark's alone."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.core.elastic import Region
    from repro.core.module import ModuleFootprint
    from repro.shell import Shell
    from repro.shell.server import ElasticServer, StreamRequest

    class Echo:
        def prefill(self, prompt):
            return 1, None

        def decode(self, tok, state):
            return tok + 1, state

    shell = Shell([Region(rid=0, n_chips=1, hbm_bytes=1 << 34)])
    shell.submit("lm", [ModuleFootprint(1 << 30, 1e9, 4096)], app_id=0)
    server = ElasticServer(shell, n_slots=1)
    server.register_engine(0, Echo())
    server.submit(StreamRequest(app_id=0, prompt=np.zeros(2, np.int32),
                                max_new=3))
    server.step()                            # the fabric compiles here
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("server.tick", tick=-1):
            pass                             # a program span before the
        for _ in range(2):                   # benchmark's first
            with TraceAnnotation("bench.tick"):
                server.step()
    rec = tr.load(glob.glob(f"{tmp_path}/**/*.xplane.pb",
                            recursive=True)[0])
    assert [h[0] for h in rec["host"]] == ["bench.tick", "bench.tick"]
    lo, hi = tr.window(rec)
    assert (lo, hi) == (rec["host"][0][1],
                        rec["host"][1][1] + rec["host"][1][2])
