"""What decides ``correct``: the plain reference, its control, and a run
whose timed path is broken underneath.  Small widths on the CPU; the full
widths run on the chip (``bench/calibrate.py``)."""
import copy
import hashlib

import numpy as np
import pytest

from bench import compare, spec
from bench.drivers.prefill import Prefill, check, tokens_fn
from bench.run import run_cell

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SMALL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
             vocab=512)


def small(name, **model):
    cell = spec.load_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(SMALL, **model)
    if cell.model.get("moe"):
        cell.model["moe"]["n_experts"] = 4
    cell.traffic = dict(cell.traffic, batch=2, seq_len=1024)
    cell.check = dict(cell.check, check_sequences=4)
    return cell


MIXTRAL = "mixtral_8x7b_2l.prefill_8k"
QWEN = "qwen2_5_3b.prefill_8k"


@pytest.mark.parametrize("name,capacity_factor", [(MIXTRAL, 0.5),
                                                  (MIXTRAL, 1.25),
                                                  (QWEN, None)])
def test_reference_equals_the_program_in_float32(name, capacity_factor):
    """At float32 the program and the reference agree to rounding, also
    where the expert capacity drops packets (factor 0.5); a reference
    without the capacity rule does not."""
    cell = small(name, dtype="float32")
    if capacity_factor:
        cell.model["moe"]["capacity_factor"] = capacity_factor
    p = Prefill(cell, 3)
    served = np.asarray(p.step(p.params, {"tokens": p.tokens(0)}))
    toks = np.asarray(p.tokens(0))
    ref, _ = cell.arch.Reference(
        cell.model, cell.config.get("routing")).last_logits(cell.weights(3),
                                                            toks)
    assert compare.logit_errors(served[:, :512], ref).max() < 1e-5
    if capacity_factor == 0.5:
        m = copy.deepcopy(cell.model)
        m["moe"]["capacity_factor"] = 1.25
        loose, _ = cell.arch.Reference(m, cell.config["routing"]).last_logits(
            cell.weights(3), toks)
        assert compare.logit_errors(served[:, :512], loose).min() > 0.1


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


# sha256 of the weights, the first step's tokens, and the reference's
# last-position logits and router margins at the SMALL sizes for seed 3,
# as drawn before the architecture moved to ``bench/arch/decoder.py``.
PINNED = {
    MIXTRAL: {
        "weights": "28b18ba4d69590b1061dbc3b15881cf8"
                   "f5c006d6c70ccce8ad98e28887c03109",
        "tokens": "ca0dda78d08d86636ded44b0f57be5e1"
                  "e2bb8388a071b05839fd80211f159cfc",
        "logits": "7e6aa9e6dad81849a12241fb11565b74"
                  "19638bb254703dd5667a49323b330999",
        "margin": "df3c86ec6fe24ddeddeb8a5b9071e725"
                  "93a24e1404e8b7c8231c166baec2fea0"},
    QWEN: {
        "weights": "5c9f14ad5c6a6c16543d0185856174b1"
                   "f795b2206e8ce2a35c7ce799b4b10e3a",
        "tokens": "ca0dda78d08d86636ded44b0f57be5e1"
                  "e2bb8388a071b05839fd80211f159cfc",
        "logits": "c26a579e11d7886e75fc9bd67b00ea37"
                  "67236a51815d2719a2b466d6c94608d0",
        "margin": "61bb8569fd84fac54f476f70b85a29f9"
                  "3f1f80ab24ed40bf8f89cc4e4abea42a"},
}


@pytest.mark.parametrize("name", [MIXTRAL, QWEN])
def test_weights_traffic_and_reference_logits_are_pinned(name):
    """The same seed draws bit-identical weights and tokens, and the
    reference gives bit-identical logits, through the cell's
    architecture."""
    import jax

    cell = small(name)
    params = cell.weights(3)
    toks = tokens_fn(cell.traffic, cell.model["vocab"], 3)(0)
    logits, margin = cell.arch.Reference(
        cell.model, cell.config.get("routing")).last_logits(
            params, np.asarray(toks))
    assert {"weights": digest(*jax.tree.leaves(params)),
            "tokens": digest(toks), "logits": digest(logits),
            "margin": digest(margin)} == PINNED[name]


@pytest.mark.parametrize("name", [MIXTRAL, QWEN])
def test_fp8_control_fails_and_the_program_passes(name):
    """The control (the reference in float8 in the program's place) reads
    over the cell's limit; the bf16 program reads under it.  Qwen keeps 6
    layers: its limit is set for the 36 of the cell, over which rounding
    adds up further than over 2."""
    cell = small(name, n_layers=6 if name == QWEN else 2)
    limit = cell.check["limits"]["logit_err"]
    p = Prefill(cell, 11)
    p.warm(1)
    p.window(0.01)
    toks, served = p.sample(4)
    p.free()
    program = check(cell, 11, toks, served)
    control = check(cell, 11, toks, served, control=cell.arch.Reference(
        cell.model, cell.config.get("routing"), "fp8"))
    assert program["compared"] >= 1
    assert program["logit_err"] < limit < control["logit_err"]


def test_router_ties_are_left_out_by_the_reference_margin():
    served = np.ones((3, 4))
    ref = np.ones((3, 4))
    served[1] = -1.0                      # far off, but a router tie
    out = compare.judge(served, ref, np.array([0.3, 0.01, np.inf]), 0.05,
                        limit=0.1)
    assert out == {"logit_err": 0.0, "compared": 2, "ties": 1, "failed": 0,
                   "errors": [0.0, 2.0, 0.0],
                   "margins": [0.3, 0.01, np.inf]}
    served[0, 0] = np.nan
    assert compare.judge(served, ref, np.full(3, 1.0), 0.05)["logit_err"] \
        == np.inf


@pytest.mark.parametrize("name", [MIXTRAL, QWEN])
def test_a_run_is_correct_and_an_altered_answer_is_not(name):
    """A whole run with the chip check skipped; then the same run with
    every answer altered where the step produces it (each row gets the
    logits of another prompt)."""
    import jax.numpy as jnp

    cell = small(name)
    good = run_cell(cell, 2**33 + 1, 0.2, False, device=CPU)
    assert good["correct"] and good["failed"] == 0
    assert list(good)[-1] == "checks"
    assert good["metrics"]["prefill_tok_s"]["value"] > 0

    def altered(step):
        return lambda params, batch: jnp.roll(step(params, batch), 1, axis=0)

    bad = run_cell(cell, 2**33 + 1, 0.2, False, device=CPU,
                   step_wrapper=altered)
    assert not bad["correct"] and bad["failed"] >= 1


CHAT = "mixtral_8x7b_2l.chat_closed8"


def small_chat(**model):
    cell = small(CHAT, **model)
    mix = copy.deepcopy(cell.traffic)
    mix.update(clients=2, prompt_lens=[8, 16])
    mix["max_new"].update(min=8, max=24)
    mix["server"].update(n_slots=2, max_len=48)
    cell.traffic = mix
    return cell


def test_served_tokens_equal_the_reference_in_float32():
    """At float32 the served tokens (admission replay, then decode through
    the KV cache) are the reference's first choices, teacher-forced."""
    from bench.drivers import serve_closed

    cell = small_chat(dtype="float32")
    s = serve_closed.Served(cell, 4)
    s.warm()
    served = s.window(0.5)["served"]
    picked = serve_closed.sample(served, cell.check["check_requests"], 4)
    s.free()
    got = serve_closed.check(cell, 4, picked)
    assert got["compared"] >= 20 and got["token_gap"] < 1e-4


def test_serve_control_fails_and_the_program_passes():
    """The control's widest gap grows with the tokens compared: the sample
    of 12 requests holds about 180 here, some hundreds on the chip."""
    from bench.drivers import serve_closed

    cell = small_chat()
    limit = cell.check["limits"]["token_gap"]
    lines = list(serve_closed.calibrate(cell, [9], {9}, 1.0))
    assert lines[0]["tokens"] >= 150
    assert lines[0]["program"]["token_gap"] < limit
    assert lines[0]["control"]["token_gap"] > limit


def test_a_served_run_is_correct_and_an_altered_token_is_not():
    """A whole serving run with the chip check skipped; then the same run
    with every decoded token altered where the engine produces it."""
    cell = small_chat()
    good = run_cell(cell, 2**33 + 5, 0.5, False, device=CPU)
    assert good["correct"] and good["failed"] == 0
    assert list(good)[-1] == "checks"
    assert good["metrics"]["decode_tok_s"]["value"] > 0
    assert good["metrics"]["itl_p95_ms"]["value"] > 0

    def altered(prefill, decode):
        def bad_decode(tok, state):
            nxt, state = decode(tok, state)
            return (nxt + 1) % cell.model["vocab"], state
        return prefill, bad_decode

    bad = run_cell(cell, 2**33 + 5, 0.5, False, device=CPU,
                   step_wrapper=altered)
    assert not bad["correct"] and bad["failed"] >= 1


def test_gaps_leave_out_router_ties_by_the_reference_margin():
    gaps = [np.array([0.0, 3.0, 0.1]), np.array([np.nan])]
    margins = [np.array([1.0, 0.01, 1.0]), np.array([1.0])]
    out = compare.judge_gaps(gaps, margins, 0.05, limit=0.5)
    assert {k: out[k] for k in ("token_gap", "compared", "ties",
                                "failed")} == {"token_gap": np.inf,
                                               "compared": 3, "ties": 1,
                                               "failed": 1}
    assert out["margins"][0] == [1.0, 0.01, 1.0]
    out = compare.judge_gaps(gaps[:1], margins[:1], 0.05, limit=0.05)
    assert out["token_gap"] == pytest.approx(0.1) and out["failed"] == 1


def test_router_shift_reads_the_gap_between_the_kth_and_next_expert():
    ref = np.array([[3.0, 2.0, 1.0, 0.0], [1.0, 0.9, 0.85, 0.0]])
    prog = ref + np.array([[0.0, 0.1, -0.1, 0.0], [0.0, -0.04, 0.04, 0.0]])
    out = compare.router_shift([prog], [ref], k=2)
    assert out["shift"] == pytest.approx(0.2)
    assert out["shift_first"] == pytest.approx(0.2)
    assert out["abs"] == pytest.approx(0.1)
    assert out["flips"] == 1 and out["flip_margin"] == pytest.approx(0.05)
    assert out["positions"] == 2


def test_router_probe_reads_the_program_router():
    """At float32 the router logits the probe reads from the program's
    prefill step and from the engine's admission replay are the
    reference's, to rounding."""
    from bench.drivers import serve_closed
    from bench.router_probe import RouterProbe
    from repro.models import moe as moe_mod

    program = moe_mod.moe_apply
    try:
        probe = RouterProbe()
        line, = prefill_calibrate(small(MIXTRAL, dtype="float32"), probe)
        assert line["router"]["shift"] < 1e-4
        n_seqs = line["program"]["compared"] + line["program"]["ties"]
        assert line["router"]["positions"] == 2 * n_seqs * 1024
        line, = serve_closed.calibrate(small_chat(dtype="float32"), [6],
                                       set(), 0.5, probe)
        assert line["router"]["shift"] < 1e-4
        assert line["router"]["flips"] == 0
    finally:
        moe_mod.moe_apply = program


def prefill_calibrate(cell, probe):
    from bench.drivers.prefill import calibrate
    return list(calibrate(cell, [5], set(), 0.2, probe))
