"""FLOP and byte counts of the yardstick against hand counts."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import counts

CONFIGS = Path(__file__).parent / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_mixtral_prefill_flops_hand_count():
    T, B, S = 8192, 4, 2048
    proj = 2 * T * 4096 * (32 * 128 + 2 * 8 * 128) + 2 * T * 4096 * 4096
    attn = 4 * B * 32 * 128 * (S * (S + 1) // 2)
    ffn = 2 * T * 4096 * 8 + 2 * 6 * T * 4096 * 14336
    head = 2 * B * 4096 * 32000
    want = 2 * (proj + attn + ffn) + head
    assert want == 13_196_396_068_864
    assert counts.prefill_flops(model("mixtral_8x7b_2l"), B, S) == want


def test_qwen_prefill_flops_hand_count():
    T, B, S = 8192, 4, 2048
    proj = 2 * T * 2048 * (16 * 128 + 2 * 2 * 128) + 2 * T * 2048 * 2048
    attn = 4 * B * 16 * 128 * (S * (S + 1) // 2)
    mlp = 6 * T * 2048 * 11008
    head = 2 * B * 2048 * 151936
    want = 36 * (proj + attn + mlp) + head
    assert want == 47_935_532_302_336
    assert counts.prefill_flops(model("qwen2_5_3b"), B, S) == want


def test_window_limits_attended_pairs():
    assert counts.attended_pairs(6) == 21
    # window 2: position i sees min(i + 1, 2) keys
    assert counts.attended_pairs(6, window=2) == 1 + 2 * 5
    assert counts.attended_pairs(6, window=8) == 21


def test_capacity_matches_the_program():
    from repro.models.config import MoEConfig
    from repro.models.moe import expert_capacity

    for g, cf, E, k in [(1024, 1.25, 8, 2), (100, 1.0, 4, 1), (7, 2.0, 16, 2)]:
        moe = {"n_experts": E, "top_k": k, "capacity_factor": cf}
        assert counts.expert_capacity(g, moe) == expert_capacity(
            g, MoEConfig(n_experts=E, top_k=k, capacity_factor=cf))


def _moved_bytes(dst, cap, E, d, itemsize=2):
    """Bytes a gather-slab crossbar moves for one group's routing ``dst``
    [tokens, k]: token rows in, kept packet rows out and back, combined
    rows written, 16 B of routing state per kept packet."""
    flat = dst.reshape(-1)
    seen = np.zeros(E, int)
    kept = 0
    for e in flat:
        kept += seen[e] < cap
        seen[e] += 1
    T = dst.shape[0]
    return 2 * T * d * itemsize + 2 * kept * d * itemsize + 16 * kept, kept


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fabric_bytes_equal_the_least_movement_without_drops(seed):
    m = model("mixtral_8x7b_2l")
    moe, d, g = m["moe"], m["d_model"], 1024
    rng = np.random.default_rng(seed)
    cap = counts.expert_capacity(g, moe)
    total, kept_all = 0, 0
    for _ in range(8):                      # 8 groups: one prefill step
        dst = np.stack([rng.choice(8, 2, replace=False) for _ in range(g)])
        b, kept = _moved_bytes(dst, cap, 8, d)
        total, kept_all = total + b, kept_all + kept
    assert kept_all == 8 * g * 2           # uniform routing drops nothing
    assert counts.fabric_bytes(m, 8 * g, g) == m["n_layers"] * total


def test_fabric_bytes_never_exceed_the_programs_data_plane():
    """With drops, the count is bounded by what the program's data plane
    moves: every offered packet is written (dropped ones to a trash row)
    and read back."""
    m = model("mixtral_8x7b_2l")
    d, g, T = m["d_model"], 1024, 8192
    offered = T * 2
    program = m["n_layers"] * (2 * T * d * 2 + 2 * offered * d * 2
                               + 16 * offered)
    assert counts.fabric_bytes(m, T, g) <= program


@pytest.mark.parametrize("position", [0, 127, 254])
def test_token_flops_hand_count(position):
    """One Mixtral token at ``position``: projections, attention over the
    keys up to and including it, 2 of 8 experts and the router, and the
    head over the whole vocabulary."""
    keys = position + 1
    proj = 2 * 4096 * (32 * 128 + 2 * 8 * 128) + 2 * 4096 * 4096
    attn = 4 * 32 * 128 * keys
    ffn = 2 * 4096 * 8 + 2 * 6 * 4096 * 14336
    want = 2 * (proj + attn + ffn) + 2 * 4096 * 32000
    assert counts.token_flops(model("mixtral_8x7b_2l"), position) == want


def test_token_flops_of_the_last_position_are_one_prefill_token():
    """A prefill of one sequence requires what its tokens require one by
    one, less the head at every position but the last."""
    m = model("qwen2_5_3b")
    S = 64
    head = 2 * 2048 * 151936
    by_token = sum(counts.token_flops(m, p) for p in range(S))
    assert counts.prefill_flops(m, 1, S) == by_token - (S - 1) * head
