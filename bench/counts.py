"""Operations and bytes that a step must do, computed from shapes.

These are the yardstick's own counts, independent of how the program
implements the step: matrix multiplications only (norms, softmax and
elementwise work are left out), causal attention counted once (each query
attends to the keys at or before it, inside the window if there is one),
the expert FFN only for the top-k experts a token is routed to, and the
head only for the last position, which is all that prefill returns.
"""
from __future__ import annotations

import math

ROUTING_STATE_BYTES = 16   # per packet: destination, slot, weight, error


def attended_pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs of causal attention over one sequence."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    w = window
    return w * (w + 1) // 2 + (seq_len - w) * w


def prefill_flops(model: dict, batch: int, seq_len: int) -> float:
    """FLOPs one prefill step of ``batch`` x ``seq_len`` tokens requires."""
    d, L = model["d_model"], model["n_layers"]
    H, Kv = model["n_heads"], model["n_kv_heads"]
    hd = model.get("head_dim") or d // H
    ff, V = model["d_ff"], model["vocab"]
    T = batch * seq_len
    proj = 2 * T * d * (H * hd + 2 * Kv * hd) + 2 * T * H * hd * d
    attn = 4 * batch * H * hd * attended_pairs(seq_len,
                                               model.get("attn_window"))
    moe = model.get("moe")
    if moe:
        ffn = (2 * T * d * moe["n_experts"]
               + moe["top_k"] * 6 * T * d * ff)
    else:
        ffn = 6 * T * d * ff
    head = 2 * batch * d * V
    return float(L * (proj + attn + ffn) + head)


def token_flops(model: dict, position: int) -> float:
    """FLOPs one token requires at ``position`` (0-based) of a sequence
    whose earlier keys are cached: its projections, attention over the
    ``position + 1`` keys it sees, the top-k experts or the MLP, and the
    head over the whole vocabulary (a served token's logits)."""
    d, L = model["d_model"], model["n_layers"]
    H, Kv = model["n_heads"], model["n_kv_heads"]
    hd = model.get("head_dim") or d // H
    ff, V = model["d_ff"], model["vocab"]
    window = model.get("attn_window")
    keys = position + 1 if window is None else min(position + 1, window)
    proj = 2 * d * (H * hd + 2 * Kv * hd) + 2 * H * hd * d
    attn = 4 * H * hd * keys
    moe = model.get("moe")
    ffn = (2 * d * moe["n_experts"] + moe["top_k"] * 6 * d * ff) if moe \
        else 6 * d * ff
    return float(L * (proj + attn + ffn) + 2 * d * V)


def fabric_packets(tokens: int, moe: dict, group_tokens: int) -> int:
    """Packets a crossbar must carry for ``tokens``: top-k per token,
    at most E x C per group (the rest is dropped at the quota)."""
    E, k = moe["n_experts"], moe["top_k"]
    groups = tokens // group_tokens
    cap = expert_capacity(group_tokens, moe)
    return groups * min(group_tokens * k, E * cap)


def expert_capacity(group_tokens: int, moe: dict, multiple: int = 8) -> int:
    """Per-group, per-expert slab depth: capacity factor x mean load,
    rounded up to whole sublane tiles of ``multiple`` rows."""
    c = math.ceil(moe["capacity_factor"] * group_tokens * moe["top_k"]
                  / moe["n_experts"])
    return max(multiple, math.ceil(c / multiple) * multiple)


def fabric_bytes(model: dict, tokens: int, group_tokens: int,
                 itemsize: int = 2) -> float:
    """Bytes any crossbar must move through one prefill step's MoE layers:
    each token row read once; each packet row written once on dispatch,
    then read once and combined into its token row (written once); and
    the routing state of every packet."""
    moe = model["moe"]
    d = model["d_model"]
    packets = fabric_packets(tokens, moe, group_tokens)
    per_layer = (2 * tokens * d * itemsize          # token rows in and out
                 + 2 * packets * d * itemsize       # packet rows out and in
                 + packets * ROUTING_STATE_BYTES)
    return float(model["n_layers"] * per_layer)
