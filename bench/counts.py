"""Pieces of the yardstick's counts of operations and bytes, computed
from shapes, that every architecture's counts (``bench/arch/<arch>.py``)
share.

The counts are independent of how the program implements a step: matrix
multiplications only (norms, softmax and elementwise work are left out),
causal attention counted once (each query attends to the keys at or
before it, inside the window if there is one), and the expert FFN only
for the top-k experts a token is routed to.
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
