"""Model weights drawn from the seed, on the device, in one jitted call.

The layout is the ``DenseLM`` parameter tree the program consumes (its
input format); the values are the benchmark's own: every leaf is a normal
draw scaled by 1/sqrt(fan-in) (embedding rows by 1, norm gains around 1,
biases small), cast to the served dtype inside the same program, so no
full-size float32 copy is ever held.  The plain reference regenerates the
same tree from the same seed, so it takes nothing the program has made.
"""
from __future__ import annotations

import math

VOCAB_PAD_MULTIPLE = 2048


def vocab_padded(vocab: int) -> int:
    return math.ceil(vocab / VOCAB_PAD_MULTIPLE) * VOCAB_PAD_MULTIPLE


def layout(model: dict) -> dict:
    """Nested dict of leaves ``(shape, kind, scale)``; ``kind`` is
    "normal" (std = scale) or "gain" (1 + scale * normal)."""
    d, L = model["d_model"], model["n_layers"]
    H, Kv = model["n_heads"], model["n_kv_heads"]
    hd = model.get("head_dim") or d // H
    ff, Vp = model["d_ff"], vocab_padded(model["vocab"])
    rs = lambda n: 1.0 / math.sqrt(n)
    attn = {"wq": ((L, d, H * hd), "normal", rs(d)),
            "wk": ((L, d, Kv * hd), "normal", rs(d)),
            "wv": ((L, d, Kv * hd), "normal", rs(d)),
            "wo": ((L, H * hd, d), "normal", rs(H * hd))}
    if model.get("qkv_bias"):
        attn.update({"bq": ((L, H * hd), "normal", 0.1),
                     "bk": ((L, Kv * hd), "normal", 0.1),
                     "bv": ((L, Kv * hd), "normal", 0.1)})
    layer = {"norm1": ((L, d), "gain", 0.1), "attn": attn,
             "norm2": ((L, d), "gain", 0.1)}
    moe = model.get("moe")
    if moe:
        E = moe["n_experts"]
        layer["moe"] = {"w_router": ((L, d, E), "normal", rs(d)),
                        "w_in": ((L, E, d, 2 * ff), "normal", rs(d)),
                        "w_out": ((L, E, ff, d), "normal", rs(ff))}
    else:
        layer["mlp"] = {"w_in": ((L, d, 2 * ff), "normal", rs(d)),
                        "w_out": ((L, ff, d), "normal", rs(ff))}
    out = {"embed": ((Vp, d), "normal", 1.0),
           "final_norm": ((d,), "gain", 0.1),
           "layers": layer}
    if not model.get("tied_embeddings"):
        out["lm_head"] = ((d, Vp), "normal", rs(d))
    return out


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def seed_key(seed: int, stream: int):
    """A key for one stream (weights 0, traffic 1) of a seed of up to 64
    bits: both 32-bit halves are folded in."""
    import jax
    import numpy as np

    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(stream))


def generate(model: dict, seed: int):
    """The whole parameter tree, drawn on the default device in the
    served dtype."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(model.get("dtype", "bfloat16"))
    spec = layout(model)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)

    def draw(key):
        out = []
        for i, (shape, kind, scale) in enumerate(leaves):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            v = 1.0 + scale * z if kind == "gain" else scale * z
            out.append(v.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(draw)(seed_key(seed, 0))


def shapes(model: dict) -> dict:
    """Leaf shapes, in the tree layout."""
    import jax
    return jax.tree.map(lambda leaf: leaf[0], layout(model), is_leaf=_is_leaf)


def n_params(model: dict) -> int:
    import jax
    return sum(math.prod(s) for s in
               jax.tree.leaves(shapes(model), is_leaf=lambda x: isinstance(x, tuple)))
