"""Model weights drawn from the seed, on the device, in one jitted call.

The layout is the program's parameter tree (its input format), as the
architecture's ``layout(model)`` gives it (``bench/arch/<arch>.py``): a
nested dict of leaves ``(shape, kind, scale)``; ``kind`` is "normal"
(std = scale) or "gain" (1 + scale * normal).  The values are the
benchmark's own, each leaf a normal draw cast to the served dtype inside
the same program, so no full-size float32 copy is ever held.  The plain
reference regenerates the same tree from the same seed, so it takes
nothing the program has made.
"""
from __future__ import annotations

import math

VOCAB_PAD_MULTIPLE = 2048


def vocab_padded(vocab: int) -> int:
    """The program's embedding rows: the vocabulary padded to a multiple
    of ``VOCAB_PAD_MULTIPLE``."""
    return math.ceil(vocab / VOCAB_PAD_MULTIPLE) * VOCAB_PAD_MULTIPLE


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


def generate(layout: dict, seed: int, dtype: str):
    """The whole parameter tree of ``layout``, drawn on the default device
    in ``dtype``."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_leaf)

    def draw(key):
        out = []
        for i, (shape, kind, scale) in enumerate(leaves):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            v = 1.0 + scale * z if kind == "gain" else scale * z
            out.append(v.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(draw)(seed_key(seed, 0))


def shapes(layout: dict) -> dict:
    """Leaf shapes, in the tree layout."""
    import jax
    return jax.tree.map(lambda leaf: leaf[0], layout, is_leaf=_is_leaf)


def n_params(layout: dict) -> int:
    import jax
    return sum(math.prod(s) for s in
               jax.tree.leaves(shapes(layout), is_leaf=lambda x: isinstance(x, tuple)))
