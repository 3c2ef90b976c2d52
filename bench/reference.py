"""Building blocks of the plain references, in float32.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``, no kernels, no
cache, no crossbar: the pieces an architecture's ``Reference``
(``bench/arch/<arch>.py``) is composed of.  RMSNorm, rotate-half RoPE,
causal GQA attention of one sequence with an optional sliding window,
SwiGLU, and for experts the capacity rule a configuration states: tokens
form groups of ``group_tokens``; within a group each (token, choice)
packet, in token-major order, takes the next free slot of its expert, and
a packet beyond ``capacity_factor`` x the mean load is dropped
(``capacity_slots``); each expert then runs once over its slab of slots
(``expert_slab``).

Nothing here imports the program.  ``precision="fp8"`` is the control:
every operand of a projection, expert or head matrix multiplication
(``matmul``) is rounded to float8 (e4m3, one scale per tensor), the
precision below the served bfloat16.
"""
from __future__ import annotations

PRECISIONS = ("float32", "fp8")
FP8_MAX = 448.0            # largest finite float8_e4m3fn


def quantizer(precision: str):
    """The rounding applied to each matmul operand: none, or float8."""
    import jax.numpy as jnp

    if precision == "float32":
        return lambda x: x
    if precision == "fp8":
        def q(x):
            s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
            return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return q
    raise ValueError(f"precision must be one of {PRECISIONS}: {precision!r}")


def matmul(spec: str, a, b, q):
    """``einsum(spec, q(a), q(b))`` at the highest precision."""
    import jax
    import jax.numpy as jnp

    return jnp.einsum(spec, q(a), q(b), precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, g, eps: float):
    import jax
    import jax.numpy as jnp

    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * g.astype(jnp.float32)


def rope(x, pos, theta: float):
    """Rotate-half RoPE of ``x`` [..., S, heads, hd] at positions ``pos``
    [S]."""
    import jax.numpy as jnp

    f32 = jnp.float32
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd))
    ang = pos[:, None].astype(f32) * freqs            # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(qs, ks, vs, window=None):
    """Causal GQA attention of one sequence: ``qs`` [S, H, hd], ``ks`` and
    ``vs`` [S, Kv, hd]; a key is seen from its own position on, and within
    ``window`` positions if one is given."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    S, H, hd = qs.shape
    G = H // ks.shape[1]
    k = jnp.repeat(ks, G, axis=1)
    v = jnp.repeat(vs, G, axis=1)
    s = jnp.einsum("qhd,khd->hqk", qs, k, precision=hi)
    s = s * hd ** -0.5
    i = jnp.arange(S)
    mask = i[None, :] <= i[:, None]
    if window is not None:
        mask &= i[None, :] > i[:, None] - window
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=hi)


def swiglu(x, w_in, w_out, q):
    """SwiGLU: ``w_in`` [d, 2 ff] holds the gate, then the up projection."""
    import jax
    import jax.numpy as jnp

    gate, up = jnp.split(matmul("...d,df->...f", x, w_in, q), 2, axis=-1)
    return matmul("...f,fd->...d", jax.nn.silu(gate) * up, w_out, q)


def capacity_slots(top_e, top_p, g: int, n_experts: int):
    """The capacity rule's queues: ``top_e`` and ``top_p`` [n, S, k] (each
    token's experts and weights) as groups of ``g`` tokens, packets in
    token-major order -> (``dst``, ``w``, ``rank``), each [G, g*k]: a
    packet's expert, its weight, and its place in its expert's queue.
    Under capacity ``C`` a packet keeps its place as its slot if
    ``rank < C`` and is dropped otherwise."""
    import jax.numpy as jnp

    n, S, k = top_e.shape
    G = n * S // g
    dst = top_e.reshape(G, g * k)
    w = top_p.reshape(G, g * k)
    onehot = (dst[..., None] == jnp.arange(n_experts)).astype(jnp.int32)
    before = jnp.cumsum(onehot, axis=1) - onehot
    rank = jnp.take_along_axis(before, dst[..., None], -1)[..., 0]
    return dst, w, rank


def expert_slab(y, e: int, h, dst, w, keep, rank, C: int, ffn):
    """Expert ``e``'s pass over its slab: its kept packets of ``h`` [G, g,
    d] in their slots, ``ffn`` (the expert, [G, C, d] -> [G, C, d]) over
    the slab, and the outputs weighted back into their token rows, added
    to ``y`` [G, g, d]."""
    import jax.numpy as jnp

    f32 = jnp.float32
    G, g, d = h.shape
    k = dst.shape[1] // g
    sel = keep & (dst == e)                          # [G, g*k]
    slot = jnp.where(sel, rank, C)                   # C: trash row
    rows = jnp.arange(G)[:, None]
    xk = jnp.repeat(h, k, axis=1)                    # [G, g*k, d]
    slab = jnp.zeros((G, C + 1, d), f32).at[rows, slot].set(xk)
    out = ffn(slab[:, :C])
    out = jnp.concatenate([out, jnp.zeros((G, 1, d), f32)], 1)
    back = out[rows, slot] * (w * sel)[..., None]
    return y + back.reshape(G, -1, k, d).sum(2)
