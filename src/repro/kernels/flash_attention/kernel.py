"""Flash-attention Pallas TPU kernel: causal / sliding-window GQA attention.

Layout is head-major ([B, H, S, D]) so a (batch, head, q-block) grid cell
streams kv blocks through VMEM while the MXU consumes (block_q x block_k)
score tiles. Online softmax keeps running (m, l, acc) in VMEM scratch across
the sequential kv grid dimension.

Adaptation notes (GPU flash-attention -> TPU):
- tile sizes default to (block_q, block_k) = (256, 512): MXU-aligned
  (multiples of 128 lanes / 8 sublanes) and small enough that
  q + k + v + acc tiles fit comfortably in ~1 MB of VMEM at D = 128;
- no warp-level reductions: row max / row sum are VPU reductions over the
  128-lane axis;
- the kv loop is a *sequential grid dimension* (dimension_semantics
  "arbitrary"), not an in-kernel loop, so Mosaic double-buffers the kv block
  DMAs against MXU compute (the overlap the paper gets from separate bus
  lines per destination);
- banded (sliding-window) masks skip fully-masked kv tiles with pl.when —
  SWA prefill does O(S * window) work, not masked O(S^2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 block_q: int, block_k: int, sm_scale: float, causal: bool,
                 window: int | None, q_offset: int, true_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Absolute positions of this tile's rows/cols.
    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # Tile-level skip: entirely above the causal diagonal, or entirely
    # outside the sliding window band.
    q_first = q_offset + qi * block_q
    q_last = q_first + block_q - 1
    k_first = ki * block_k
    k_last = k_first + block_k - 1
    live = True
    if causal:
        live = jnp.asarray(k_first <= q_last)
    if window is not None:
        live = live & jnp.asarray(k_last > q_first - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

        mask = (k_pos < true_k)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                   # [bq, 128]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)             # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])         # [bq, 1]
        p = jnp.exp(s - m_new[:, :1])                         # [bq, bk]
        p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_prev[:, :1] + jnp.sum(p, axis=1, keepdims=True)

        v = v_ref[0, 0].astype(jnp.float32)                   # [bk, D]
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0, 0, :, :] = (acc_ref[...]
                             / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "q_offset",
                     "true_k", "interpret"))
def flash_attention_hm(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = True, window: int | None = None,
                       block_q: int = 256, block_k: int = 512,
                       q_offset: int = 0, true_k: int | None = None,
                       interpret: bool = False) -> jax.Array:
    """Head-major flash attention.

    q: [B, H, Sq, D]; k, v: [B, Kv, Sk, D]; H = Kv * G. Sequence lengths must
    already be padded to the block sizes (ops.py handles padding + layout);
    ``true_k`` is the unpadded key length (padded keys are masked out).
    """
    B, H, Sq, D = q.shape
    _, Kv, Sk, _ = k.shape
    G = H // Kv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq = Sq // block_q
    nk = Sk // block_k
    sm_scale = D ** -0.5

    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, sm_scale=sm_scale,
        causal=causal, window=window, q_offset=q_offset,
        true_k=Sk if true_k is None else true_k)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running sum
            pltpu.VMEM((block_q, D), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
