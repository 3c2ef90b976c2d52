"""Mamba-2 SSD chunk-scan Pallas TPU kernel (state-space duality).

One grid cell processes one (batch, head, chunk). The chunk axis is the
sequential grid dimension: the inter-chunk SSM state h [P, N] lives in VMEM
scratch and carries across chunks, while the within-chunk quadratic term runs
on the MXU:

    cum_t   = cumsum(dt_t * A)                       (log decay, VPU)
    G[i,j]  = (C_i . B_j) * exp(cum_i - cum_j) * dt_j   for i >= j
    y_diag  = G @ x                                  ([Q,Q] @ [Q,P], MXU)
    y_off   = exp(cum) * (C @ h^T)                   ([Q,N] @ [N,P], MXU)
    h'      = exp(cum_Q) * h + (w * x)^T @ B         (w = exp(cum_Q-cum)*dt)

The cumulative-decay subtraction stays in log space (<= 0 before exp), so the
kernel is stable for long chunks; accumulation is f32 regardless of input
dtype. Tiles at (Q=256, P=64, N=128) use ~((Q*Q) + 3*(Q*N) + 2*(Q*P)) * 4 B
~ 0.6 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _ssd_kernel(x_ref, dA_ref, dt_ref, b_ref, c_ref, y_ref, hlast_ref,
                h_scratch, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    x = x_ref[0, 0].astype(jnp.float32)                      # [Q, P]
    dA = dA_ref[0, 0].astype(jnp.float32)                    # [Q]
    dt = dt_ref[0, 0].astype(jnp.float32)                    # [Q]
    B = b_ref[0].astype(jnp.float32)                         # [Q, N]
    C = c_ref[0].astype(jnp.float32)                         # [Q, N]
    h = h_scratch[...]                                       # [P, N]

    cum = jnp.cumsum(dA)                                     # [Q], <= 0 steps
    # within-chunk quadratic term
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    li = cum[:, None] - cum[None, :]                         # [Q, Q]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    G = jnp.where(causal, CB * jnp.exp(jnp.where(causal, li, 0.0)), 0.0)
    G = G * dt[None, :]
    y = jax.lax.dot_general(G, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [Q, P]

    # inter-chunk contribution from the carried state
    Ch = jax.lax.dot_general(C, h, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, P]
    y = y + jnp.exp(cum)[:, None] * Ch

    # state update
    w = jnp.exp(cum[-1] - cum) * dt                          # [Q]
    xw = x * w[:, None]                                      # [Q, P]
    h_new = (h * jnp.exp(cum[-1])
             + jax.lax.dot_general(xw, B, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32))

    y_ref[0, 0] = y.astype(y_ref.dtype)
    h_scratch[...] = h_new

    @pl.when(ci == nc - 1)
    def _final():
        hlast_ref[0, 0] = h_new.astype(hlast_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_call(x: jax.Array, dA: jax.Array, dt: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 256, interpret: bool = False):
    """Head-major SSD scan.

    x: [B, H, S, P]; dA, dt: [B, H, S]; Bm, Cm: [B, S, N] (shared across
    heads). S must be a multiple of ``chunk``. Returns (y [B, H, S, P],
    h_last [B, H, P, N]) with y in x.dtype, h_last f32.
    """
    Bsz, H, S, P = x.shape
    N = Bm.shape[-1]
    nc = S // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, 1, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dA, dt, Bm, Cm)
    return y, h_last
