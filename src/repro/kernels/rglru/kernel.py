"""RG-LRU linear-recurrence Pallas TPU kernel (RecurrentGemma / Griffin).

Computes the diagonal recurrence  h_t = a_t * h_{t-1} + b_t  where a, b are
[B, S, L] and the gated input b is prefolded by the caller (the gate matmuls
are XLA's job; the sequential recurrence is the part XLA serialises badly).

Grid: (batch, lane-block, chunk) with the chunk axis sequential; the carry
h [1, bL] lives in VMEM scratch. Within a chunk the scan is a log2(Q)-step
Hillis–Steele doubling over the [Q, bL] tile — pure VPU shifts/multiplies,
no per-timestep loop:

    for s in (1, 2, 4, ..., Q/2):
        b += a * shift_down(b, s);  a *= shift_down(a, s)

after which b_t = h_t given h_{-1}=0 and a_t = prod_{k<=t} a_k, so the carry
folds in as  h_t += a_cum_t * h_carry.  Tile (Q=256, bL=512) uses ~2 MB VMEM
(two f32 work arrays + shifts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h_ref, hlast_ref, carry, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        carry[...] = jnp.zeros_like(carry)

    a = a_ref[0].astype(jnp.float32)                          # [Q, bL]
    b = b_ref[0].astype(jnp.float32)                          # [Q, bL]

    # Hillis–Steele doubling: after log2(Q) rounds, a = cumulative product,
    # b = within-chunk scan of (a, b).
    s = 1
    while s < chunk:
        a_sh = jnp.pad(a, ((s, 0), (0, 0)), constant_values=1.0)[:-s]
        b_sh = jnp.pad(b, ((s, 0), (0, 0)), constant_values=0.0)[:-s]
        b = b + a * b_sh
        a = a * a_sh
        s *= 2

    h = b + a * carry[...]                                    # fold carry in
    h_ref[0] = h.astype(h_ref.dtype)
    carry[...] = h[-1:, :]

    @pl.when(ci == nc - 1)
    def _final():
        hlast_ref[0] = h[-1:, :].astype(hlast_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "block_l", "interpret"))
def rglru_call(a: jax.Array, b: jax.Array, *, chunk: int = 256,
               block_l: int = 512, interpret: bool = False):
    """a, b: [B, S, L] (S % chunk == 0, L % block_l == 0).

    Returns (h [B, S, L] f32, h_last [B, L] f32).
    """
    Bsz, S, L = a.shape
    nc = S // chunk
    nl = L // block_l
    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    h, h_last = pl.pallas_call(
        kernel,
        grid=(Bsz, nl, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_l), lambda bz, l, c: (bz, c, l)),
            pl.BlockSpec((1, chunk, block_l), lambda bz, l, c: (bz, c, l)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_l), lambda bz, l, c: (bz, c, l)),
            pl.BlockSpec((1, 1, block_l), lambda bz, l, c: (bz, 0, l)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, S, L), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, 1, L), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_l), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return h, h_last[:, 0]
