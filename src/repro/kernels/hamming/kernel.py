"""Hamming(31,26) + constant-multiplier Pallas kernels — the paper's own
computation modules (§V-B), bit-parallel over int32 VPU lanes.

The FPGA implements these as combinational LUT logic fed one 32-bit word per
cycle by the WB slave interface. The TPU-native equivalent processes a
(8 x 128)-word tile per VPU issue: every bit position of the codeword is a
shift/mask/xor over the whole tile, and the parity computation folds with
the same xor-halving trick the LZC arbiter family uses (no popcount unit
needed). Throughput per grid cell is 1024 words — the paper's whole 16 KB
use case is four cells.

Data bits sit at codeword positions {1..31} \\ {1,2,4,8,16}; parity bit at
2^i covers positions with bit i set (even parity); the decoder's syndrome is
the 1-indexed error position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

PARITY_POS = (1, 2, 4, 8, 16)
DATA_POS = tuple(p for p in range(1, 32) if p not in PARITY_POS)
COVER_MASKS = tuple(
    sum(1 << (p - 1) for p in range(1, 32) if (p >> i) & 1) for i in range(5))
DATA_MASK26 = (1 << 26) - 1


def _parity(x: jax.Array) -> jax.Array:
    """Even-parity bit of each lane via xor-halving (VPU shifts, no popcount)."""
    x = x ^ (x >> 16)
    x = x ^ (x >> 8)
    x = x ^ (x >> 4)
    x = x ^ (x >> 2)
    x = x ^ (x >> 1)
    return x & 1


def _encode_tile(data: jax.Array) -> jax.Array:
    data = data & DATA_MASK26
    code = jnp.zeros_like(data)
    for k, pos in enumerate(DATA_POS):
        code = code | (((data >> k) & 1) << (pos - 1))
    for i, ppos in enumerate(PARITY_POS):
        par = _parity(code & COVER_MASKS[i])
        code = code | (par << (ppos - 1))
    return code


def _decode_tile(code: jax.Array):
    code = code & ((1 << 31) - 1)
    syndrome = jnp.zeros_like(code)
    for i in range(5):
        syndrome = syndrome | (_parity(code & COVER_MASKS[i]) << i)
    corrected = (syndrome != 0).astype(jnp.int32)
    flip = jnp.where(syndrome != 0, 1 << (jnp.maximum(syndrome, 1) - 1), 0)
    fixed = code ^ flip
    data = jnp.zeros_like(code)
    for k, pos in enumerate(DATA_POS):
        data = data | (((fixed >> (pos - 1)) & 1) << k)
    return data, corrected


def _encode_kernel(x_ref, o_ref):
    o_ref[...] = _encode_tile(x_ref[...])


def _decode_kernel(x_ref, data_ref, corr_ref):
    data, corr = _decode_tile(x_ref[...])
    data_ref[...] = data
    corr_ref[...] = corr


def _mul_kernel(x_ref, o_ref, *, constant: int):
    # 32-bit wraparound multiply (the FPGA multiplier truncates to 32 bits).
    # Reinterpret the constant as a signed 32-bit lane value.
    c32 = constant & 0xFFFFFFFF
    if c32 >= 1 << 31:
        c32 -= 1 << 32
    o_ref[...] = x_ref[...] * jnp.int32(c32)


_TILE = (8, 128)


def _call_elementwise(kernel, x: jax.Array, n_out: int, interpret: bool):
    R, Ccols = x.shape
    grid = (R // _TILE[0],)
    spec = pl.BlockSpec((_TILE[0], Ccols), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((R, Ccols), jnp.int32)
                 for _ in range(n_out)]
    out_specs = [spec] * n_out
    if n_out == 1:
        out_shape, out_specs = out_shape[0], out_specs[0]
    return pl.pallas_call(
        kernel, grid=grid, in_specs=[spec], out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret)(x)


@functools.partial(jax.jit, static_argnames=("interpret",))
def encode_call(x: jax.Array, *, interpret: bool = False) -> jax.Array:
    return _call_elementwise(_encode_kernel, x, 1, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_call(x: jax.Array, *, interpret: bool = False):
    return _call_elementwise(_decode_kernel, x, 2, interpret)


@functools.partial(jax.jit, static_argnames=("constant", "interpret"))
def mul_call(x: jax.Array, *, constant: int, interpret: bool = False):
    return _call_elementwise(
        functools.partial(_mul_kernel, constant=constant), x, 1, interpret)
