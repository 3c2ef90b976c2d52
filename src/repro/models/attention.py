"""GQA attention: chunked (flash-style) prefill/train path, cached decode path.

The prefill path is a pure-jnp online-softmax attention, double-scanned over
query and key/value chunks so (i) the HLO stays small (one chunk body compiled
once), (ii) peak memory is O(q_chunk x kv_chunk), never O(S^2) — which is what
lets 32k prefill lower on a 16 GB chip, and (iii) sliding-window attention
iterates only the banded kv chunks, making SWA prefill genuinely
sub-quadratic rather than masked-quadratic.

The Pallas flash kernel (``repro.kernels.flash_attention``) implements the
same contract for the TPU deploy path; this module is the XLA fallback used
by the CPU dry-run and the kernel's oracle.

Both paths run under the op-name scope ``attn.core``, so a device trace can
time attention apart from the projections around it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import apply_rope  # re-export for layer code
from repro.models.common import scan_or_unroll

NEG_INF = -1e30


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: [B, Sq, K, G, D]; k: [B, Sk, K, D] -> scores [B, K, G, Sq, Sk]."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32),
                      k.astype(jnp.float32))


def _gqa_out(p: jax.Array, v: jax.Array) -> jax.Array:
    """p: [B, K, G, Sq, Sk]; v: [B, Sk, K, D] -> [B, K, G, Sq, D]."""
    return jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))


@jax.named_scope("attn.core")
def attention_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      q_chunk: int = 512, kv_chunk: int = 1024,
                      q_offset: int = 0, unroll: bool = False) -> jax.Array:
    """Chunked online-softmax attention.

    q: [B, Sq, H, D]; k, v: [B, Sk, Kv, D] with H = Kv * G (GQA).
    ``window``: sliding-window size (attend to keys in (pos-window, pos]).
    ``q_offset``: absolute position of q[0] relative to k[0] (cross-chunk
    prefill continuation). Returns [B, Sq, H, D] in q.dtype.
    """
    B, Sq, H, D = q.shape
    _, Sk, Kv, _ = k.shape
    G = H // Kv
    scale = D ** -0.5
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    # Pad sequence dims to chunk multiples.
    pq = (-Sq) % q_chunk
    pk = (-Sk) % kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    nq, nk = qp.shape[1] // q_chunk, kp.shape[1] // kv_chunk
    qp = qp.reshape(B, nq, q_chunk, Kv, G, D) * scale
    kp = kp.reshape(B, nk, kv_chunk, Kv, D)
    vp = vp.reshape(B, nk, kv_chunk, Kv, D)

    kv_per_q = nk
    banded = window is not None and causal
    if banded:
        # A q chunk only sees kv chunks covering (q_start - window, q_end].
        kv_per_q = min(nk, (window + q_chunk) // kv_chunk + 2)

    def q_body(_, qi):
        qc = jnp.take(qp, qi, axis=1)                    # [B, qc, Kv, G, D]
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_body(carry, kj_raw):
            m, l, acc = carry
            chunk_ok = (kj_raw >= 0) & (kj_raw < nk)     # guard band overrun
            kj = jnp.clip(kj_raw, 0, nk - 1)
            kc = jnp.take(kp, kj, axis=1)                # [B, kc, Kv, D]
            vc = jnp.take(vp, kj, axis=1)
            k_pos = kj * kv_chunk + jnp.arange(kv_chunk)
            s = _gqa_scores(qc, kc)                      # [B,Kv,G,qc,kc]
            mask = k_pos[None, :] <= (q_pos[:, None] if causal
                                      else jnp.full_like(q_pos[:, None],
                                                         jnp.iinfo(jnp.int32).max))
            if window is not None:
                mask &= k_pos[None, :] > (q_pos[:, None] - window)
            mask &= k_pos[None, :] < Sk                  # kv padding
            mask &= chunk_ok
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + _gqa_out(p, vc)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Kv, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kv, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Kv, G, q_chunk, D), jnp.float32)
        if banded:
            first = (q_pos[0] - (window - 1)) // kv_chunk
            kjs = jnp.maximum(first, 0) + jnp.arange(kv_per_q)
        else:
            kjs = jnp.arange(kv_per_q)
        (m, l, acc), _ = scan_or_unroll(kv_body, (m0, l0, a0), kjs,
                                        unroll=unroll)
        out = acc / jnp.maximum(l, 1e-30)[..., None]     # [B,Kv,G,qc,D]
        return None, out.astype(q.dtype)

    _, outs = scan_or_unroll(q_body, None, jnp.arange(nq),
                             unroll=unroll)               # [nq,B,Kv,G,qc,D]
    out = jnp.transpose(outs, (1, 0, 4, 2, 3, 5))          # [B,nq,qc,Kv,G,D]
    out = out.reshape(B, nq * q_chunk, Kv * G, D)
    return out[:, :Sq]


# ----------------------------------------------------------------------
# KV cache + decode
# ----------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class KVCache:
    """Per-layer-stacked KV cache. ``positions`` holds the absolute position
    stored in each slot (-1 = empty); sliding-window archs use a ring buffer
    of ``window`` slots, so the 524k-decode cache stays bounded."""

    k: jax.Array           # [L, B, S, Kv, D]  (post-rope keys)
    v: jax.Array           # [L, B, S, Kv, D]
    positions: jax.Array   # [B, S] int32
    length: jax.Array      # [] int32 — number of tokens absorbed so far


def init_cache(n_layers: int, batch: int, max_len: int, n_kv: int, head_dim: int,
               *, window: Optional[int] = None, dtype=jnp.bfloat16) -> KVCache:
    slots = min(window, max_len) if window else max_len
    return KVCache(
        k=jnp.zeros((n_layers, batch, slots, n_kv, head_dim), dtype),
        v=jnp.zeros((n_layers, batch, slots, n_kv, head_dim), dtype),
        positions=jnp.full((batch, slots), -1, jnp.int32),
        length=jnp.zeros((), jnp.int32))


def cache_write(cache_k: jax.Array, cache_v: jax.Array, positions: jax.Array,
                k_new: jax.Array, v_new: jax.Array, pos: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Write one token's K/V at slot ``pos % slots`` (ring for SWA).

    cache_k/v: [B, S, Kv, D]; k_new/v_new: [B, 1, Kv, D]; pos: [] int32.
    """
    slots = cache_k.shape[1]
    slot = (pos % slots).astype(jnp.int32)
    ck = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype),
                                      (0, slot, 0, 0))
    pcol = jnp.full((positions.shape[0], 1), pos, jnp.int32)
    pp = jax.lax.dynamic_update_slice(positions, pcol, (0, slot))
    return ck, cv, pp


@jax.named_scope("attn.core")
def attention_decode(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                     slot_positions: jax.Array, pos: jax.Array,
                     window: Optional[int] = None) -> jax.Array:
    """Single-token attention against the cache.

    q: [B, 1, H, D]; cache_k/v: [B, S, Kv, D]; slot_positions: [B, S].
    Returns [B, 1, H, D].
    """
    B, _, H, D = q.shape
    Kv = cache_k.shape[2]
    G = H // Kv
    qf = q.reshape(B, 1, Kv, G, D) * (D ** -0.5)
    s = _gqa_scores(qf, cache_k)[..., 0, :]             # [B, Kv, G, S]
    valid = (slot_positions >= 0) & (slot_positions <= pos)
    if window is not None:
        valid &= slot_positions > pos - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, cache_v.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)
