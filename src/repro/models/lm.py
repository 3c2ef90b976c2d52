"""Model assembly: dense / MoE / VLM / enc-dec / SSM / hybrid LMs.

One uniform contract per family (``Model``):

- ``init(key)``                 — parameters (stacked [L, ...] for lax.scan)
- ``param_specs(multi_pod)``    — PartitionSpec tree (same structure)
- ``loss(params, batch)``       — training objective (chunked vocab xent)
- ``prefill(params, batch)``    — full-sequence forward -> last-token logits
- ``decode_step(params, state, tokens)`` — one token with cached state
- ``decode_state_shapes(shape, multi_pod)`` — ShapeDtypeStructs + specs for
  the dry-run (no allocation)

Design notes (see DESIGN.md §4):
- layers run under ``jax.lax.scan`` with stacked params, so the compiled HLO
  holds ONE block regardless of depth (compile-time and HLO size sanity on a
  1-core host, and the unit XLA pipelines collectives against);
- remat policy is configurable per arch (train only);
- the LM loss is computed in sequence chunks so the [B, S, V] logits tensor
  is never materialised (vocabs here reach 256k);
- normalisation/positional encoding are unified to RMSNorm + RoPE across the
  zoo (documented adaptation); dims, attention patterns (GQA/SWA/MQA), MoE
  routing, SSD and RG-LRU recurrences are faithful.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (ParamDef, dtype_of, init_params,
                                 logical_to_spec, ones_init, rms_norm,
                                 scan_or_unroll, softmax_xent, spec_tree)
from repro.models.config import ModelConfig, ShapeConfig

Params = Any


# ======================================================================
# helpers
# ======================================================================
def stack_defs(defs: Dict[str, Any], n: int) -> Dict[str, Any]:
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, ("layers",) + d.spec, d.init, d.scale),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


def attn_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    d, H, Kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    kv_ax = "tp" if cfg.kv_shard == "tp" else None
    out = {
        "wq": ParamDef((d, H * hd), ("fsdp", "tp")),
        "wk": ParamDef((d, Kv * hd), ("fsdp", kv_ax)),
        "wv": ParamDef((d, Kv * hd), ("fsdp", kv_ax)),
        "wo": ParamDef((H * hd, d), ("tp", "fsdp")),
    }
    if cfg.qkv_bias:
        out.update({
            "bq": ParamDef((H * hd,), ("tp",), init=lambda k, s, t, sc: jnp.zeros(s, t)),
            "bk": ParamDef((Kv * hd,), (kv_ax,), init=lambda k, s, t, sc: jnp.zeros(s, t)),
            "bv": ParamDef((Kv * hd,), (kv_ax,), init=lambda k, s, t, sc: jnp.zeros(s, t)),
        })
    return out


def qkv(params, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """Project + rope. Returns q [B,S,H,hd], k/v [B,S,Kv,hd] (k post-rope)."""
    B, S, _ = x.shape
    H, Kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,de->bse", x, params["wq"])
    k = jnp.einsum("bsd,de->bse", x, params["wk"])
    v = jnp.einsum("bsd,de->bse", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, Kv, hd)
    v = v.reshape(B, S, Kv, hd)
    q = attn.apply_rope(q, positions, cfg.rope_theta)
    k = attn.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def chunked_lm_loss(h: jax.Array, w_head: jax.Array, labels: jax.Array,
                    true_vocab: int, chunk: int = 512,
                    unroll: bool = False) -> jax.Array:
    """Sequence-chunked vocab xent: never materialises [B, S, V] logits."""
    B, S, d = h.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    nc = h.shape[1] // chunk
    hc = h.reshape(B, nc, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(B, nc, chunk).swapaxes(0, 1)
    valid = (jnp.arange(nc * chunk).reshape(nc, chunk) < S)

    @jax.checkpoint
    def body(tot, inp):
        hh, ll, vv = inp
        logits = jnp.einsum("bsd,dv->bsv", hh, w_head)
        per_tok = _xent_per_token(logits, ll, true_vocab)
        return tot + jnp.sum(per_tok * vv[None, :]), None

    tot, _ = scan_or_unroll(body, jnp.zeros((), jnp.float32),
                            (hc, lc, valid), unroll=unroll)
    return tot / (B * S)


def _xent_per_token(logits, labels, true_vocab):
    logits = logits.astype(jnp.float32)
    if logits.shape[-1] > true_vocab:
        mask = jnp.arange(logits.shape[-1]) < true_vocab
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold


def remat_wrap(fn, policy: str):
    if policy == "nothing":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)       # "full": save nothing


def batch_axes(global_batch: int, multi_pod: bool) -> Optional[Any]:
    """Batch sharding that respects divisibility (B=1 long-decode stays
    replicated on the data axis)."""
    need = 32 if multi_pod else 16
    if global_batch % need == 0:
        return ("pod", "data") if multi_pod else "data"
    if global_batch % 16 == 0 and multi_pod:
        return "data"
    return None


# ======================================================================
# Decode state (uniform across families)
# ======================================================================
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DecodeState:
    pos: jax.Array                              # [] int32 — next position
    kv_k: Optional[jax.Array] = None            # [La, B, Sc, Kv, hd]
    kv_v: Optional[jax.Array] = None
    kv_pos: Optional[jax.Array] = None          # [B, Sc]
    cross_k: Optional[jax.Array] = None         # [L, B, F, Kv, hd] (enc-dec)
    cross_v: Optional[jax.Array] = None
    ssm_state: Optional[jax.Array] = None       # [L, B, H, P, N]
    conv_tail: Optional[jax.Array] = None       # [L, B, W-1, convdim]
    rec_h: Optional[jax.Array] = None           # [Lr, B, lru]
    rec_tail: Optional[jax.Array] = None        # [Lr, B, 3, lru]


# ======================================================================
# Base class
# ======================================================================
class LMBase:
    def __init__(self, cfg: ModelConfig):
        cfg.validate()
        self.cfg = cfg
        self.dtype = dtype_of(cfg.dtype)
        # Batch mesh axis for activation sharding constraints. Set by the
        # launcher (build_step) when tracing under a mesh; None disables.
        # Without these constraints the SPMD partitioner resolves the
        # remat-boundary activations inconsistently between the forward and
        # the rematted backward copy and REPLICATES the recompute over the
        # data axis (observed: 2.1x per-layer FLOPs on the 16x16 pod) — see
        # EXPERIMENTS.md §Perf iteration "activation sharding constraints".
        self.batch_axis: Optional[Any] = None

    def constrain(self, x: jax.Array) -> jax.Array:
        """Pin a [B, S, d] activation to (batch-sharded, replicated, ...)."""
        if self.batch_axis is None:
            return x
        spec = P(self.batch_axis, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, spec)

    # ---- embedding / head ------------------------------------------------
    def _embed_defs(self) -> Dict[str, Any]:
        cfg = self.cfg
        out = {
            "embed": ParamDef((cfg.vocab_padded, cfg.d_model), ("tp", "fsdp")),
            "final_norm": ParamDef((cfg.d_model,), (None,), init=ones_init),
        }
        if not cfg.tied_embeddings:
            out["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_padded),
                                      ("fsdp", "tp"))
        return out

    def _head_weight(self, params):
        if self.cfg.tied_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def _embed(self, params, tokens):
        return jnp.take(params["embed"], tokens, axis=0)

    # ---- public API -------------------------------------------------------
    def param_defs(self) -> Dict[str, Any]:
        raise NotImplementedError

    def init(self, key: jax.Array) -> Params:
        # One jitted program: each leaf's f32 draw fuses into its cast, so
        # no full-size f32 copy of a leaf is ever held (eager init peaks at
        # ~3x a bf16 leaf's bytes — more than a chip holds for an 8-expert
        # d=4096 layer stack).
        return jax.jit(
            lambda k: init_params(self.param_defs(), k, self.dtype))(key)

    def param_specs(self, multi_pod: bool) -> Params:
        return spec_tree(self.param_defs(), multi_pod=multi_pod)

    def param_shapes(self) -> Params:
        return jax.tree.map(
            lambda d: jax.ShapeDtypeStruct(d.shape, self.dtype),
            self.param_defs(), is_leaf=lambda x: isinstance(x, ParamDef))

    def n_params(self) -> int:
        import math
        return sum(math.prod(d.shape)
                   for d in jax.tree.leaves(
                       self.param_defs(),
                       is_leaf=lambda x: isinstance(x, ParamDef)))

    # ---- inputs -------------------------------------------------------
    def input_shapes(self, shape: ShapeConfig, multi_pod: bool
                     ) -> Tuple[Dict[str, jax.ShapeDtypeStruct], Dict[str, P]]:
        """(ShapeDtypeStructs, PartitionSpecs) for the data batch."""
        B, S = shape.global_batch, shape.seq_len
        bspec = batch_axes(B, multi_pod)
        structs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        specs = {"tokens": P(bspec, None)}
        if shape.kind == "train":
            structs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
            specs["labels"] = P(bspec, None)
        if shape.kind == "decode":
            structs["tokens"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
            specs["tokens"] = P(bspec, None)
        if self.cfg.n_vision_patches:
            structs["patches"] = jax.ShapeDtypeStruct(
                (B, self.cfg.n_vision_patches, self.cfg.d_model), self.dtype)
            specs["patches"] = P(bspec, None, None)
        if self.cfg.family == "encdec":
            structs["frames"] = jax.ShapeDtypeStruct(
                (B, self.cfg.encoder_len, self.cfg.d_model), self.dtype)
            specs["frames"] = P(bspec, None, None)
        return structs, specs

    def decode_state_shapes(self, shape, multi_pod):
        raise NotImplementedError

    # subclasses implement
    def loss(self, params, batch):
        raise NotImplementedError

    def prefill(self, params, batch):
        raise NotImplementedError

    def decode_step(self, params, state: DecodeState, batch):
        raise NotImplementedError


# ======================================================================
# Dense / MoE / VLM decoder-only LM
# ======================================================================
class DenseLM(LMBase):
    """Decoder-only transformer: GQA (+optional SWA window, qkv-bias), with
    per-layer MLP or crossbar-dispatched MoE."""

    def _layer_defs(self) -> Dict[str, Any]:
        cfg = self.cfg
        d = {
            "norm1": ParamDef((cfg.d_model,), (None,), init=ones_init),
            "attn": attn_defs(cfg),
            "norm2": ParamDef((cfg.d_model,), (None,), init=ones_init),
        }
        if cfg.moe is not None:
            d["moe"] = moe_mod.moe_defs(cfg.d_model, cfg.d_ff, cfg.moe,
                                        cfg.mlp_act)
        else:
            d["mlp"] = mlp_mod.mlp_defs(cfg.d_model, cfg.d_ff, cfg.mlp_act)
        return d

    def param_defs(self) -> Dict[str, Any]:
        out = self._embed_defs()
        out["layers"] = stack_defs(self._layer_defs(), self.cfg.n_layers)
        return out

    # ---- forward ------------------------------------------------------
    def _block(self, lp, x, positions, moe_group: int,
               moe_capacity: Optional[int] = None):
        """One layer: (x, aux loss, (k, v, dropped)) — the layer's post-RoPE
        keys and values and the packets its experts dropped, which only
        ``prefill_cache`` keeps."""
        cfg = self.cfg
        x = self.constrain(x)
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        q, k, v = qkv(lp["attn"], h, cfg, positions)
        o = attn.attention_prefill(q, k, v, causal=True,
                                   window=cfg.attn_window,
                                   unroll=not cfg.scan_layers)
        o = jnp.einsum("bse,ed->bsd",
                       o.reshape(o.shape[0], o.shape[1], -1), lp["attn"]["wo"])
        x = x + o
        h2 = rms_norm(x, lp["norm2"], cfg.norm_eps)
        if cfg.moe is not None:
            y, stats = moe_mod.moe_apply(lp["moe"], h2, cfg.moe, cfg.mlp_act,
                                         group_size=moe_group,
                                         dispatch_impl=cfg.moe.dispatch,
                                         capacity=moe_capacity,
                                         kernel_mode=cfg.moe.kernel_mode)
            aux, dropped = stats["aux_loss"], stats["dropped"]
        else:
            y = mlp_mod.mlp_apply(lp["mlp"], h2, cfg.mlp_act)
            aux = jnp.zeros((), jnp.float32)
            dropped = jnp.zeros((), jnp.int32)
        return x + y, aux, (k, v, dropped)

    def _backbone(self, params, x, positions, *, train: bool,
                  moe_group: int = 1024,
                  moe_capacity: Optional[int] = None,
                  keep_kv: bool = False):
        """Final-normed hidden states and the summed aux loss; with
        ``keep_kv`` also every layer's (k, v, dropped), stacked [L, ...]."""
        cfg = self.cfg

        def body(carry, lp):
            xx, aux = carry
            xx, a, kv = self._block(lp, xx, positions, moe_group,
                                    moe_capacity)
            return (xx, aux + a), (kv if keep_kv else None)

        fn = remat_wrap(body, cfg.remat if train else "nothing")
        (x, aux), kv = scan_or_unroll(fn, (x, jnp.zeros((), jnp.float32)),
                                      params["layers"],
                                      unroll=not cfg.scan_layers)
        x = self.constrain(x)
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return (h, aux, kv) if keep_kv else (h, aux)

    def _inputs_embed(self, params, batch):
        x = self._embed(params, batch["tokens"])
        if self.cfg.n_vision_patches and "patches" in batch:
            Pn = self.cfg.n_vision_patches
            x = jnp.concatenate([batch["patches"].astype(x.dtype),
                                 x[:, Pn:]], axis=1)
        return x

    def loss(self, params, batch):
        cfg = self.cfg
        x = self._inputs_embed(params, batch)
        positions = jnp.arange(x.shape[1])[None, :]
        h, aux = self._backbone(params, x, positions, train=True,
                                moe_group=min(1024, x.shape[0] * x.shape[1]))
        lm = chunked_lm_loss(h, self._head_weight(params), batch["labels"],
                             cfg.vocab, unroll=not cfg.scan_layers)
        return lm + 0.01 * aux

    def prefill(self, params, batch):
        x = self._inputs_embed(params, batch)
        positions = jnp.arange(x.shape[1])[None, :]
        h, _ = self._backbone(params, x, positions, train=False)
        logits = jnp.einsum("bd,dv->bv", h[:, -1], self._head_weight(params))
        return logits

    def prefill_cache(self, params, tokens: jax.Array, max_len: int,
                      capacity: Optional[int] = None):
        """One parallel causal prefill of ``tokens`` [B, S] that also fills
        the KV cache: ``(logits [B, V] of the last position, DecodeState,
        dropped)``.

        The state is what S ``decode_step`` calls leave behind from an
        empty ``init_decode_state(B, max_len)``: positions 0..S-1 hold each
        layer's post-RoPE k and v, ``kv_pos[:, :S] = arange(S)`` (the rest
        -1) and ``pos = S``.  The prompt must fit the cache without
        wrapping (S <= its slots).  Each sequence is its own MoE group;
        ``capacity`` is the experts' quota in it (default: the
        configuration's rule), and ``dropped`` counts the packets it
        dropped over all layers.
        """
        B, S = tokens.shape
        state = self.init_decode_state(B, max_len)
        if S > state.kv_k.shape[2]:
            raise ValueError(f"a prompt of {S} tokens does not fit a cache "
                             f"of {state.kv_k.shape[2]} slots")
        x = self._embed(params, tokens)
        h, _, (k, v, dropped) = self._backbone(
            params, x, jnp.arange(S)[None, :], train=False, moe_group=S,
            moe_capacity=capacity, keep_kv=True)
        logits = jnp.einsum("bd,dv->bv", h[:, -1], self._head_weight(params))
        at = (0, 0, 0, 0, 0)
        state = dataclasses.replace(
            state, pos=jnp.asarray(S, jnp.int32),
            kv_k=jax.lax.dynamic_update_slice(
                state.kv_k, k.astype(state.kv_k.dtype), at),
            kv_v=jax.lax.dynamic_update_slice(
                state.kv_v, v.astype(state.kv_v.dtype), at),
            kv_pos=state.kv_pos.at[:, :S].set(jnp.arange(S, dtype=jnp.int32)))
        return logits, state, jnp.sum(dropped)

    # ---- decode -------------------------------------------------------
    def decode_state_shapes(self, shape: ShapeConfig, multi_pod: bool):
        cfg = self.cfg
        B = shape.global_batch
        slots = min(cfg.attn_window, shape.seq_len) if cfg.attn_window \
            else shape.seq_len
        bspec = batch_axes(B, multi_pod)
        kv_shape = (cfg.n_layers, B, slots, cfg.n_kv_heads, cfg.hd)
        structs = DecodeState(
            pos=jax.ShapeDtypeStruct((), jnp.int32),
            kv_k=jax.ShapeDtypeStruct(kv_shape, self.dtype),
            kv_v=jax.ShapeDtypeStruct(kv_shape, self.dtype),
            kv_pos=jax.ShapeDtypeStruct((B, slots), jnp.int32))
        specs = DecodeState(
            pos=P(),
            kv_k=P(None, bspec, "model", None, None),
            kv_v=P(None, bspec, "model", None, None),
            kv_pos=P(bspec, "model"))
        return structs, specs

    def init_decode_state(self, batch: int, max_len: int) -> DecodeState:
        cfg = self.cfg
        slots = min(cfg.attn_window, max_len) if cfg.attn_window else max_len
        z = lambda *s: jnp.zeros(s, self.dtype)
        return DecodeState(
            pos=jnp.zeros((), jnp.int32),
            kv_k=z(cfg.n_layers, batch, slots, cfg.n_kv_heads, cfg.hd),
            kv_v=z(cfg.n_layers, batch, slots, cfg.n_kv_heads, cfg.hd),
            kv_pos=jnp.full((batch, slots), -1, jnp.int32))

    def decode_step(self, params, state: DecodeState, batch):
        cfg = self.cfg
        tok = batch["tokens"]                         # [B, 1]
        x = self._embed(params, tok)
        pos = state.pos
        positions = jnp.full((x.shape[0], 1), pos, jnp.int32)

        def body(xx, inp):
            lp, ck, cv = inp
            h = rms_norm(xx, lp["norm1"], cfg.norm_eps)
            q, k, v = qkv(lp["attn"], h, cfg, positions)
            ck, cv, kvpos = attn.cache_write(ck, cv, state.kv_pos, k, v, pos)
            o = attn.attention_decode(q, ck, cv, kvpos, pos,
                                      window=cfg.attn_window)
            o = jnp.einsum("bse,ed->bsd",
                           o.reshape(o.shape[0], 1, -1), lp["attn"]["wo"])
            xx = xx + o
            h2 = rms_norm(xx, lp["norm2"], cfg.norm_eps)
            if cfg.moe is not None:
                y, _ = moe_mod.moe_apply(lp["moe"], h2, cfg.moe, cfg.mlp_act,
                                         group_size=h2.shape[0],
                                         dispatch_impl=cfg.moe.dispatch,
                                         kernel_mode=cfg.moe.kernel_mode)
            else:
                y = mlp_mod.mlp_apply(lp["mlp"], h2, cfg.mlp_act)
            return xx + y, (ck, cv)

        x, (ck, cv) = scan_or_unroll(
            body, x, (params["layers"], state.kv_k, state.kv_v),
            unroll=not cfg.scan_layers)
        # kv_pos update is layer-independent: recompute once.
        slots = state.kv_k.shape[2]
        slot = (pos % slots).astype(jnp.int32)
        kv_pos = jax.lax.dynamic_update_slice(
            state.kv_pos, jnp.full((x.shape[0], 1), pos, jnp.int32), (0, slot))
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, -1], self._head_weight(params))
        new_state = dataclasses.replace(state, pos=pos + 1, kv_k=ck, kv_v=cv,
                                        kv_pos=kv_pos)
        return logits, new_state


# ======================================================================
# Mamba-2 (attention-free SSM)
# ======================================================================
class SSMLM(LMBase):
    def param_defs(self) -> Dict[str, Any]:
        cfg = self.cfg
        layer = {
            "norm": ParamDef((cfg.d_model,), (None,), init=ones_init),
            "mixer": ssm_mod.ssm_defs(cfg.d_model, cfg.ssm),
        }
        out = self._embed_defs()
        out["layers"] = stack_defs(layer, cfg.n_layers)
        return out

    def _backbone(self, params, x, *, train: bool):
        cfg = self.cfg

        def body(xx, lp):
            xx = self.constrain(xx)
            h = rms_norm(xx, lp["norm"], cfg.norm_eps)
            y, _, _ = ssm_mod.ssm_apply(lp["mixer"], h, cfg.ssm,
                                        unroll=not cfg.scan_layers)
            return xx + y, None

        fn = remat_wrap(body, cfg.remat if train else "nothing")
        x, _ = scan_or_unroll(fn, x, params["layers"],
                              unroll=not cfg.scan_layers)
        return rms_norm(x, params["final_norm"], cfg.norm_eps)

    def loss(self, params, batch):
        x = self._embed(params, batch["tokens"])
        h = self._backbone(params, x, train=True)
        return chunked_lm_loss(h, self._head_weight(params), batch["labels"],
                               self.cfg.vocab,
                               unroll=not self.cfg.scan_layers)

    def prefill(self, params, batch):
        x = self._embed(params, batch["tokens"])
        h = self._backbone(params, x, train=False)
        return jnp.einsum("bd,dv->bv", h[:, -1], self._head_weight(params))

    def _state_dims(self):
        cfg = self.cfg
        ssm = cfg.ssm
        H = ssm.n_heads(cfg.d_model)
        conv_dim = ssm.expand * cfg.d_model + 2 * ssm.d_state
        return H, ssm.head_dim, ssm.d_state, conv_dim, ssm.conv_width

    def decode_state_shapes(self, shape: ShapeConfig, multi_pod: bool):
        cfg = self.cfg
        B = shape.global_batch
        H, Pd, N, conv_dim, W = self._state_dims()
        bspec = batch_axes(B, multi_pod)
        structs = DecodeState(
            pos=jax.ShapeDtypeStruct((), jnp.int32),
            ssm_state=jax.ShapeDtypeStruct((cfg.n_layers, B, H, Pd, N),
                                           jnp.float32),
            conv_tail=jax.ShapeDtypeStruct((cfg.n_layers, B, W - 1, conv_dim),
                                           self.dtype))
        specs = DecodeState(
            pos=P(),
            ssm_state=P(None, bspec, "model", None, None),
            conv_tail=P(None, bspec, None, "model"))
        return structs, specs

    def init_decode_state(self, batch: int, max_len: int) -> DecodeState:
        cfg = self.cfg
        H, Pd, N, conv_dim, W = self._state_dims()
        return DecodeState(
            pos=jnp.zeros((), jnp.int32),
            ssm_state=jnp.zeros((cfg.n_layers, batch, H, Pd, N), jnp.float32),
            conv_tail=jnp.zeros((cfg.n_layers, batch, W - 1, conv_dim),
                                self.dtype))

    def decode_step(self, params, state: DecodeState, batch):
        cfg = self.cfg
        x = self._embed(params, batch["tokens"])

        def body(xx, inp):
            lp, st, tail = inp
            h = rms_norm(xx, lp["norm"], cfg.norm_eps)
            y, st2, tail2 = ssm_mod.ssm_apply(lp["mixer"], h, cfg.ssm,
                                              state=st, conv_tail=tail,
                                              decode=True)
            return xx + y, (st2, tail2)

        x, (st, tail) = scan_or_unroll(
            body, x, (params["layers"], state.ssm_state, state.conv_tail),
            unroll=not cfg.scan_layers)
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, -1], self._head_weight(params))
        return logits, dataclasses.replace(state, pos=state.pos + 1,
                                           ssm_state=st, conv_tail=tail)


# ======================================================================
# RecurrentGemma-style hybrid: (rec, rec, local-attn) groups
# ======================================================================
class HybridLM(LMBase):
    """`pattern_rec` RG-LRU blocks then one local-attention block per group;
    trailing non-group layers are recurrent blocks."""

    def __init__(self, cfg: ModelConfig):
        super().__init__(cfg)
        per = cfg.hybrid.pattern_rec + 1
        self.n_groups = cfg.n_layers // per
        self.n_trail = cfg.n_layers - self.n_groups * per
        self.lru = cfg.hybrid.lru_width or cfg.d_model

    def _rec_defs(self):
        cfg = self.cfg
        return {
            "norm1": ParamDef((cfg.d_model,), (None,), init=ones_init),
            "rec": rglru_mod.rglru_defs(cfg.d_model, self.lru),
            "norm2": ParamDef((cfg.d_model,), (None,), init=ones_init),
            "mlp": mlp_mod.mlp_defs(cfg.d_model, cfg.d_ff, cfg.mlp_act),
        }

    def _attn_block_defs(self):
        cfg = self.cfg
        return {
            "norm1": ParamDef((cfg.d_model,), (None,), init=ones_init),
            "attn": attn_defs(cfg),
            "norm2": ParamDef((cfg.d_model,), (None,), init=ones_init),
            "mlp": mlp_mod.mlp_defs(cfg.d_model, cfg.d_ff, cfg.mlp_act),
        }

    def param_defs(self) -> Dict[str, Any]:
        cfg = self.cfg
        group = {
            "rec": stack_defs(self._rec_defs(), cfg.hybrid.pattern_rec),
            "attn_blk": self._attn_block_defs(),
        }
        out = self._embed_defs()
        out["groups"] = stack_defs(group, self.n_groups)
        if self.n_trail:
            out["trail"] = stack_defs(self._rec_defs(), self.n_trail)
        return out

    # ---- block bodies ---------------------------------------------------
    def _rec_block(self, lp, x, h0=None, tail=None, decode=False):
        cfg = self.cfg
        if not decode:
            x = self.constrain(x)
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        y, h_last, tail2 = rglru_mod.rglru_block_apply(
            lp["rec"], h, h0=h0, conv_tail=tail, decode=decode)
        x = x + y
        h2 = rms_norm(x, lp["norm2"], cfg.norm_eps)
        return x + mlp_mod.mlp_apply(lp["mlp"], h2, cfg.mlp_act), h_last, tail2

    def _attn_block(self, lp, x, positions):
        cfg = self.cfg
        x = self.constrain(x)
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        q, k, v = qkv(lp["attn"], h, cfg, positions)
        o = attn.attention_prefill(q, k, v, causal=True,
                                   window=cfg.hybrid.attn_window,
                                   unroll=not cfg.scan_layers)
        x = x + jnp.einsum("bse,ed->bsd",
                           o.reshape(o.shape[0], o.shape[1], -1),
                           lp["attn"]["wo"])
        h2 = rms_norm(x, lp["norm2"], cfg.norm_eps)
        return x + mlp_mod.mlp_apply(lp["mlp"], h2, cfg.mlp_act)

    def _backbone(self, params, x, positions, *, train: bool):
        cfg = self.cfg

        unroll = not cfg.scan_layers

        def rec_scan(xx, stacked):
            def rbody(c, lp):
                c2, _, _ = self._rec_block(lp, c)
                return c2, None
            out, _ = scan_or_unroll(rbody, xx, stacked, unroll=unroll)
            return out

        def gbody(xx, gp):
            xx = rec_scan(xx, gp["rec"])
            return self._attn_block(gp["attn_blk"], xx, positions), None

        fn = remat_wrap(gbody, cfg.remat if train else "nothing")
        x, _ = scan_or_unroll(fn, x, params["groups"], unroll=unroll)
        if self.n_trail:
            x = rec_scan(x, params["trail"])
        return rms_norm(x, params["final_norm"], cfg.norm_eps)

    def loss(self, params, batch):
        x = self._embed(params, batch["tokens"])
        positions = jnp.arange(x.shape[1])[None, :]
        h = self._backbone(params, x, positions, train=True)
        return chunked_lm_loss(h, self._head_weight(params), batch["labels"],
                               self.cfg.vocab,
                               unroll=not self.cfg.scan_layers)

    def prefill(self, params, batch):
        x = self._embed(params, batch["tokens"])
        positions = jnp.arange(x.shape[1])[None, :]
        h = self._backbone(params, x, positions, train=False)
        return jnp.einsum("bd,dv->bv", h[:, -1], self._head_weight(params))

    # ---- decode -------------------------------------------------------
    def decode_state_shapes(self, shape: ShapeConfig, multi_pod: bool):
        cfg = self.cfg
        B = shape.global_batch
        slots = min(cfg.hybrid.attn_window, shape.seq_len)
        n_rec = self.n_groups * cfg.hybrid.pattern_rec + self.n_trail
        bspec = batch_axes(B, multi_pod)
        kv = (self.n_groups, B, slots, cfg.n_kv_heads, cfg.hd)
        structs = DecodeState(
            pos=jax.ShapeDtypeStruct((), jnp.int32),
            kv_k=jax.ShapeDtypeStruct(kv, self.dtype),
            kv_v=jax.ShapeDtypeStruct(kv, self.dtype),
            kv_pos=jax.ShapeDtypeStruct((B, slots), jnp.int32),
            rec_h=jax.ShapeDtypeStruct((n_rec, B, self.lru), jnp.float32),
            rec_tail=jax.ShapeDtypeStruct((n_rec, B, 3, self.lru), self.dtype))
        kv_seq_axis = "model" if cfg.n_kv_heads == 1 else None
        specs = DecodeState(
            pos=P(), kv_k=P(None, bspec, kv_seq_axis, None, None),
            kv_v=P(None, bspec, kv_seq_axis, None, None),
            kv_pos=P(bspec, kv_seq_axis),
            rec_h=P(None, bspec, "model"),
            rec_tail=P(None, bspec, None, "model"))
        return structs, specs

    def init_decode_state(self, batch: int, max_len: int) -> DecodeState:
        cfg = self.cfg
        slots = min(cfg.hybrid.attn_window, max_len)
        n_rec = self.n_groups * cfg.hybrid.pattern_rec + self.n_trail
        z = lambda *s: jnp.zeros(s, self.dtype)
        return DecodeState(
            pos=jnp.zeros((), jnp.int32),
            kv_k=z(self.n_groups, batch, slots, cfg.n_kv_heads, cfg.hd),
            kv_v=z(self.n_groups, batch, slots, cfg.n_kv_heads, cfg.hd),
            kv_pos=jnp.full((batch, slots), -1, jnp.int32),
            rec_h=jnp.zeros((n_rec, batch, self.lru), jnp.float32),
            rec_tail=z(n_rec, batch, 3, self.lru))

    def decode_step(self, params, state: DecodeState, batch):
        cfg = self.cfg
        x = self._embed(params, batch["tokens"])
        pos = state.pos
        positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
        pr = cfg.hybrid.pattern_rec
        n_grp_rec = self.n_groups * pr
        rec_h_g = state.rec_h[:n_grp_rec].reshape(self.n_groups, pr,
                                                  *state.rec_h.shape[1:])
        rec_t_g = state.rec_tail[:n_grp_rec].reshape(self.n_groups, pr,
                                                     *state.rec_tail.shape[1:])

        def gbody(xx, inp):
            gp, hs, tails, ck, cv = inp

            def rbody(c, rin):
                lp, h0, tl = rin
                c2, h_last, tl2 = self._rec_block(lp, c, h0=h0, tail=tl,
                                                  decode=True)
                return c2, (h_last, tl2)

            xx, (h_new, t_new) = scan_or_unroll(
                rbody, xx, (gp["rec"], hs, tails),
                unroll=not cfg.scan_layers)
            lp = gp["attn_blk"]
            h = rms_norm(xx, lp["norm1"], cfg.norm_eps)
            q, k, v = qkv(lp["attn"], h, cfg, positions)
            ck, cv, kvpos = attn.cache_write(ck, cv, state.kv_pos, k, v, pos)
            o = attn.attention_decode(q, ck, cv, kvpos, pos,
                                      window=cfg.hybrid.attn_window)
            xx = xx + jnp.einsum("bse,ed->bsd", o.reshape(o.shape[0], 1, -1),
                                 lp["attn"]["wo"])
            h2 = rms_norm(xx, lp["norm2"], cfg.norm_eps)
            xx = xx + mlp_mod.mlp_apply(lp["mlp"], h2, cfg.mlp_act)
            return xx, (h_new, t_new, ck, cv)

        x, (h_new, t_new, ck, cv) = scan_or_unroll(
            gbody, x, (params["groups"], rec_h_g, rec_t_g,
                       state.kv_k, state.kv_v), unroll=not cfg.scan_layers)

        trail_h, trail_t = (state.rec_h[n_grp_rec:], state.rec_tail[n_grp_rec:])
        if self.n_trail:
            def tbody(c, rin):
                lp, h0, tl = rin
                c2, h_last, tl2 = self._rec_block(lp, c, h0=h0, tail=tl,
                                                  decode=True)
                return c2, (h_last, tl2)
            x, (trail_h, trail_t) = scan_or_unroll(
                tbody, x, (params["trail"], trail_h, trail_t),
                unroll=not cfg.scan_layers)

        slots = state.kv_k.shape[2]
        slot = (pos % slots).astype(jnp.int32)
        kv_pos = jax.lax.dynamic_update_slice(
            state.kv_pos, jnp.full((x.shape[0], 1), pos, jnp.int32), (0, slot))
        rec_h = jnp.concatenate([h_new.reshape(n_grp_rec, *h_new.shape[2:]),
                                 trail_h], axis=0)
        rec_tail = jnp.concatenate([t_new.reshape(n_grp_rec, *t_new.shape[2:]),
                                    trail_t], axis=0)
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, -1], self._head_weight(params))
        return logits, dataclasses.replace(
            state, pos=pos + 1, kv_k=ck, kv_v=cv, kv_pos=kv_pos,
            rec_h=rec_h, rec_tail=rec_tail)


# ======================================================================
# Whisper-style encoder-decoder (audio frontend stubbed to frame embeddings)
# ======================================================================
class EncDecLM(LMBase):
    def _enc_layer_defs(self):
        cfg = self.cfg
        return {
            "norm1": ParamDef((cfg.d_model,), (None,), init=ones_init),
            "attn": attn_defs(cfg),
            "norm2": ParamDef((cfg.d_model,), (None,), init=ones_init),
            "mlp": mlp_mod.mlp_defs(cfg.d_model, cfg.d_ff, cfg.mlp_act),
        }

    def _dec_layer_defs(self):
        d = self._enc_layer_defs()
        d["norm_x"] = ParamDef((self.cfg.d_model,), (None,), init=ones_init)
        d["xattn"] = attn_defs(self.cfg)
        return d

    def param_defs(self):
        cfg = self.cfg
        out = self._embed_defs()
        out["enc_layers"] = stack_defs(self._enc_layer_defs(),
                                       cfg.n_encoder_layers)
        out["enc_norm"] = ParamDef((cfg.d_model,), (None,), init=ones_init)
        out["dec_layers"] = stack_defs(self._dec_layer_defs(), cfg.n_layers)
        return out

    def _encode(self, params, frames, *, train: bool):
        cfg = self.cfg
        positions = jnp.arange(frames.shape[1])[None, :]

        def body(xx, lp):
            xx = self.constrain(xx)
            h = rms_norm(xx, lp["norm1"], cfg.norm_eps)
            q, k, v = qkv(lp["attn"], h, cfg, positions)
            o = attn.attention_prefill(q, k, v, causal=False,
                                       unroll=not cfg.scan_layers)
            xx = xx + jnp.einsum("bse,ed->bsd",
                                 o.reshape(o.shape[0], o.shape[1], -1),
                                 lp["attn"]["wo"])
            h2 = rms_norm(xx, lp["norm2"], cfg.norm_eps)
            return xx + mlp_mod.mlp_apply(lp["mlp"], h2, cfg.mlp_act), None

        fn = remat_wrap(body, cfg.remat if train else "nothing")
        x, _ = scan_or_unroll(fn, frames, params["enc_layers"],
                              unroll=not cfg.scan_layers)
        return rms_norm(x, params["enc_norm"], cfg.norm_eps)

    def _dec_block(self, lp, x, enc, positions):
        cfg = self.cfg
        x = self.constrain(x)
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        q, k, v = qkv(lp["attn"], h, cfg, positions)
        o = attn.attention_prefill(q, k, v, causal=True,
                                   unroll=not cfg.scan_layers)
        x = x + jnp.einsum("bse,ed->bsd",
                           o.reshape(o.shape[0], o.shape[1], -1),
                           lp["attn"]["wo"])
        hx = rms_norm(x, lp["norm_x"], cfg.norm_eps)
        enc_pos = jnp.arange(enc.shape[1])[None, :]
        qx, _, _ = qkv(lp["xattn"], hx, cfg,
                       jnp.zeros((x.shape[0], x.shape[1]), jnp.int32))
        kx = jnp.einsum("bsd,de->bse", enc, lp["xattn"]["wk"])
        vx = jnp.einsum("bsd,de->bse", enc, lp["xattn"]["wv"])
        if cfg.qkv_bias:
            kx, vx = kx + lp["xattn"]["bk"], vx + lp["xattn"]["bv"]
        B, F = enc.shape[0], enc.shape[1]
        kx = attn.apply_rope(kx.reshape(B, F, cfg.n_kv_heads, cfg.hd), enc_pos,
                             cfg.rope_theta)
        vx = vx.reshape(B, F, cfg.n_kv_heads, cfg.hd)
        ox = attn.attention_prefill(qx, kx, vx, causal=False,
                                    unroll=not cfg.scan_layers)
        x = x + jnp.einsum("bse,ed->bsd",
                           ox.reshape(ox.shape[0], ox.shape[1], -1),
                           lp["xattn"]["wo"])
        h2 = rms_norm(x, lp["norm2"], cfg.norm_eps)
        return x + mlp_mod.mlp_apply(lp["mlp"], h2, cfg.mlp_act)

    def _decode_stack(self, params, x, enc, positions, *, train: bool):
        cfg = self.cfg

        def body(xx, lp):
            return self._dec_block(lp, xx, enc, positions), None

        fn = remat_wrap(body, cfg.remat if train else "nothing")
        x, _ = scan_or_unroll(fn, x, params["dec_layers"],
                              unroll=not cfg.scan_layers)
        return rms_norm(x, params["final_norm"], cfg.norm_eps)

    def loss(self, params, batch):
        cfg = self.cfg
        enc = self._encode(params, batch["frames"], train=True)
        x = self._embed(params, batch["tokens"])
        positions = jnp.arange(x.shape[1])[None, :]
        h = self._decode_stack(params, x, enc, positions, train=True)
        return chunked_lm_loss(h, self._head_weight(params), batch["labels"],
                               cfg.vocab, unroll=not cfg.scan_layers)

    def prefill(self, params, batch):
        enc = self._encode(params, batch["frames"], train=False)
        x = self._embed(params, batch["tokens"])
        positions = jnp.arange(x.shape[1])[None, :]
        h = self._decode_stack(params, x, enc, positions, train=False)
        return jnp.einsum("bd,dv->bv", h[:, -1], self._head_weight(params))

    # ---- decode -------------------------------------------------------
    def decode_state_shapes(self, shape: ShapeConfig, multi_pod: bool):
        cfg = self.cfg
        B = shape.global_batch
        bspec = batch_axes(B, multi_pod)
        kv = (cfg.n_layers, B, shape.seq_len, cfg.n_kv_heads, cfg.hd)
        xkv = (cfg.n_layers, B, cfg.encoder_len, cfg.n_kv_heads, cfg.hd)
        structs = DecodeState(
            pos=jax.ShapeDtypeStruct((), jnp.int32),
            kv_k=jax.ShapeDtypeStruct(kv, self.dtype),
            kv_v=jax.ShapeDtypeStruct(kv, self.dtype),
            kv_pos=jax.ShapeDtypeStruct((B, shape.seq_len), jnp.int32),
            cross_k=jax.ShapeDtypeStruct(xkv, self.dtype),
            cross_v=jax.ShapeDtypeStruct(xkv, self.dtype))
        specs = DecodeState(
            pos=P(), kv_k=P(None, bspec, "model", None, None),
            kv_v=P(None, bspec, "model", None, None),
            kv_pos=P(bspec, "model"),
            cross_k=P(None, bspec, None, None, None),
            cross_v=P(None, bspec, None, None, None))
        return structs, specs

    def init_decode_state(self, batch: int, max_len: int) -> DecodeState:
        cfg = self.cfg
        z = lambda *s: jnp.zeros(s, self.dtype)
        return DecodeState(
            pos=jnp.zeros((), jnp.int32),
            kv_k=z(cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd),
            kv_v=z(cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd),
            kv_pos=jnp.full((batch, max_len), -1, jnp.int32),
            cross_k=z(cfg.n_layers, batch, cfg.encoder_len, cfg.n_kv_heads,
                      cfg.hd),
            cross_v=z(cfg.n_layers, batch, cfg.encoder_len, cfg.n_kv_heads,
                      cfg.hd))

    def decode_step(self, params, state: DecodeState, batch):
        cfg = self.cfg
        x = self._embed(params, batch["tokens"])
        pos = state.pos
        positions = jnp.full((x.shape[0], 1), pos, jnp.int32)

        def body(xx, inp):
            lp, ck, cv, xk, xv = inp
            h = rms_norm(xx, lp["norm1"], cfg.norm_eps)
            q, k, v = qkv(lp["attn"], h, cfg, positions)
            ck, cv, kvpos = attn.cache_write(ck, cv, state.kv_pos, k, v, pos)
            o = attn.attention_decode(q, ck, cv, kvpos, pos)
            xx = xx + jnp.einsum("bse,ed->bsd", o.reshape(o.shape[0], 1, -1),
                                 lp["attn"]["wo"])
            hx = rms_norm(xx, lp["norm_x"], cfg.norm_eps)
            qx, _, _ = qkv(lp["xattn"], hx, cfg,
                           jnp.zeros((xx.shape[0], 1), jnp.int32))
            xpos = jnp.broadcast_to(jnp.arange(xk.shape[1]),
                                    (xx.shape[0], xk.shape[1]))
            ox = attn.attention_decode(qx, xk, xv, xpos,
                                       jnp.int32(xk.shape[1]))
            xx = xx + jnp.einsum("bse,ed->bsd", ox.reshape(ox.shape[0], 1, -1),
                                 lp["xattn"]["wo"])
            h2 = rms_norm(xx, lp["norm2"], cfg.norm_eps)
            return xx + mlp_mod.mlp_apply(lp["mlp"], h2, cfg.mlp_act), (ck, cv)

        x, (ck, cv) = scan_or_unroll(
            body, x, (params["dec_layers"], state.kv_k, state.kv_v,
                      state.cross_k, state.cross_v),
            unroll=not cfg.scan_layers)
        slots = state.kv_k.shape[2]
        slot = (pos % slots).astype(jnp.int32)
        kv_pos = jax.lax.dynamic_update_slice(
            state.kv_pos, jnp.full((x.shape[0], 1), pos, jnp.int32), (0, slot))
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, -1], self._head_weight(params))
        return logits, dataclasses.replace(state, pos=pos + 1, kv_k=ck,
                                           kv_v=cv, kv_pos=kv_pos)


# ======================================================================
def build_model(cfg: ModelConfig) -> LMBase:
    family = {
        "dense": DenseLM, "moe": DenseLM, "vlm": DenseLM,
        "ssm": SSMLM, "hybrid": HybridLM, "encdec": EncDecLM,
    }[cfg.family]
    return family(cfg)
