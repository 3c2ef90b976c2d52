"""The Mistral/Qwen2 decoder that the program's ``DenseLM`` runs.

Every layer alike: RMSNorm -> GQA attention with rotate-half RoPE (causal,
one optional sliding window, optional QKV biases) -> residual -> RMSNorm
-> a SwiGLU MLP or a softmax top-k mixture of experts renormalised over
the top k -> residual; then the final norm and the head (tied to the
embedding or not).
"""
from __future__ import annotations

import functools
import math

import numpy as np

from bench import counts
from bench import reference as R
from bench.weights import vocab_padded


def layout(model: dict) -> dict:
    """The ``DenseLM`` parameter tree, every layer stacked over
    ``n_layers``: nested dict of leaves ``(shape, kind, scale)``."""
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


class Reference:
    """Logits of whole sequences, layer by layer: at the last position
    (``last_logits``) or at every position (``all_logits``).  Weights are
    cast to float32 one layer, and for experts one expert, at a time, so
    the reference fits beside the served bf16 tree."""

    def __init__(self, model: dict, routing: dict = None,
                 precision: str = "float32"):
        import jax
        import jax.numpy as jnp

        self.m = model
        self.d = model["d_model"]
        self.H, self.Kv = model["n_heads"], model["n_kv_heads"]
        self.hd = model.get("head_dim") or self.d // self.H
        self.eps = model["norm_eps"]
        self.moe = model.get("moe")
        self.routing = routing
        q = R.quantizer(precision)
        f32 = jnp.float32
        window = model.get("attn_window")

        def mm(spec, a, b):
            return R.matmul(spec, a, b, q)

        def norm(x, g):
            return R.rms_norm(x, g, self.eps)

        @jax.jit
        def embed(table, tokens):
            return jnp.take(table, tokens, axis=0).astype(f32)

        @jax.jit
        def attention(layers, l, x):
            a = jax.tree.map(lambda w: w[l].astype(f32), layers["attn"])
            n, S, _ = x.shape
            h = norm(x, layers["norm1"][l])
            qx = mm("nsd,de->nse", h, a["wq"])
            kx = mm("nsd,de->nse", h, a["wk"])
            vx = mm("nsd,de->nse", h, a["wv"])
            if "bq" in a:
                qx, kx, vx = qx + a["bq"], kx + a["bk"], vx + a["bv"]
            pos = jnp.arange(S)
            qx = R.rope(qx.reshape(n, S, self.H, self.hd), pos,
                        model["rope_theta"])
            kx = R.rope(kx.reshape(n, S, self.Kv, self.hd), pos,
                        model["rope_theta"])
            vx = vx.reshape(n, S, self.Kv, self.hd)
            o = jax.lax.map(lambda t: R.attend(*t, window), (qx, kx, vx))
            return x + mm("nse,ed->nsd", o.reshape(n, S, -1), a["wo"])

        @jax.jit
        def mlp(layers, l, x):
            h = norm(x, layers["norm2"][l])
            return x + R.swiglu(h, layers["mlp"]["w_in"][l].astype(f32),
                                layers["mlp"]["w_out"][l].astype(f32), q)

        self._embed, self._attention, self._mlp = embed, attention, mlp
        if self.moe:
            self._init_moe(mm, norm, q)

        @functools.partial(jax.jit, static_argnums=(3, 4))
        def head(final_norm, table, x, tied, last):
            w = table.T if tied else table
            h = norm(x[:, -1] if last else x, final_norm)
            return mm("...d,dv->...v", h, w[:, :model["vocab"]].astype(f32))

        self._head = head

    def _init_moe(self, mm, norm, q):
        import jax
        import jax.numpy as jnp

        E, k = self.moe["n_experts"], self.moe["top_k"]
        f32 = jnp.float32

        @functools.partial(jax.jit, static_argnums=(3, 4))
        def route(layers, l, x, g, C):
            """Groups of ``g`` tokens, packets, slots under capacity ``C``;
            each position's margin between the k-th and the (k+1)-th
            router logit, and the router logits."""
            n, S, d = x.shape
            h = norm(x, layers["norm2"][l])
            logits = mm("nsd,de->nse", h,
                        layers["moe"]["w_router"][l].astype(f32))
            top = jax.lax.top_k(logits, k + 1)[0]
            margin = top[..., k - 1] - top[..., k]
            probs = jax.nn.softmax(logits, axis=-1)
            top_p, top_e = jax.lax.top_k(probs, k)          # [n, S, k]
            top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
            dst, w, rank = R.capacity_slots(top_e, top_p, g, E)
            return (h.reshape(n * S // g, g, d), dst, w, rank < C, rank,
                    margin, logits)

        @functools.partial(jax.jit, static_argnums=(9,))
        def expert(layers, l, y, e, h, dst, w, keep, rank, C):
            def ffn(slab):
                return R.swiglu(slab,
                                layers["moe"]["w_in"][l, e].astype(f32),
                                layers["moe"]["w_out"][l, e].astype(f32), q)

            return R.expert_slab(y, e, h, dst, w, keep, rank, C, ffn)

        self._route, self._expert = route, expert

    def _groups(self, S: int):
        """(tokens a group, expert capacity) for sequences of ``S`` tokens:
        the configuration's rule, or, without one, a group per sequence
        whose capacity holds every packet (dropless)."""
        if self.routing is None:
            return S, S
        g = self.routing["group_tokens"]
        return g, counts.expert_capacity(g, self.moe)

    def _forward(self, params, tokens, last: bool, router: list = None):
        import jax.numpy as jnp

        layers = params["layers"]
        x = self._embed(params["embed"], jnp.asarray(tokens))
        n, S, d = x.shape
        margin = np.full((n, S), np.inf, np.float32)
        for l in range(self.m["n_layers"]):
            x = self._attention(layers, l, x)
            if self.moe:
                g, C = self._groups(S)
                h, dst, w, keep, rank, m, r = self._route(layers, l, x, g,
                                                          C)
                if router is not None:
                    router.append(np.asarray(r))
                y = jnp.zeros_like(h)
                for e in range(self.moe["n_experts"]):
                    y = self._expert(layers, l, y, e, h, dst, w, keep, rank,
                                     C)
                x = x + y.reshape(n, S, d)
                margin = np.minimum(margin, np.asarray(m))
            else:
                x = self._mlp(layers, l, x)
        tied = bool(self.m.get("tied_embeddings"))
        table = params["embed"] if tied else params["lm_head"]
        return self._head(params["final_norm"], table, x, tied, last), margin

    def last_logits(self, params, tokens, router: list = None):
        """``tokens`` [n, S] -> (logits [n, vocab] float32, router margin
        [n]: the smallest gap over the MoE layers between the last
        position's k-th and (k+1)-th router logit; +inf without experts).
        ``router``, when given, receives each MoE layer's router logits
        [n, S, n_experts]."""
        logits, margin = self._forward(params, tokens, last=True,
                                       router=router)
        return np.asarray(logits, np.float32), margin[:, -1]

    def all_logits(self, params, tokens, router: list = None):
        """``tokens`` [n, S] -> (logits [n, S, vocab] float32 on the
        device, router margin [n, S] as in ``last_logits``)."""
        return self._forward(params, tokens, last=False, router=router)


def prefill_flops(model: dict, batch: int, seq_len: int) -> float:
    """FLOPs one prefill step of ``batch`` x ``seq_len`` tokens requires:
    matrix multiplications only, causal attention counted once (inside
    the window if there is one), the expert FFN only for the top-k
    experts a token is routed to, and the head only for the last
    position, which is all that prefill returns."""
    d, L = model["d_model"], model["n_layers"]
    H, Kv = model["n_heads"], model["n_kv_heads"]
    hd = model.get("head_dim") or d // H
    ff, V = model["d_ff"], model["vocab"]
    T = batch * seq_len
    proj = 2 * T * d * (H * hd + 2 * Kv * hd) + 2 * T * H * hd * d
    attn = 4 * batch * H * hd * counts.attended_pairs(
        seq_len, model.get("attn_window"))
    moe = model.get("moe")
    if moe:
        ffn = (2 * T * d * moe["n_experts"]
               + moe["top_k"] * 6 * T * d * ff)
    else:
        ffn = 6 * T * d * ff
    head = 2 * batch * d * V
    return float(L * (proj + attn + ffn) + head)


def token_flops(model: dict, position: int) -> float:
    """FLOPs one token requires at ``position`` (0-based) of a sequence
    whose earlier keys are cached: its projections, attention over the
    ``position + 1`` keys it sees, the top-k experts or the MLP, and the
    head over the whole vocabulary (a served token's logits)."""
    d, L = model["d_model"], model["n_layers"]
    H, Kv = model["n_heads"], model["n_kv_heads"]
    hd = model.get("head_dim") or d // H
    ff, V = model["d_ff"], model["vocab"]
    window = model.get("attn_window")
    keys = position + 1 if window is None else min(position + 1, window)
    proj = 2 * d * (H * hd + 2 * Kv * hd) + 2 * H * hd * d
    attn = 4 * H * hd * keys
    moe = model.get("moe")
    ffn = (2 * d * moe["n_experts"] + moe["top_k"] * 6 * d * ff) if moe \
        else 6 * d * ff
    return float(L * (proj + attn + ffn) + 2 * d * V)


def fabric_bytes(model: dict, tokens: int, group_tokens: int,
                 itemsize: int = 2) -> float:
    """Bytes any crossbar must move through one prefill step's MoE layers:
    each token row read once; each packet row written once on dispatch,
    then read once and combined into its token row (written once); and
    the routing state of every packet."""
    moe = model["moe"]
    d = model["d_model"]
    packets = counts.fabric_packets(tokens, moe, group_tokens)
    per_layer = (2 * tokens * d * itemsize          # token rows in and out
                 + 2 * packets * d * itemsize       # packet rows out and in
                 + packets * counts.ROUTING_STATE_BYTES)
    return float(model["n_layers"] * per_layer)
