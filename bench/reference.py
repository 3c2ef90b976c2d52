"""Plain reference forward of the decoder-only LM family, in float32.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``, no kernels, no
cache, no crossbar: embedding, per layer RMSNorm -> GQA attention with
RoPE (causal, optional sliding window) -> residual -> RMSNorm -> SwiGLU MLP
or a top-k mixture of experts -> residual, then the final norm and the
head at the last position.  It follows the published Mistral/Qwen2 layer
equations (rotate-half RoPE, softmax router renormalised over the top k)
and, for experts, the capacity rule the configuration states: tokens form
groups of ``group_tokens``; within a group each (token, choice) packet,
in token-major order, takes the next free slot of its expert, and a
packet beyond ``capacity_factor`` x the mean load is dropped.

Without a routing rule (``routing=None``) the experts are dropless: every
token reaches its top-k experts, as in decode, where the program's groups
are the few tokens of one call and never fill an expert.

It imports nothing of the program.  Weights come from ``bench.weights``
(regenerated from the seed) and are cast to float32 one layer, and for
experts one expert, at a time, so the reference fits beside the served
bf16 tree.

``precision="fp8"`` is the control: every operand of a projection, expert
or head matrix multiplication is rounded to float8 (e4m3, one scale per
tensor), the precision below the served bfloat16.
"""
from __future__ import annotations

import functools

import numpy as np

PRECISIONS = ("float32", "fp8")
FP8_MAX = 448.0            # largest finite float8_e4m3fn


def _quantizer(precision: str):
    import jax.numpy as jnp

    if precision == "float32":
        return lambda x: x
    if precision == "fp8":
        def q(x):
            s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
            return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return q
    raise ValueError(f"precision must be one of {PRECISIONS}: {precision!r}")


class Reference:
    """Logits of whole sequences, layer by layer: at the last position
    (``last_logits``) or at every position (``all_logits``)."""

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
        q = _quantizer(precision)
        hi = jax.lax.Precision.HIGHEST
        f32 = jnp.float32

        def mm(spec, a, b):
            return jnp.einsum(spec, q(a), q(b), precision=hi)

        def norm(x, g):
            x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                  + self.eps)
            return x * g.astype(f32)

        def rope(x, pos):
            half = self.hd // 2
            freqs = 1.0 / (model["rope_theta"]
                           ** (jnp.arange(0, self.hd, 2, dtype=f32) / self.hd))
            ang = pos[:, None].astype(f32) * freqs            # [S, hd/2]
            cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
            x1, x2 = x[..., :half], x[..., half:]
            return jnp.concatenate([x1 * cos - x2 * sin,
                                    x2 * cos + x1 * sin], -1)

        window = model.get("attn_window")

        def attend(qs, ks, vs):                  # one sequence
            S = qs.shape[0]
            G = self.H // self.Kv
            k = jnp.repeat(ks, G, axis=1)
            v = jnp.repeat(vs, G, axis=1)
            s = jnp.einsum("qhd,khd->hqk", qs, k, precision=hi)
            s = s * self.hd ** -0.5
            i = jnp.arange(S)
            mask = i[None, :] <= i[:, None]
            if window is not None:
                mask &= i[None, :] > i[:, None] - window
            s = jnp.where(mask[None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("hqk,khd->qhd", p, v, precision=hi)

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
            qx = rope(qx.reshape(n, S, self.H, self.hd), pos)
            kx = rope(kx.reshape(n, S, self.Kv, self.hd), pos)
            vx = vx.reshape(n, S, self.Kv, self.hd)
            o = jax.lax.map(lambda t: attend(*t), (qx, kx, vx))
            return x + mm("nse,ed->nsd", o.reshape(n, S, -1), a["wo"])

        def swiglu(x, w_in, w_out):
            gate, up = jnp.split(mm("...d,df->...f", x, w_in), 2, axis=-1)
            return mm("...f,fd->...d", jax.nn.silu(gate) * up, w_out)

        @jax.jit
        def mlp(layers, l, x):
            h = norm(x, layers["norm2"][l])
            return x + swiglu(h, layers["mlp"]["w_in"][l].astype(f32),
                              layers["mlp"]["w_out"][l].astype(f32))

        self._embed, self._attention, self._mlp = embed, attention, mlp
        if self.moe:
            self._init_moe(mm, norm, swiglu)

        @functools.partial(jax.jit, static_argnums=(3, 4))
        def head(final_norm, table, x, tied, last):
            w = table.T if tied else table
            h = norm(x[:, -1] if last else x, final_norm)
            return mm("...d,dv->...v", h, w[:, :model["vocab"]].astype(f32))

        self._head = head

    def _init_moe(self, mm, norm, swiglu):
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
            G = n * S // g
            dst = top_e.reshape(G, g * k)
            w = top_p.reshape(G, g * k)
            onehot = (dst[..., None] == jnp.arange(E)).astype(jnp.int32)
            before = jnp.cumsum(onehot, axis=1) - onehot
            rank = jnp.take_along_axis(before, dst[..., None], -1)[..., 0]
            return (h.reshape(G, g, d), dst, w, rank < C, rank, margin,
                    logits)

        @functools.partial(jax.jit, static_argnums=(9,))
        def expert(layers, l, y, e, h, dst, w, keep, rank, C):
            G, _, d = h.shape
            sel = keep & (dst == e)                          # [G, g*k]
            slot = jnp.where(sel, rank, C)                   # C: trash row
            rows = jnp.arange(G)[:, None]
            xk = jnp.repeat(h, k, axis=1)                    # [G, g*k, d]
            slab = jnp.zeros((G, C + 1, d), f32).at[rows, slot].set(xk)
            out = swiglu(slab[:, :C],
                         layers["moe"]["w_in"][l, e].astype(f32),
                         layers["moe"]["w_out"][l, e].astype(f32))
            out = jnp.concatenate([out, jnp.zeros((G, 1, d), f32)], 1)
            back = out[rows, slot] * (w * sel)[..., None]
            return y + back.reshape(G, -1, k, d).sum(2)

        self._route, self._expert = route, expert

    def _groups(self, S: int):
        """(tokens a group, expert capacity) for sequences of ``S`` tokens:
        the configuration's rule, or, without one, a group per sequence
        whose capacity holds every packet (dropless)."""
        from bench.counts import expert_capacity

        if self.routing is None:
            return S, S
        g = self.routing["group_tokens"]
        return g, expert_capacity(g, self.moe)

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
