"""The program's router logits, read to set a cell's ``tie_margin``.

Only ``bench.calibrate --router`` installs the probe, never a run.  It
wraps the program's ``repro.models.moe.moe_apply`` so that each MoE layer
call also sends to the host the router logits of its own input, computed
as the program's router computes them (bfloat16 operands, cast to
float32), in program order.  ``compare.router_shift`` then measures how
far they lie from the plain reference's.
"""
from __future__ import annotations

import numpy as np


class RouterProbe:
    def __init__(self):
        import jax
        import jax.numpy as jnp

        from repro.models import moe as moe_mod

        self._jax = jax
        self.armed, self.got = False, []
        program = moe_mod.moe_apply

        def sink(logits):
            if self.armed:
                self.got.append(np.asarray(logits, np.float32))

        def moe_apply(params, x, moe, act, **kw):
            logits = jnp.einsum("...d,de->...e", x,
                                params["w_router"]).astype(jnp.float32)
            jax.debug.callback(sink, logits, ordered=True)
            return program(params, x, moe, act, **kw)

        moe_mod.moe_apply = moe_apply

    def capture(self, fn) -> list:
        """Call ``fn`` with the probe armed: the router logits of every
        MoE layer call it made, in order."""
        jax = self._jax
        jax.effects_barrier()
        self.got, self.armed = [], True
        try:
            jax.block_until_ready(fn())
            jax.effects_barrier()
        finally:
            self.armed = False
        return self.got
