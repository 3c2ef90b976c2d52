"""Step functions + sharding trees for training and serving.

``build_step`` returns everything the dry-run / launcher needs for one
(arch x shape) cell: the step callable, example-input ShapeDtypeStructs and
the in/out shardings, all derived from the model's declarative param specs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig, ShapeConfig
from repro.models.lm import LMBase, build_model
from repro.optim.adamw import AdamW, OptState


def named(mesh: Mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


@dataclasses.dataclass
class StepBundle:
    """One lowered cell: callable + arg structs + shardings."""
    step: Callable
    arg_structs: Tuple[Any, ...]
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...] = ()


# ----------------------------------------------------------------------
def make_train_step(model: LMBase, opt: AdamW, microbatches: int = 1):
    """One optimizer step; ``microbatches > 1`` accumulates gradients over
    sequential microbatches (activations shrink x M — how the big train
    shapes fit a 16 GB chip; grads/optimizer see the same mathematics)."""

    def train_step(params, opt_state: OptState, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
        else:
            def split(x):
                B = x.shape[0]
                return x.reshape(microbatches, B // microbatches,
                                 *x.shape[1:])

            mb = jax.tree.map(split, batch)

            def accum(carry, mbatch):
                loss_sum, gsum = carry
                l, g = jax.value_and_grad(model.loss)(params, mbatch)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (loss_sum + l, gsum), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                accum, (jnp.zeros((), jnp.float32), g0), mb)
            inv = 1.0 / microbatches
            loss = loss * inv
            grads = jax.tree.map(lambda g: g * inv, grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = AdamW.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


def opt_state_structs(model: LMBase) -> OptState:
    pshapes = model.param_shapes()
    f32 = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                       pshapes)
    return OptState(step=jax.ShapeDtypeStruct((), jnp.int32), m=f32, v=f32)


def opt_state_specs(model: LMBase, multi_pod: bool) -> OptState:
    pspecs = model.param_specs(multi_pod)
    return OptState(step=P(), m=pspecs, v=pspecs)


# ----------------------------------------------------------------------
def build_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
               *, multi_pod: bool, opt: Optional[AdamW] = None,
               microbatches: int = 1,
               constrain_activations: bool = True,
               kernel_mode: Optional[str] = None) -> StepBundle:
    """Build one (arch x shape) cell.

    ``kernel_mode`` overrides ``cfg.moe.kernel_mode`` for MoE archs — the
    seam sweeps use to lower the same train step against different crossbar
    kernels ("xla" | "pallas" | "pallas_interpret") without editing model
    configs or any call site below this one.  MoE train cells backprop
    through the fabric: ``jax.value_and_grad(model.loss)`` hits the
    custom_vjp scatter/gather rules, so the lowered backward replays the
    flat address route instead of a dense [T, E*C] selection matmul.
    """
    from repro.models.lm import batch_axes
    if (kernel_mode is not None and cfg.moe is not None
            and kernel_mode != cfg.moe.kernel_mode):
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, kernel_mode=kernel_mode))
    model = build_model(cfg)
    if constrain_activations:
        # Pin [B, S, d] activations to batch sharding at every layer
        # boundary; without this the partitioner replicates the rematted
        # backward recompute over the data axis (§Perf iteration 1).
        model.batch_axis = batch_axes(shape.global_batch, multi_pod)
    pshapes = model.param_shapes()
    pspecs = model.param_specs(multi_pod)
    bstructs, bspecs = model.input_shapes(shape, multi_pod)

    if shape.kind == "train":
        opt = opt or AdamW()
        step = make_train_step(model, opt, microbatches)
        args = (pshapes, opt_state_structs(model), bstructs)
        in_sh = (named(mesh, pspecs), named(mesh, opt_state_specs(model, multi_pod)),
                 named(mesh, bspecs))
        out_sh = (named(mesh, pspecs), named(mesh, opt_state_specs(model, multi_pod)),
                  NamedSharding(mesh, P()))
        return StepBundle(step, args, in_sh, out_sh, donate_argnums=(0, 1))

    if shape.kind == "prefill":
        def serve_step(params, batch):
            return model.prefill(params, batch)
        args = (pshapes, bstructs)
        in_sh = (named(mesh, pspecs), named(mesh, bspecs))
        vocab_spec = P(None, "model")
        out_sh = NamedSharding(mesh, vocab_spec)
        return StepBundle(serve_step, args, in_sh, out_sh)

    # decode
    sstructs, sspecs = model.decode_state_shapes(shape, multi_pod)

    def serve_step(params, state, batch):
        return model.decode_step(params, state, batch)

    args = (pshapes, sstructs, bstructs)
    in_sh = (named(mesh, pspecs), named(mesh, sspecs), named(mesh, bspecs))
    out_sh = (NamedSharding(mesh, P(None, "model")), named(mesh, sspecs))
    return StepBundle(serve_step, args, in_sh, out_sh, donate_argnums=(1,))


def lower_step(bundle: StepBundle, mesh: Mesh):
    fn = jax.jit(bundle.step, in_shardings=bundle.in_shardings,
                 out_shardings=bundle.out_shardings,
                 donate_argnums=bundle.donate_argnums)
    with jax.set_mesh(mesh):
        return fn.lower(*bundle.arg_structs)
