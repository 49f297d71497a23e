"""Training step: microbatched gradient accumulation + AdamW update.

The step function is a single jit-compiled program:

  1. the global batch is split into `n_microbatches` chunks along batch;
  2. a lax.scan accumulates fp32 gradients (per-layer remat inside the model
     keeps the live set to one layer's activations per microbatch);
  3. gradients are clipped by global norm and applied with AdamW
     (fp32 or int8-quantized moments — repro.train.optimizer);
  4. optimizer states carry ZeRO-1 sharding (extra 'zero' = (pod, data) axis
     on their first divisible dimension), so XLA materializes the classic
     reduce-scatter(grads) -> sharded update -> all-gather(params) schedule.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models.transformer import Model
from repro.sharding import current_ctx
from repro.train.optimizer import OptConfig, apply_updates, init_opt_state


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    n_microbatches: int = 1
    opt: OptConfig = dataclasses.field(default_factory=OptConfig)
    # bf16 accumulation halves the persistent grad buffer — required to fit
    # the ~1T-param cells in 16 GB/chip HBM.
    grad_accum_dtype: str = "float32"
    # FSDP: additionally shard the parameters over the ('pod', 'data') axes
    # (gathered per layer by GSPMD). Enabled for the >100B archs.
    fsdp_params: bool = False
    # ZeRO-sharded gradient accumulator: the per-microbatch gradient
    # all-reduce over the data axes becomes a reduce-scatter (half the
    # bytes), with one gather deferred to the optimizer. §Perf lever.
    zero_grad_accum: bool = False


def init_train_state(model: Model, seed: int, tcfg: TrainConfig):
    params = model.init(seed)
    return {"params": params, "opt": init_opt_state(params, tcfg.opt)}


def abstract_train_state(model: Model, tcfg: TrainConfig):
    return jax.eval_shape(
        lambda: {"params": model.init(0),
                 "opt": init_opt_state(model.init(0), tcfg.opt)})


def _zero_axes(axes_leaf, shape):
    """Axes + 'zero' (= the data/pod axes) on the first dimension that is
    still unsharded AND divisible by the zero-axis size — layer counts like
    61 or 35 do not divide 16/32, so naive dim-0 placement silently loses
    the ZeRO sharding (261 GB/device for kimi-k2's moments)."""
    ctx = current_ctx()
    dp = ctx.axes_size("zero")
    axes = list(axes_leaf) + [None] * (len(shape) - len(axes_leaf))
    for i, a in enumerate(axes):
        # assignable = carries no mesh axes yet ('embed' etc. map to ())
        free = a is None or not ctx.mesh_axes(a)
        if free and dp > 1 and shape[i] % dp == 0:
            axes[i] = "zero"
            break
    return tuple(axes)


def train_state_axes(model: Model, tcfg: TrainConfig):
    """Logical axes for the whole train state (params + optimizer).

    Must be called under the target mesh context (divisibility of the ZeRO
    dimension is mesh-dependent)."""
    p_axes = model.param_axes()
    abstract = model.abstract_params()

    def for_param(ax, sds):
        return _zero_axes(ax, sds.shape) if tcfg.fsdp_params else ax

    def for_moment(ax, sds):
        base = _zero_axes(ax, sds.shape)
        if tcfg.opt.name == "adamw8":
            # quantized moment: {'q': int8 like param (last dim padded to the
            # quant block), 's': per-block scales}
            from repro.train.optimizer import BLOCK
            qshape = sds.shape[:-1] + (
                ((sds.shape[-1] + BLOCK - 1) // BLOCK) * BLOCK,)
            sshape = sds.shape[:-1] + (qshape[-1] // BLOCK,)
            return {"q": _zero_axes(ax, qshape),
                    "s": _zero_axes(ax[:-1] + (None,), sshape)}
        return base

    is_axes_leaf = lambda x: isinstance(x, tuple) and all(  # noqa: E731
        isinstance(i, (str, type(None))) for i in x)
    m_axes = jax.tree.map(for_moment, p_axes, abstract,
                          is_leaf=is_axes_leaf)
    return {
        "params": jax.tree.map(for_param, p_axes, abstract,
                               is_leaf=is_axes_leaf),
        "opt": {"m": m_axes, "v": m_axes, "step": ()},
    }


def grad_accum_axes(model: Model):
    """ZeRO-style logical axes for the gradient accumulator."""
    p_axes = model.param_axes()
    abstract = model.abstract_params()
    is_axes_leaf = lambda x: isinstance(x, tuple) and all(  # noqa: E731
        isinstance(i, (str, type(None))) for i in x)
    return jax.tree.map(lambda ax, sds: _zero_axes(ax, sds.shape),
                        p_axes, abstract, is_leaf=is_axes_leaf)


def make_train_step(model: Model, tcfg: TrainConfig,
                    param_shardings=None, accum_shardings=None) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    ``param_shardings``: optional pytree of NamedShardings used to pin the
    gradient accumulator to the parameters' (ZeRO/FSDP) layout — without it
    GSPMD may leave the accumulator replicated over the data axes, which
    costs hundreds of GB/device at the 1T-param scale."""

    def loss_fn(params, mb):
        return model.loss(params, mb)

    def constrain(tree):
        sh_tree = accum_shardings if accum_shardings is not None \
            else param_shardings
        if sh_tree is None:
            return tree
        return jax.tree.map(
            lambda x, sh: jax.lax.with_sharding_constraint(x, sh)
            if sh is not None else x, tree, sh_tree)

    def train_step(state, batch):
        params = state["params"]
        n_mb = tcfg.n_microbatches

        if n_mb <= 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            def split(x):
                b = x.shape[0]
                return x.reshape(n_mb, b // n_mb, *x.shape[1:])

            mbs = jax.tree.map(split, batch)

            def acc_body(carry, mb):
                loss_sum, gacc = carry
                loss, grads = jax.value_and_grad(loss_fn)(params, mb)
                gacc = jax.tree.map(
                    lambda a, g: a + g.astype(a.dtype), gacc, grads)
                return (loss_sum + loss, gacc), None

            acc_dt = jnp.dtype(tcfg.grad_accum_dtype)
            g0 = constrain(jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), params))
            (loss_sum, grads), _ = jax.lax.scan(
                acc_body, (jnp.zeros(()), g0), mbs)
            loss = loss_sum / n_mb
            grads = jax.tree.map(lambda g: g / n_mb, grads)

        new_params, new_opt, metrics = apply_updates(
            params, grads, state["opt"], tcfg.opt)
        metrics["loss"] = loss
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step
