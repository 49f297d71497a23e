"""Plain float32 arithmetic shared by the references in ``bench/arch/``,
and the fp8 arithmetic of their control.

Nothing here imports the program. The reference computes in float32 at
``highest`` matmul precision. The control is the same reference with every
matrix product fed float8 (e4m3) scaled per slice: activations per row
(token), weights per output column, as an fp8 weight-and-activation path
would feed them. The rest (norms, attention, the SSM recurrence, the
residual stream) stays float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FP8_MAX = 448.0          # largest finite float8_e4m3fn
HIGHEST = jax.lax.Precision.HIGHEST


def fp8(x: jax.Array, axis: int) -> jax.Array:
    """``x`` rounded to float8 e4m3 with one scale per slice along
    ``axis`` (the reduced axis), returned in float32."""
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a: jax.Array, w: jax.Array, mode: str) -> jax.Array:
    """``a (..., K) @ w (K, N)`` in float32, or through fp8 for the
    control."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "fp8":
        a, w = fp8(a, -1), fp8(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with a ``(1 + scale)`` weight, the program's convention."""
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def embed(table: jax.Array, tokens: jax.Array, mode: str) -> jax.Array:
    """Rows of the embedding table, fp8 per row for the control."""
    t = fp8(table, -1) if mode == "fp8" else table.astype(jnp.float32)
    return jnp.take(t, tokens, axis=0)


def tied_logits(h: jax.Array, table: jax.Array, vocab: int,
                mode: str) -> jax.Array:
    """``h (N, D)`` against the tied table's first ``vocab`` rows."""
    return mm(h, table[:vocab].T, mode)


def normal(key, shape, fan_in: int, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32)
            / jnp.sqrt(float(fan_in))).astype(dtype)


def make_leaves(key, leaves: dict, dtype) -> dict:
    """One array per ``name -> (shape, init)`` entry, each from its own
    split of ``key``. ``init`` is ``("normal", fan_in)``, ``("norm",)``
    (a (1 + scale) weight near 1), or a callable ``(key, shape) -> array``.
    Call inside ``jax.jit`` so that the whole tree is one program."""
    keys = jax.random.split(key, len(leaves))
    out = {}
    for k, (name, (shape, init)) in zip(keys, sorted(leaves.items())):
        if init[0] == "normal":
            out[name] = normal(k, shape, init[1], dtype)
        elif init[0] == "norm":
            out[name] = (0.1 * jax.random.normal(k, shape)).astype(dtype)
        else:
            out[name] = init[0](k, shape).astype(dtype)
    return out
