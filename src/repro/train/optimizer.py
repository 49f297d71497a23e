"""Optimizers: AdamW with fp32 or int8 block-quantized moments.

The int8 variant ("adamw8") stores both Adam moments as int8 with per-block
fp32 scales (block = 128 along the last axis). This is the optimizer-state
compression that makes the 1T-param `kimi-k2` cell fit v5e HBM and doubles
as the framework's state-compression feature: moments shrink 4x, and with
ZeRO-1 sharding over the data axis the per-chip optimizer footprint for
kimi-k2 drops from 16 GB (fp32) to ~0.25 GB.

Both variants are pure pytree transforms — no optax dependency.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

BLOCK = 128


@dataclasses.dataclass(frozen=True)
class OptConfig:
    name: str = "adamw8"           # 'adamw' | 'adamw8'
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup: int = 100
    total_steps: int = 10_000


def lr_at(cfg: OptConfig, step: jax.Array) -> jax.Array:
    """Linear warmup + cosine decay."""
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup, 1), 1.0)
    t = jnp.clip((step - cfg.warmup) /
                 jnp.maximum(cfg.total_steps - cfg.warmup, 1), 0.0, 1.0)
    return cfg.lr * warm * (0.5 * (1 + jnp.cos(jnp.pi * t)))


# ------------------------------------------------------- int8 block quant
def _pad_to_block(x):
    n = x.shape[-1]
    pad = (-n) % BLOCK
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, pad


def quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """fp32 -> (int8 values, fp32 per-block scales)."""
    orig = x.shape
    xp, pad = _pad_to_block(x.astype(jnp.float32))
    blocks = xp.reshape(*xp.shape[:-1], -1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q.reshape(xp.shape), scale[..., 0]


def dequantize(q: jax.Array, scale: jax.Array, orig_len: int) -> jax.Array:
    blocks = q.reshape(*q.shape[:-1], -1, BLOCK).astype(jnp.float32)
    x = (blocks * scale[..., None]).reshape(q.shape)
    return x[..., :orig_len]


# ------------------------------------------------------------------ adamw
def init_opt_state(params, cfg: OptConfig):
    def zeros_like_fp32(p):
        return jnp.zeros(p.shape, jnp.float32)

    def zeros_like_q8(p):
        z = jnp.zeros(p.shape, jnp.float32)
        q, s = quantize(z)
        return {"q": q, "s": s}

    if cfg.name == "adamw":
        return {
            "m": jax.tree.map(zeros_like_fp32, params),
            "v": jax.tree.map(zeros_like_fp32, params),
            "step": jnp.zeros((), jnp.int32),
        }
    if cfg.name == "adamw8":
        return {
            "m": jax.tree.map(zeros_like_q8, params),
            "v": jax.tree.map(zeros_like_q8, params),
            "step": jnp.zeros((), jnp.int32),
        }
    raise ValueError(cfg.name)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def apply_updates(params, grads, opt_state, cfg: OptConfig):
    """One AdamW step. Returns (new_params, new_opt_state, metrics)."""
    step = opt_state["step"] + 1
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-12))
    lr = lr_at(cfg, step)
    bc1 = 1 - cfg.b1 ** step.astype(jnp.float32)
    bc2 = 1 - cfg.b2 ** step.astype(jnp.float32)
    quantized = cfg.name == "adamw8"

    def upd_flat(p, g, m, v):
        g = g.astype(jnp.float32) * clip
        if quantized:
            m_f = dequantize(m["q"], m["s"], p.shape[-1])
            # v is stored in sqrt domain: entries span decades within a
            # block and sit in the update's denominator, so linear int8
            # rounds the small ones to zero and their updates blow up.
            # Quantizing sqrt(v) makes the int8 error land linearly in
            # the denominator instead.
            v_f = jnp.square(dequantize(v["q"], v["s"], p.shape[-1]))
        else:
            m_f, v_f = m, v
        m_f = cfg.b1 * m_f + (1 - cfg.b1) * g
        v_f = cfg.b2 * v_f + (1 - cfg.b2) * jnp.square(g)
        u = (m_f / bc1) / (jnp.sqrt(v_f / bc2) + cfg.eps)
        p32 = p.astype(jnp.float32)
        new_p = (p32 - lr * (u + cfg.weight_decay * p32)).astype(p.dtype)
        if quantized:
            mq, ms = quantize(m_f)
            vq, vs = quantize(jnp.sqrt(v_f))
            return new_p, {"q": mq, "s": ms}, {"q": vq, "s": vs}
        return new_p, m_f, v_f

    def upd(p, g, m, v):
        # Chunk giant leaves (MoE expert stacks, embedding tables) so the
        # dequantized fp32 moment transients stay bounded — otherwise buffer
        # assignment wants tens of GB/device at the 1T scale. The chunk
        # count is capped at 64 (bounded dispatch overhead).
        if p.size <= (1 << 28):
            return upd_flat(p, g, m, v)
        n = p.shape[0]
        chunks = next((c for c in range(min(n, 64), 1, -1) if n % c == 0), 1)
        if chunks == 1:
            return upd_flat(p, g, m, v)

        def resh(x):
            return x.reshape(chunks, n // chunks, *x.shape[1:])

        def unresh(x):
            return x.reshape(n, *x.shape[2:])

        def body(_, xs):
            return None, upd_flat(*xs)

        xs = jax.tree.map(resh, (p, g, m, v))
        _, out = jax.lax.scan(body, None, xs)
        return jax.tree.map(unresh, out)

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = tdef.flatten_up_to(grads)
    is_state = (lambda x: isinstance(x, dict) and "q" in x) if quantized \
        else None
    flat_m = jax.tree.flatten(opt_state["m"], is_leaf=is_state)[0]
    flat_v = jax.tree.flatten(opt_state["v"], is_leaf=is_state)[0]
    out = [upd(p, g, m, v) for p, g, m, v in
           zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = jax.tree.unflatten(tdef, [o[0] for o in out])
    new_m = jax.tree.unflatten(tdef, [o[1] for o in out])
    new_v = jax.tree.unflatten(tdef, [o[2] for o in out])
    new_state = {"m": new_m, "v": new_v, "step": step}
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
