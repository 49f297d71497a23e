"""Dense pre-norm decoder (``kind: dense``): weights from a seed, the plain
float32 reference, and what one decode step needs.

The block, as the configuration file states it: RMSNorm with a (1 + scale)
weight, multi-head attention with rotary embedding over the whole head
(half-split), causal softmax attention, a SwiGLU MLP, a final RMSNorm and a
head tied to the embedding. Where this departs from the published model,
the configuration file lists it under ``assumed``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.harness import numerics as nx


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    hd: int
    ff: int
    vocab: int
    padded_vocab: int
    theta: float
    eps: float

    @classmethod
    def of(cls, model: dict) -> "Dims":
        hd = model.get("head_dim") or model["d_model"] // model["n_heads"]
        v = model["vocab"]
        return cls(layers=model["n_layers"], d=model["d_model"],
                   heads=model["n_heads"], kv_heads=model["n_kv_heads"],
                   hd=hd, ff=model["d_ff"], vocab=v,
                   padded_vocab=(v + 127) // 128 * 128,
                   theta=float(model.get("rope_theta", 10000.0)),
                   eps=float(model.get("norm_eps", 1e-6)))


def make_params(model: dict, key) -> dict:
    """The served weight tree, random from ``key``, in the served dtype.
    Run inside ``jax.jit``."""
    m = Dims.of(model)
    dtype = jnp.dtype(model["param_dtype"])
    L, d, q, kv, f = m.layers, m.d, m.heads * m.hd, m.kv_heads * m.hd, m.ff
    k_top, k_layers = jax.random.split(key)
    top = nx.make_leaves(k_top, {
        "embed": ((m.padded_vocab, d), ("normal", d)),
        "ln_final": ((d,), ("norm",)),
    }, dtype)
    top["layers"] = nx.make_leaves(k_layers, {
        "ln_attn": ((L, d), ("norm",)),
        "wq": ((L, d, q), ("normal", d)),
        "wk": ((L, d, kv), ("normal", d)),
        "wv": ((L, d, kv), ("normal", d)),
        "wo": ((L, q, d), ("normal", q)),
        "ln_mlp": ((L, d), ("norm",)),
        "w_gate": ((L, d, f), ("normal", d)),
        "w_up": ((L, d, f), ("normal", d)),
        "w_down": ((L, f, d), ("normal", f)),
    }, dtype)
    return top


def _rope(x, theta):
    """x (B, T, H, hd), rotated by position along T, half-split."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _layer(layers, i, x, m: Dims, mode: str):
    p = jax.tree.map(lambda a: a[i], layers)
    b, t, _ = x.shape
    h = nx.rms_norm(x, p["ln_attn"], m.eps)
    q = _rope(nx.mm(h, p["wq"], mode).reshape(b, t, m.heads, m.hd), m.theta)
    k = _rope(nx.mm(h, p["wk"], mode).reshape(b, t, m.kv_heads, m.hd),
              m.theta)
    v = nx.mm(h, p["wv"], mode).reshape(b, t, m.kv_heads, m.hd)
    group = m.heads // m.kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=nx.HIGHEST)
    s = s / jnp.sqrt(float(m.hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=nx.HIGHEST)
    x = x + nx.mm(o.reshape(b, t, m.heads * m.hd), p["wo"], mode)
    h = nx.rms_norm(x, p["ln_mlp"], m.eps)
    g = jax.nn.silu(nx.mm(h, p["w_gate"], mode)) * nx.mm(h, p["w_up"], mode)
    return x + nx.mm(g, p["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _embed(table, tokens, m: Dims, mode: str):
    return nx.embed(table, tokens, mode)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _final(ln, h, table, m: Dims, mode: str):
    return nx.tied_logits(nx.rms_norm(h, ln, m.eps), table, m.vocab, mode)


def forward(model: dict, params: dict, tokens: jax.Array,
            mode: str = "f32") -> jax.Array:
    """Residual stream after the last layer, float32, for ``tokens``
    (B, T) from position 0; one compiled program per layer call."""
    m = Dims.of(model)
    x = _embed(params["embed"], tokens, m, mode)
    for i in range(m.layers):
        x = _layer(params["layers"], i, x, m, mode)
    return x


def logits(model: dict, params: dict, h: jax.Array,
           mode: str = "f32") -> jax.Array:
    """Logits over the valid vocabulary for residual rows ``h (N, D)``."""
    return _final(params["ln_final"], h, params["embed"], Dims.of(model),
                  mode)


def step_cost(model: dict, weight_bytes: int, n_active: int,
              ctx_sum: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) that one decode step needs for ``n_active``
    lanes attending over ``ctx_sum`` live positions in all: every weight
    read once, each active lane's live keys and values read and its new
    ones written, the matmuls and the attention of the active lanes."""
    m = Dims.of(model)
    q, kv = m.heads * m.hd, m.kv_heads * m.hd
    per_layer = m.d * q + 2 * m.d * kv + q * m.d + 3 * m.d * m.ff
    matmul = m.layers * per_layer + m.vocab * m.d
    flops = 2.0 * matmul * n_active + 4.0 * m.layers * q * ctx_sum
    kv_pos = m.layers * 2 * kv * jnp.dtype(model["compute_dtype"]).itemsize
    bytes_ = weight_bytes + kv_pos * (ctx_sum + n_active)
    return flops, float(bytes_)
