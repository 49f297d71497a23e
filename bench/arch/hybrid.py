"""Mamba2 / attention hybrid with an MLP after every mixer (``kind:
hybrid``, the granite form): weights from a seed, the plain float32
reference, and what one decode step needs.

The model, as the configuration file states it. Layer i is an attention
layer where i % attn_every == attn_offset and a Mamba2 layer elsewhere.
Every layer is h += r * mixer(norm(h)), then h += r * MLP(norm(h)), with r
the residual multiplier; the norms are RMSNorm with a (1 + scale) weight.

- Token embeddings are scaled by the embedding multiplier.
- The attention layer is grouped-query attention with no position
  embedding, causal softmax of q.k times the attention multiplier.
- The Mamba2 layer is ``bench/arch/ssm.py``'s: one input projection to (z,
  x, B, C, dt), a causal depthwise conv over (x, B, C) without bias, SiLU,
  the SSD recurrence with one B/C group, run token by token, the D skip, a
  gated RMSNorm of y * silu(z) (epsilon 1e-6), the output projection.
- The MLP is SwiGLU.
- A final RMSNorm, a head tied to the embedding, logits divided by the
  logit scale.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.arch import ssm
from bench.harness import numerics as nx

GATED_NORM_EPS = 1e-6   # the SSD layer's gated norm, as the program has it


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    period: int
    offset: int
    d: int
    heads: int
    kv_heads: int
    hd: int
    ff: int
    d_inner: int
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    conv: int
    vocab: int
    padded_vocab: int
    eps: float
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float

    @classmethod
    def of(cls, model: dict) -> "Dims":
        s = model["ssm"]
        di = s["expand"] * model["d_model"]
        v = model["vocab"]
        period, offset = model["attn_every"], model["attn_offset"]
        if not 0 <= offset < period or model["n_layers"] % period:
            raise ValueError("bench/arch/hybrid.py takes whole periods of "
                             "attn_every layers with 0 <= attn_offset < "
                             "attn_every")
        hd = model.get("head_dim") or model["d_model"] // model["n_heads"]
        return cls(layers=model["n_layers"], period=period, offset=offset,
                   d=model["d_model"], heads=model["n_heads"],
                   kv_heads=model["n_kv_heads"], hd=hd, ff=model["d_ff"],
                   d_inner=di, ssm_heads=di // s["head_dim"],
                   ssm_head_dim=s["head_dim"], d_state=s["d_state"],
                   conv=s["conv_width"], vocab=v,
                   padded_vocab=(v + 127) // 128 * 128,
                   eps=float(model.get("norm_eps", 1e-6)),
                   embedding_multiplier=float(
                       model.get("embedding_multiplier", 1.0)),
                   residual_multiplier=float(
                       model.get("residual_multiplier", 1.0)),
                   attention_multiplier=float(
                       model.get("attention_multiplier") or hd ** -0.5),
                   logits_scaling=float(model.get("logits_scaling", 1.0)))

    @property
    def n_attn(self) -> int:
        return self.layers // self.period

    @property
    def n_mamba(self) -> int:
        return self.layers - self.n_attn

    def where(self, i: int) -> tuple[str, int]:
        """Layer ``i``'s stack in the weight tree (``attn`` or ``mamba``)
        and its index there."""
        p, j = divmod(i, self.period)
        if j == self.offset:
            return "attn", p
        return "mamba", i - p - (j > self.offset)


def _mlp(d: int, ff: int, stack: tuple) -> dict:
    return {"ln_mlp": (stack + (d,), ("norm",)),
            "w_gate": (stack + (d, ff), ("normal", d)),
            "w_up": (stack + (d, ff), ("normal", d)),
            "w_down": (stack + (ff, d), ("normal", ff))}


def make_params(model: dict, key) -> dict:
    """The served weight tree, random from ``key``, in the served dtype.
    Run inside ``jax.jit``.

    The embedding table is drawn with its scale divided by the embedding
    multiplier, so that the scaled embedding enters the residual stream
    at 1/sqrt(D) per element, as the other kinds' does. Drawn at
    1/sqrt(D), 12 times that outweighs the sum of all 80 residual
    branches, and with the head tied to the table the served token is
    then the input token, whatever the history: no comparison could tell
    a broken cache from a sound one."""
    m = Dims.of(model)
    dtype = jnp.dtype(model["param_dtype"])
    d, di, n, h, ff = m.d, m.d_inner, m.d_state, m.ssm_heads, m.ff
    q, kv = m.heads * m.hd, m.kv_heads * m.hd
    k_top, k_mamba, k_attn = jax.random.split(key, 3)
    top = nx.make_leaves(k_top, {
        "embed": ((m.padded_vocab, d),
                  ("normal", d * m.embedding_multiplier ** 2)),
        "ln_final": ((d,), ("norm",)),
    }, dtype)
    st = (m.n_mamba,)
    top["mamba"] = nx.make_leaves(k_mamba, {
        "ln_ssm": (st + (d,), ("norm",)),
        "in_proj": (st + (d, 2 * di + 2 * n + h), ("normal", d)),
        "conv_w": (st + (m.conv, di + 2 * n), ("normal", m.conv)),
        "dt_bias": (st + (h,), (ssm._dt_bias,)),
        "A_log": (st + (h,), (ssm._a_log,)),
        "D": (st + (h,), (ssm._d,)),
        "ssm_norm": (st + (di,), ("norm",)),
        "out_proj": (st + (di, d), ("normal", di)),
        **_mlp(d, ff, st),
    }, dtype)
    st = (m.n_attn,)
    top["attn"] = nx.make_leaves(k_attn, {
        "ln_attn": (st + (d,), ("norm",)),
        "wq": (st + (d, q), ("normal", d)),
        "wk": (st + (d, kv), ("normal", d)),
        "wv": (st + (d, kv), ("normal", d)),
        "wo": (st + (q, d), ("normal", q)),
        **_mlp(d, ff, st),
    }, dtype)
    return top


def _ssm_mixer(p, h, m: Dims, mode: str):
    """The Mamba2 mixer over ``h (B, T, D)``, already normed."""
    b, t, _ = h.shape
    di, n = m.d_inner, m.d_state
    proj = nx.mm(h, p["in_proj"], mode)
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * n], \
        proj[..., 2 * di + 2 * n:]
    w = p["conv_w"].astype(jnp.float32)
    xp = jnp.pad(xbc, ((0, 0), (m.conv - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, j:j + t] * w[j] for j in range(m.conv)))
    xs = xbc[..., :di].reshape(b, t, m.ssm_heads, m.ssm_head_dim)
    Bm, Cm = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = ssm._scan_heads(xs, dt, A, Bm, Cm)
    y = y + p["D"].astype(jnp.float32)[:, None] * xs
    y = nx.rms_norm(y.reshape(b, t, di) * jax.nn.silu(z), p["ssm_norm"],
                    GATED_NORM_EPS)
    return nx.mm(y, p["out_proj"], mode)


def _attn_mixer(p, h, m: Dims, mode: str):
    """Causal grouped-query attention over ``h (B, T, D)``, no position
    embedding."""
    b, t, _ = h.shape
    q = nx.mm(h, p["wq"], mode).reshape(b, t, m.heads, m.hd)
    k = nx.mm(h, p["wk"], mode).reshape(b, t, m.kv_heads, m.hd)
    v = nx.mm(h, p["wv"], mode).reshape(b, t, m.kv_heads, m.hd)
    group = m.heads // m.kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=nx.HIGHEST)
    s = s * m.attention_multiplier
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=nx.HIGHEST)
    return nx.mm(o.reshape(b, t, m.heads * m.hd), p["wo"], mode)


@functools.partial(jax.jit, static_argnames=("m", "mode", "stack"))
def _layer(stacked, j, x, m: Dims, mode: str, stack: str):
    p = jax.tree.map(lambda a: a[j], stacked)
    r = m.residual_multiplier
    if stack == "attn":
        y = _attn_mixer(p, nx.rms_norm(x, p["ln_attn"], m.eps), m, mode)
    else:
        y = _ssm_mixer(p, nx.rms_norm(x, p["ln_ssm"], m.eps), m, mode)
    x = x + r * y
    h = nx.rms_norm(x, p["ln_mlp"], m.eps)
    g = jax.nn.silu(nx.mm(h, p["w_gate"], mode)) * nx.mm(h, p["w_up"], mode)
    return x + r * nx.mm(g, p["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _embed(table, tokens, m: Dims, mode: str):
    return nx.embed(table, tokens, mode) * m.embedding_multiplier


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _final(ln, h, table, m: Dims, mode: str):
    return nx.tied_logits(nx.rms_norm(h, ln, m.eps), table, m.vocab,
                          mode) / m.logits_scaling


def forward(model: dict, params: dict, tokens: jax.Array,
            mode: str = "f32") -> jax.Array:
    """Residual stream after the last layer, float32, for ``tokens``
    (B, T) from position 0; one compiled program per layer call."""
    m = Dims.of(model)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], tokens, m, mode)
        for i in range(m.layers):
            stack, j = m.where(i)
            x = _layer(params[stack], j, x, m, mode, stack)
    return x


def logits(model: dict, params: dict, h: jax.Array,
           mode: str = "f32") -> jax.Array:
    """Logits over the valid vocabulary for residual rows ``h (N, D)``."""
    with jax.default_matmul_precision("highest"):
        return _final(params["ln_final"], h, params["embed"], Dims.of(model),
                      mode)


def step_cost(model: dict, weight_bytes: int, n_active: int,
              ctx_sum: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) that one decode step needs for ``n_active``
    lanes attending over ``ctx_sum`` live positions in all: every weight
    read once; each active lane's float32 state and conv window read and
    written in every Mamba2 layer; in every attention layer each active
    lane's live keys and values read and its new ones written; the
    matmuls, the conv, the state update and the attention of the active
    lanes."""
    m = Dims.of(model)
    q, kv = m.heads * m.hd, m.kv_heads * m.hd
    conv_ch = m.d_inner + 2 * m.d_state
    mlp = 3 * m.d * m.ff
    mamba = m.d * (conv_ch + m.d_inner + m.ssm_heads) + m.d_inner * m.d
    attn = m.d * q + 2 * m.d * kv + q * m.d
    matmul = m.n_mamba * (mamba + mlp) + m.n_attn * (attn + mlp) \
        + m.vocab * m.d
    hpn = m.ssm_heads * m.ssm_head_dim * m.d_state
    per_lane = m.n_mamba * (6 * hpn + 2 * m.conv * conv_ch)
    flops = (2.0 * matmul + per_lane) * n_active \
        + 4.0 * m.n_attn * q * ctx_sum
    act = jnp.dtype(model["compute_dtype"]).itemsize
    lane_bytes = m.n_mamba * 2 * (hpn * 4 + (m.conv - 1) * conv_ch * act)
    kv_pos = m.n_attn * 2 * kv * act
    return flops, float(weight_bytes + lane_bytes * n_active
                        + kv_pos * (ctx_sum + n_active))
