"""Mamba2 / SSD stack (``kind: ssm``): weights from a seed, the plain
float32 reference, and what one decode step needs.

The block, as the configuration file states it: RMSNorm with a (1 + scale)
weight; one input projection to (z, x, B, C, dt); a causal depthwise conv
of width W over (x, B, C) without bias, then SiLU; dt = softplus(dt +
dt_bias), A = -exp(A_log); the recurrence s_t = exp(dt_t A) s_{t-1} +
dt_t x_t B_t^T per head with one B/C group, y_t = s_t C_t + D x_t; a gated
RMSNorm of y * silu(z); the output projection. No MLP; a final RMSNorm and
a head tied to the embedding. The reference runs the recurrence token by
token, not in chunks.
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
    d_inner: int
    heads: int
    head_dim: int
    d_state: int
    conv: int
    vocab: int
    padded_vocab: int
    eps: float

    @classmethod
    def of(cls, model: dict) -> "Dims":
        s = model["ssm"]
        di = s["expand"] * model["d_model"]
        v = model["vocab"]
        return cls(layers=model["n_layers"], d=model["d_model"], d_inner=di,
                   heads=di // s["head_dim"], head_dim=s["head_dim"],
                   d_state=s["d_state"], conv=s["conv_width"], vocab=v,
                   padded_vocab=(v + 127) // 128 * 128,
                   eps=float(model.get("norm_eps", 1e-6)))


def _dt_bias(key, shape):
    # softplus^-1 of dt drawn log-uniform in [1e-3, 1e-1], as Mamba2 inits it
    dt = jnp.exp(jax.random.uniform(key, shape, minval=jnp.log(1e-3),
                                    maxval=jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0))


def _d(key, shape):
    return jax.random.uniform(key, shape, minval=0.5, maxval=1.5)


def make_params(model: dict, key) -> dict:
    """The served weight tree, random from ``key``, in the served dtype.
    Run inside ``jax.jit``."""
    m = Dims.of(model)
    dtype = jnp.dtype(model["param_dtype"])
    L, d, di, n, h = m.layers, m.d, m.d_inner, m.d_state, m.heads
    k_top, k_layers = jax.random.split(key)
    top = nx.make_leaves(k_top, {
        "embed": ((m.padded_vocab, d), ("normal", d)),
        "ln_final": ((d,), ("norm",)),
    }, dtype)
    top["layers"] = nx.make_leaves(k_layers, {
        "ln_ssm": ((L, d), ("norm",)),
        "in_proj": ((L, d, 2 * di + 2 * n + h), ("normal", d)),
        "conv_w": ((L, m.conv, di + 2 * n), ("normal", m.conv)),
        "dt_bias": ((L, h), (_dt_bias,)),
        "A_log": ((L, h), (_a_log,)),
        "D": ((L, h), (_d,)),
        "ssm_norm": ((L, di), ("norm",)),
        "out_proj": ((L, di, d), ("normal", di)),
    }, dtype)
    return top


def _scan_heads(xs, dt, A, Bm, Cm):
    """The recurrence over T. xs (B, T, H, P); dt (B, T, H); Bm, Cm
    (B, T, N). Returns y (B, T, H, P) without the D term."""
    b, _, h, p = xs.shape
    n = Bm.shape[-1]

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        decay = jnp.exp(dt_t * A)[..., None, None]
        s = s * decay + (dt_t[..., None, None] * x_t[..., None]
                         * b_t[:, None, None, :])
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t, precision=nx.HIGHEST)

    s0 = jnp.zeros((b, h, p, n), jnp.float32)
    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (xs, dt, Bm, Cm))
    _, ys = jax.lax.scan(step, s0, seq)
    return jnp.moveaxis(ys, 0, 1)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _layer(layers, i, x, m: Dims, mode: str):
    p = jax.tree.map(lambda a: a[i], layers)
    b, t, _ = x.shape
    di, n = m.d_inner, m.d_state
    h = nx.rms_norm(x, p["ln_ssm"], m.eps)
    proj = nx.mm(h, p["in_proj"], mode)
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * n], \
        proj[..., 2 * di + 2 * n:]
    w = p["conv_w"].astype(jnp.float32)
    xp = jnp.pad(xbc, ((0, 0), (m.conv - 1, 0), (0, 0)))
    xbc = sum(xp[:, j:j + t] * w[j] for j in range(m.conv))
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :di].reshape(b, t, m.heads, m.head_dim)
    Bm, Cm = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = _scan_heads(xs, dt, A, Bm, Cm)
    y = y + p["D"].astype(jnp.float32)[:, None] * xs
    y = nx.rms_norm(y.reshape(b, t, di) * jax.nn.silu(z), p["ssm_norm"],
                    m.eps)
    return x + nx.mm(y, p["out_proj"], mode)


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
    lanes: every weight read once; each active lane's float32 state and
    conv window read and written; the matmuls, the conv and the state
    update of the active lanes. The context length does not enter."""
    del ctx_sum
    m = Dims.of(model)
    conv_ch = m.d_inner + 2 * m.d_state
    matmul = m.layers * (m.d * (conv_ch + m.d_inner + m.heads)
                         + m.d_inner * m.d) + m.vocab * m.d
    hpn = m.heads * m.head_dim * m.d_state
    per_lane = m.layers * (6 * hpn + 2 * m.conv * conv_ch)
    flops = (2.0 * matmul + per_lane) * n_active
    act = jnp.dtype(model["compute_dtype"]).itemsize
    lane_bytes = m.layers * 2 * (hpn * 4 + (m.conv - 1) * conv_ch * act)
    return flops, float(weight_bytes + lane_bytes * n_active)
