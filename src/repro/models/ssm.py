"""Mamba2 (SSD — state-space duality) blocks: chunked prefill/train scan and
O(1) decode state updates.

The chunked SSD algorithm (Dao & Gu, 2024) splits the sequence into chunks of
Q tokens; within a chunk the output is a masked quadratic form (the "dual"
attention-like view), across chunks a small (H, P, N) state is carried by a
scan. ``ssd_ref`` is the pure-jnp oracle; ``repro.kernels.ssd_scan`` is the
Pallas TPU kernel implementing the same block decomposition.

Shapes: x (B, L, H, P); dt (B, L, H); A (H,); B/C (B, L, N)  [one state
group]; state (B, H, P, N).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import SSMConfig
from repro.models.layers import rms_norm
from repro.sharding import shard


def ssd_ref(x, dt, A, B, C, chunk: int = 128, init_state=None):
    """Chunked SSD. Returns (y (B, L, H, P), final_state (B, H, P, N))."""
    b, l, h, p = x.shape
    n = B.shape[-1]
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    lp = l + pad
    nc = lp // chunk
    xc = x.reshape(b, nc, chunk, h, p).astype(jnp.float32)
    dtc = dt.reshape(b, nc, chunk, h).astype(jnp.float32)
    Bc = B.reshape(b, nc, chunk, n).astype(jnp.float32)
    Cc = C.reshape(b, nc, chunk, n).astype(jnp.float32)
    dA = dtc * A.astype(jnp.float32)  # (B, nc, Q, H)
    seg = jnp.cumsum(dA, axis=2)      # inclusive within-chunk cumsum

    # Intra-chunk (quadratic) term: y[i] += sum_{j<=i} (C_i.B_j) e^{seg_i-seg_j} dt_j x_j
    G = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)  # (B, nc, Q, Q)
    decay = jnp.exp(seg[:, :, :, None, :] - seg[:, :, None, :, :])  # (b,nc,i,j,h)
    idx = jnp.arange(chunk)
    causal = (idx[:, None] >= idx[None, :])[None, None, :, :, None]
    M = jnp.where(causal, G[..., None] * decay, 0.0)
    y_intra = jnp.einsum("bcijh,bcjh,bcjhp->bcihp", M, dtc, xc)

    # Chunk summary state: S_c = sum_j e^{seg_Q - seg_j} dt_j x_j B_j^T
    last = seg[:, :, -1:, :]                       # (b, nc, 1, h)
    w_end = jnp.exp(last - seg)                    # (b, nc, Q, h)
    chunk_state = jnp.einsum("bcjh,bcjh,bcjhp,bcjn->bchpn",
                             w_end, dtc, xc, Bc)

    # Inter-chunk scan: S_{c} = e^{sum dA_c} S_{c-1} + chunk_state_c
    tot = jnp.exp(last[:, :, 0, :])                # (b, nc, h)
    s0 = (jnp.zeros((b, h, p, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def body(s_prev, xs):
        cs, t = xs  # (b, h, p, n), (b, h)
        s_new = s_prev * t[..., None, None] + cs
        return s_new, s_prev

    states_in = jnp.moveaxis(chunk_state, 1, 0), jnp.moveaxis(tot, 1, 0)
    final, prevs = jax.lax.scan(body, s0, states_in)
    prev_states = jnp.moveaxis(prevs, 0, 1)        # state entering each chunk

    # Inter-chunk contribution: y[i] += C_i . (e^{seg_i} S_prev)
    w_in = jnp.exp(seg)                            # (b, nc, Q, h)
    y_inter = jnp.einsum("bcin,bchpn,bcih->bcihp", Cc, prev_states, w_in)

    y = (y_intra + y_inter).reshape(b, lp, h, p)[:, :l]
    return y, final


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t):
    """One-token state update. x_t (B, H, P); dt_t (B, H); B/C_t (B, N)."""
    state = state.astype(jnp.float32)
    dA = jnp.exp(dt_t.astype(jnp.float32) * A.astype(jnp.float32))  # (B, H)
    upd = jnp.einsum("bh,bhp,bn->bhpn", dt_t.astype(jnp.float32),
                     x_t.astype(jnp.float32), B_t.astype(jnp.float32))
    new_state = state * dA[..., None, None] + upd
    y = jnp.einsum("bn,bhpn->bhp", C_t.astype(jnp.float32), new_state)
    return y, new_state


# ---------------------------------------------------------------- the block
def causal_conv(x, w, cache=None):
    """Depthwise causal conv. x (B, L, C), w (W, C). Returns (y, new_cache)
    where cache holds the last W-1 inputs for decode."""
    width = w.shape[0]
    if cache is None:
        xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([cache.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i : i + x.shape[1]] * w[i][None, None, :]
            for i in range(width))
    new_cache = xp[:, -(width - 1):] if width > 1 else None
    return y.astype(x.dtype), new_cache


def mamba_block(params, x, cfg: SSMConfig, *, conv_cache=None, ssd_state=None,
                chunk=None, use_kernel=False):
    """Full Mamba2 block. x (B, L, D). Returns (out, (conv_cache, ssd_state))."""
    b, l, d = x.shape
    di = cfg.d_inner(d)
    n = cfg.d_state
    h = cfg.n_heads(d)
    proj = x @ params["in_proj"]  # (B, L, 2*di + 2n + h)
    z, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * n], axis=-1)
    xbc, new_conv = causal_conv(xbc, params["conv_w"], conv_cache)
    xbc = jax.nn.silu(xbc)
    xs, Bv, Cv = jnp.split(xbc, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    xh = xs.reshape(b, l, h, cfg.head_dim)
    xh = shard(xh, "batch", None, "q_heads", None)
    if l == 1 and ssd_state is not None:
        y, new_state = ssd_decode_step(
            ssd_state, xh[:, 0], dt[:, 0], A, Bv[:, 0], Cv[:, 0])
        y = y[:, None]
    elif use_kernel:
        from repro.kernels import ssd_scan
        y, new_state = ssd_scan.ops.ssd(xh, dt, A, Bv, Cv,
                                        chunk=chunk or cfg.chunk)
    else:
        y, new_state = ssd_ref(xh, dt, A, Bv, Cv, chunk=chunk or cfg.chunk,
                               init_state=ssd_state)
    y = y + params["D"].astype(jnp.float32)[None, None, :, None] \
        * xh.astype(jnp.float32)
    y = y.reshape(b, l, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["ssm_norm"])
    out = y @ params["out_proj"]
    return out, (new_conv, new_state.astype(jnp.float32))
