"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid = (batch, heads, n_chunks) with chunks innermost (sequential), so the
(P, N) recurrent state lives in VMEM scratch across chunk steps — the HBM
traffic per chunk is exactly one (Q, P) x-block, one (Q, N) B/C block pair
and one (Q, P) y-block, the roofline-optimal schedule for SSD.

Per chunk (block decomposition of Dao & Gu 2024):
  seg   = prefix sum of dt * A                 (Q,)
  y_in  = (C B^T ⊙ decay ⊙ dt) · x   (masked causal, quadratic in Q)
  y_out = C · S_prev^T scaled by e^{seg}
  S     = e^{seg_Q} S_prev + Σ_j e^{seg_Q - seg_j} dt_j x_j ⊗ B_j

Validated in interpret mode against ref.py; TPU is the compile target.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref,
            s_scratch, *, chunk, seq_len):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scratch[...] = jnp.zeros_like(s_scratch)

    x = x_ref[0, 0].astype(jnp.float32)       # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)     # (Q, 1) broadcast later
    a = a_ref[pl.program_id(1)]               # scalar A_h from SMEM
    bm = b_ref[0].astype(jnp.float32)         # (Q, N)
    cm = c_ref[0].astype(jnp.float32)         # (Q, N)
    q = x.shape[0]

    # mask padded positions (dt = 0 there -> identity updates)
    pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0)
    dt = jnp.where(pos < seq_len, dt, 0.0)

    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    da = dt * a                                # (Q, 1)
    # the chip's kernel compiler has no cumsum: prefix sum as a masked
    # (Q, Q) row reduction
    seg = jnp.sum(jnp.where(jj <= ii, da.T, 0.0), axis=1,
                  keepdims=True)               # (Q, 1)
    # intra-chunk quadratic term
    g = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    decay = jnp.exp(seg - seg.T)               # (Q, Q) e^{seg_i - seg_j}
    m = jnp.where(ii >= jj, g * decay, 0.0) * dt.T  # (Q, Q) ⊙ dt_j
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, P)
    # inter-chunk contribution: C_i · S_prev with e^{seg_i}
    s_prev = s_scratch[...]                    # (N, P)
    y += jnp.exp(seg) * jax.lax.dot_general(
        cm, s_prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)    # (Q, P)
    y_ref[0, 0, :, :] = y.astype(y_ref.dtype)
    # state update: S = e^{seg_Q} S_prev + Σ_j e^{seg_Q - seg_j} dt_j B_j x_j^T
    last = seg[q - 1:q, :]                     # (1, 1)
    w_end = jnp.exp(last - seg) * dt           # (Q, 1)
    s_new = jnp.exp(last)[0, 0] * s_prev + jax.lax.dot_general(
        bm * w_end, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)    # (N, P)
    s_scratch[...] = s_new

    @pl.when(ci == pl.num_programs(2) - 1)
    def _emit_state():
        state_ref[0, 0, :, :] = s_new.astype(state_ref.dtype)


def ssd_tpu(x, dt, a, bmat, cmat, *, chunk=128, interpret=False):
    """x (B, L, H, P); dt (B, L, H) [post-softplus]; a (H,) [negative];
    bmat/cmat (B, L, N). Returns (y (B, L, H, P), state (B, H, N, P))."""
    b, l, h, p = x.shape
    n = bmat.shape[-1]
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0)))
    lp = l + pad
    nc = lp // chunk
    # layouts: x -> (B, H, L, P); dt -> (B, H, L, 1); B/C -> (B, L, N)
    xt = x.transpose(0, 2, 1, 3)
    dtt = dt.transpose(0, 2, 1)[..., None]

    kernel = functools.partial(_kernel, chunk=chunk, seq_len=l)
    y, state = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            # A whole in SMEM: a (1,) VMEM block of an (H,) vector is not
            # aligned to the 128-lane tiling, and the chip refuses it
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, lp, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, a.astype(jnp.float32), bmat, cmat)
    y = y.transpose(0, 2, 1, 3)[:, :l]
    return y, state.transpose(0, 1, 3, 2)  # -> (B, H, P, N)
