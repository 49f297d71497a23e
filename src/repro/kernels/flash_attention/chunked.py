"""Memory-efficient chunked-softmax Pallas attention variant.

Same function as ``kernel.flash_attention_tpu`` (causal / sliding-window,
GQA), different implementation point: the lazy two-pass softmax of Rabe &
Staats (arXiv:2112.05682) instead of the online single-pass rescale.

  grid = (batch * q_heads, n_q_blocks, 2 * n_kv_blocks), the last axis
  sequential. K/V stream through VMEM in (bk, d) tiles, twice: the first
  n_kv_blocks steps are pass 1, the rest pass 2.

    pass 1:  m  = max over all kv tiles of masked q·kᵀ rows
    pass 2:  l += Σ exp(s - m);  acc += exp(s - m) · v

  Because ``m`` is final before any accumulation starts, the accumulator
  is never rescaled — the per-chunk ``exp(m_prev - m_new)`` corrections
  of the online algorithm (two extra VPU passes over (bq, d) + (bq, bk)
  per chunk) disappear, at the price of reading K twice (V's index map
  holds tile 0 through pass 1, so V is read once). That trades
  bandwidth for vector work: a second implementation point on the
  energy frontier, cheaper where exp/multiply throughput is the bound
  (little cores) and dearer where HBM bandwidth is. The (bq, skv) score
  matrix is never materialized — the live set is one (bq, bk) tile plus
  the (bq, d) accumulator and the (bq, 128) max/sum rows, whatever the
  sequence length.

Validated in interpret mode against ref.py on CPU (tests/test_kernels.py);
TPU is the compile target.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale,
            causal, window, bq, bk, nk, seq_kv):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    ki = j % nk

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (bq, bk)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kv_pos < seq_kv
    if causal:
        mask &= kv_pos <= q_pos
    if window > 0:
        mask &= kv_pos > q_pos - window

    @pl.when(j < nk)
    def _max_pass():
        m_cur = jnp.max(jnp.where(mask, s, NEG), axis=1, keepdims=True)
        m_ref[...] = jnp.maximum(m_ref[...], m_cur)

    @pl.when(j >= nk)
    def _acc_pass():
        v = v_ref[0, 0].astype(jnp.float32)        # (bk, d)
        p = jnp.where(mask, jnp.exp(s - m_ref[:, :1]), 0.0)   # (bq, bk)
        l_ref[...] += jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] += jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == 2 * nk - 1)
    def _finalize():
        o_ref[0, 0, :, :] = (acc_ref[...] /
                             jnp.maximum(l_ref[:, :1], 1e-30)
                             ).astype(o_ref.dtype)


def chunked_attention_tpu(q, k, v, *, causal=True, window=0, bq=128,
                          bk=128, interpret=False):
    """q (B, Hq, Sq, D); k/v (B, Hkv, Skv, D) -> (B, Hq, Sq, D)."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)
    bq = min(bq, sq)
    bk = min(bk, skv)
    pad_q = (-sq) % bq
    pad_k = (-skv) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (sq + pad_q) // bq
    nk = (skv + pad_k) // bk

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, nk=nk, seq_kv=skv)
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, 2 * nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bh, qi, j: (bh // hq, bh % hq, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bh, qi, j: (bh // hq, (bh % hq) // group,
                                            j % nk, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bh, qi, j: (bh // hq, (bh % hq) // group,
                                            jnp.maximum(j - nk, 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bh, qi, j: (bh // hq, bh % hq, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq + pad_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq]
