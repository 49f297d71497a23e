"""Attention implementations.

Layouts: q (B, Sq, Hq, D); k/v (B, Skv, Hkv, D); GQA handled in a grouped
(B, Hkv, G, Sq, D) layout so the kv tensors are never materially repeated.

Three execution paths:
  - ``naive``     : O(S²) reference oracle (tests, tiny shapes);
  - ``xla_flash`` : chunked, memory-efficient scan over KV with running
                    softmax — pure jnp, lowers on every backend, and is the
                    math the Pallas kernel implements;
  - ``pallas``    : TPU kernel (repro.kernels.flash_attention), validated
                    against ``xla_flash``/``naive`` in interpret mode.

Distribution:
  - ``context_attention``        : all-gather-KV context parallelism — the
    query sequence is sharded over the 'model' mesh axis (shard_map), KV is
    gathered per layer; masks use absolute positions via the shard offset.
    This keeps attention TP-effective for *any* head count (no head
    divisibility constraint).
  - ``decode_attention_sharded`` : flash-decoding — the KV cache is sharded
    along the sequence axis over 'model'; each shard computes a partial
    softmax and the results merge with the log-sum-exp trick via psum.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import current_ctx, shard_map

_NEG = -1e30


def _group(q: jax.Array, n_kv: int) -> jax.Array:
    """(B, Sq, Hq, D) -> (B, n_kv, G, Sq, D)."""
    b, s, hq, d = q.shape
    g = hq // n_kv
    return q.reshape(b, s, n_kv, g, d).transpose(0, 2, 3, 1, 4)


def _ungroup(o: jax.Array) -> jax.Array:
    """(B, n_kv, G, Sq, D) -> (B, Sq, Hq, D)."""
    b, n_kv, g, s, d = o.shape
    return o.transpose(0, 3, 1, 2, 4).reshape(b, s, n_kv * g, d)


def _scale(scale, d: int) -> float:
    """The scores' multiplier: ``scale``, or 1/sqrt(D) where it is None."""
    return 1.0 / math.sqrt(d) if scale is None else scale


def _mask(q_pos, kv_pos, causal: bool, window: int):
    """Boolean mask (..., Sq, Skv): True = attend."""
    m = jnp.ones(q_pos.shape + kv_pos.shape, dtype=bool)
    if causal:
        m &= kv_pos[None, :] <= q_pos[:, None]
    if window > 0:
        m &= kv_pos[None, :] > q_pos[:, None] - window
    return m


# ------------------------------------------------------------------- naive
def naive_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    kv_offset=0, scale=None) -> jax.Array:
    b, sq, hq, d = q.shape
    skv, n_kv = k.shape[1], k.shape[2]
    qg = _group(q, n_kv)
    scale = _scale(scale, d)
    s = jnp.einsum("bhgqd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = q_offset + jnp.arange(sq)
    kv_pos = kv_offset + jnp.arange(skv)
    m = _mask(q_pos, kv_pos, causal, window)
    s = jnp.where(m[None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return _ungroup(o).astype(q.dtype)


# --------------------------------------------------------------- xla flash
def flash_attention_xla(q, k, v, *, causal=True, window=0, q_offset=0,
                        kv_offset=0, kv_chunk=512, kv_len=None,
                        scale=None) -> jax.Array:
    """Memory-efficient attention: lax.scan over KV chunks, fp32 running
    softmax. ``q_offset``/``kv_offset`` may be traced (context parallelism).
    ``kv_len``: optional traced count of valid kv positions (decode caches).
    ``scale`` multiplies the scores (None: 1/sqrt(D)).
    """
    b, sq, hq, d = q.shape
    skv, n_kv = k.shape[1], k.shape[2]
    g = hq // n_kv
    kv_chunk = min(kv_chunk, skv)
    n_chunks = max(skv // kv_chunk, 1)
    rem = skv - n_chunks * kv_chunk
    if rem:  # fold the remainder into one extra padded chunk
        pad = kv_chunk - rem
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_len is None:
            kv_len = skv
        skv = skv + pad
        n_chunks += 1
    qg = _group(q, n_kv).astype(jnp.float32)  # (B, Hkv, G, Sq, D)
    scale = _scale(scale, d)
    q_pos = q_offset + jnp.arange(sq)

    ks = k.reshape(b, n_chunks, kv_chunk, n_kv, d).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(b, n_chunks, kv_chunk, n_kv, d).transpose(1, 0, 3, 2, 4)
    chunk_ids = jnp.arange(n_chunks)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        k_c, v_c, cid = xs  # (B, Hkv, kv_chunk, D)
        kv_pos = kv_offset + cid * kv_chunk + jnp.arange(kv_chunk)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_c.astype(jnp.float32)) * scale
        msk = _mask(q_pos, kv_pos, causal, window)
        if kv_len is not None:
            msk &= (kv_pos < kv_len)[None, :]
        s = jnp.where(msk[None, None, None], s, _NEG)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.where(msk[None, None, None], jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, v_c.astype(jnp.float32))
        return (m_new, l_new, acc), None

    # Derive the initial carry from qg so it inherits qg's varying-across-mesh
    # type (required for lax.scan carries inside shard_map).
    m0 = qg[..., 0] * 0 + _NEG
    l0 = qg[..., 0] * 0
    a0 = qg * 0
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, chunk_ids))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return _ungroup(o).astype(q.dtype)


def window_attention_xla(q, k, v, *, window, q_offset=0, q_chunk=0,
                         scale=None) -> jax.Array:
    """Sliding-window attention with per-q-chunk KV slicing: each query chunk
    only reads a (window + chunk)-sized KV slice, so HLO FLOPs are
    O(S·window) rather than O(S²). ``q_offset`` may be traced.
    """
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    q_chunk = q_chunk or min(512, sq)
    span = window + q_chunk
    if span >= skv:
        return flash_attention_xla(q, k, v, causal=True, window=window,
                                   q_offset=q_offset, scale=scale)
    outs = []
    for a in range(0, sq, q_chunk):
        cq = min(q_chunk, sq - a)
        qc = q[:, a : a + cq]
        start = q_offset + a - window + 1
        start = jnp.clip(start, 0, skv - span)
        kc = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        outs.append(
            flash_attention_xla(
                qc, kc, vc, causal=True, window=window,
                q_offset=q_offset + a, kv_offset=start, kv_chunk=span,
                scale=scale,
            )
        )
    return jnp.concatenate(outs, axis=1)


# --------------------------------------------------- distributed (shard_map)
def context_attention(q, k, v, *, causal=True, window=0,
                      scale=None) -> jax.Array:
    """All-gather-KV context parallelism over the 'model' axis.

    Queries stay sequence-sharded; each shard gathers the full KV for the
    layer and computes its slice of the attention with absolute-position
    masks. Falls back to a local call when no mesh is active or the sequence
    does not divide the axis.
    """
    ctx = current_ctx()
    mesh = ctx.mesh
    sq = q.shape[1]

    def local(qq, kk, vv, q_off):
        if window > 0 and causal:
            return window_attention_xla(qq, kk, vv, window=window,
                                        q_offset=q_off, scale=scale)
        return flash_attention_xla(qq, kk, vv, causal=causal, window=window,
                                   q_offset=q_off, scale=scale)

    axes = ctx.mesh_axes("seq")
    if mesh is None or not axes or sq % ctx.axes_size("seq") != 0:
        return local(q, k, v, 0)
    axis = axes[0]
    tp = mesh.shape[axis]
    kv_sharded = k.shape[1] % tp == 0
    bspec = ctx.spec(("batch",), (q.shape[0],))[0]
    qspec = P(bspec, axis, None, None)
    kvspec = P(bspec, axis if kv_sharded else None, None, None)

    def f(qq, kk, vv):
        if kv_sharded:
            kk = jax.lax.all_gather(kk, axis, axis=1, tiled=True)
            vv = jax.lax.all_gather(vv, axis, axis=1, tiled=True)
        q_off = jax.lax.axis_index(axis) * qq.shape[1]
        return local(qq, kk, vv, q_off)

    return shard_map(f, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
                         out_specs=qspec)(q, k, v)


def decode_attention_local(q, k_cache, v_cache, *, pos, window=0,
                           kv_offset=0, scale=None) -> jax.Array:
    """Single-token attention over a cache: q (B, Hq, D), cache
    (B, S, Hkv·D) with each position's heads side by side, ``pos`` =
    current absolute position (traced) — a scalar, or a (B,) vector of
    per-slot positions (continuous batching: each lane masks against its
    own progress).

    The cache is read as it lies: splitting its heads apart would relayout
    it whenever D is no multiple of the device's 128-lane tile. q is spread
    block-diagonally instead, row h·G+j holding query head h·G+j in the D
    columns of KV head h and zeros elsewhere, so one contraction over the
    merged axis scores every head, and the diagonal blocks of the value
    contraction are the heads' outputs."""
    b, hq, d = q.shape
    skv, n_kv = k_cache.shape[1], k_cache.shape[2] // d
    g = hq // n_kv
    eye = jnp.eye(n_kv, dtype=jnp.float32)
    qg = q.reshape(b, n_kv, g, d).astype(jnp.float32)
    qbd = jnp.einsum("bhgd,hk->bhgkd", qg, eye).reshape(b, n_kv, g, -1)
    scale = _scale(scale, d)
    s = jnp.einsum("bhgc,bkc->bhgk", qbd, k_cache.astype(jnp.float32)) * scale
    kv_pos = kv_offset + jnp.arange(skv)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
    msk = kv_pos[None, :] <= pos_b[:, None]                 # (B, Skv)
    if window > 0:
        msk &= kv_pos[None, :] > pos_b[:, None] - window
    s = jnp.where(msk[:, None, None, :], s, _NEG)
    m = s.max(axis=-1)
    p = jnp.where(msk[:, None, None, :], jnp.exp(s - m[..., None]), 0.0)
    l = p.sum(axis=-1)
    r = jnp.einsum("bhgk,bkc->bhgc", p, v_cache.astype(jnp.float32))
    o = jnp.einsum("bhgkd,hk->bhgd", r.reshape(b, n_kv, g, n_kv, d), eye)
    return (o / jnp.maximum(l, 1e-30)[..., None], m, l)


def decode_attention(q, k_cache, v_cache, *, pos, window=0,
                     scale=None) -> jax.Array:
    """Flash-decoding: cache sequence-sharded over 'model', LSE-combined via
    psum — architecture-independent of head counts. q: (B, Hq, D), cache
    (B, S, Hkv·D) as :func:`decode_attention_local` takes it."""
    ctx = current_ctx()
    mesh = ctx.mesh
    b, hq, d = q.shape
    skv = k_cache.shape[1]

    axes = ctx.mesh_axes("kv_seq")
    if mesh is None or not axes or skv % ctx.axes_size("kv_seq") != 0:
        o, _, _ = decode_attention_local(q, k_cache, v_cache, pos=pos,
                                         window=window, scale=scale)
        return o.reshape(b, hq, d).astype(q.dtype)
    # kv_seq may map to several mesh axes (e.g. ('data', 'model') for the
    # batch-1 long-context cells, where the data axis would otherwise idle):
    # the cache shards over all of them and the LSE combine psums over all.
    axes = tuple(a for a in axes)
    bspec = ctx.spec(("batch",), (b,))[0]
    if bspec is not None:
        used = set(bspec if isinstance(bspec, tuple) else (bspec,))
        axes = tuple(a for a in axes if a not in used) or axes
    qspec = P(bspec, None, None)
    cspec = P(bspec, axes if len(axes) > 1 else axes[0], None)
    # per-slot pos vectors shard with the batch; scalar pos is replicated
    pspec = P(bspec) if jnp.ndim(pos) else P()

    def f(qq, kk, vv, pp):
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        base = idx * kk.shape[1]
        o, m, l = decode_attention_local(qq, kk, vv, pos=pp, window=window,
                                         kv_offset=base, scale=scale)
        # o is per-shard *normalized* (acc / l): re-weight each shard's
        # contribution by exp(m - gm) * l before the global combine.
        gm = jax.lax.pmax(m, axes)
        wl = jnp.exp(m - gm) * l
        num = jax.lax.psum(o * wl[..., None], axes)
        den = jax.lax.psum(wl, axes)
        return num / jnp.maximum(den, 1e-30)[..., None]

    o = shard_map(f, mesh=mesh, in_specs=(qspec, cspec, cspec, pspec),
                      out_specs=qspec)(q, k_cache, v_cache, pos)
    return o.reshape(b, hq, d).astype(q.dtype)


# ----------------------------------------------------------------- dispatch
def attend(q, k, v, *, causal=True, window=0, impl="xla_flash",
           q_offset=0) -> jax.Array:
    if impl == "naive":
        return naive_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset)
    if impl == "pallas":
        from repro.kernels import flash_attention as fa
        return fa.ops.flash_attention(q, k, v, causal=causal, window=window)
    if window > 0 and causal:
        return window_attention_xla(q, k, v, window=window, q_offset=q_offset)
    return flash_attention_xla(q, k, v, causal=causal, window=window,
                               q_offset=q_offset)
