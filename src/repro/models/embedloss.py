"""Distributed token embedding, vocab-parallel cross-entropy and greedy
sampling over a vocab-sharded (tied) embedding table.

The table is stored sharded over the 'model' axis on the vocab dimension.
Naive ``jnp.take``/``x @ table.T`` under GSPMD tends to all-gather the table
(GBs for 262k vocabs) — these shard_map versions keep the table in place:

- ``embed_in``  : each shard embeds all tokens against its vocab slice
  (misses contribute zeros) and the partial activations reduce-scatter onto
  the sequence axis → output arrives already sequence-sharded for context
  parallelism. Comm = B·S·D/shards, no table movement.
- ``lm_loss``   : vocab-parallel CE (Megatron-style): activations are
  gathered over the sequence axis once, each shard computes logits for its
  vocab slice in sequence chunks (bounded memory), and log-sum-exp /
  gold-logit terms combine with pmax/psum.
- ``greedy``    : decode-time argmax over the sharded vocab via local top-1 +
  global max combine.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import current_ctx, shard_map

_NEG = -1e30


def _vocab_axis(v: int):
    ctx = current_ctx()
    axes = ctx.mesh_axes("vocab")
    if ctx.mesh is None or not axes or v % ctx.axes_size("vocab"):
        return None
    return axes[0]


def embed_in(table: jax.Array, tokens: jax.Array, compute_dtype) -> jax.Array:
    """table (V, D) vocab-sharded; tokens (B, S) -> x (B, S, D) seq-sharded."""
    v, d = table.shape
    b, s = tokens.shape
    ctx = current_ctx()
    axis = _vocab_axis(v)
    if axis is None:
        return jnp.take(table, tokens, axis=0).astype(compute_dtype)
    tp = ctx.mesh.shape[axis]
    bspec = ctx.spec(("batch",), (b,))[0]
    seq_ok = s % tp == 0

    def f(tbl, tok):
        lo = jax.lax.axis_index(axis) * tbl.shape[0]
        ids = tok - lo
        ok = (ids >= 0) & (ids < tbl.shape[0])
        rows = jnp.take(tbl, jnp.clip(ids, 0, tbl.shape[0] - 1), axis=0)
        part = jnp.where(ok[..., None], rows, 0).astype(jnp.float32)
        if seq_ok:  # arrive sequence-sharded: reduce-scatter over seq
            out = jax.lax.psum_scatter(part, axis, scatter_dimension=1,
                                       tiled=True)
        else:
            out = jax.lax.psum(part, axis)
        return out.astype(compute_dtype)

    out_spec = P(bspec, axis if seq_ok else None, None)
    return shard_map(
        f, mesh=ctx.mesh, in_specs=(P(axis, None), P(bspec, None)),
        out_specs=out_spec)(table, tokens)


def lm_loss(x: jax.Array, table: jax.Array, labels: jax.Array,
            valid_vocab: int | None = None, seq_chunk: int = 1024
            ) -> jax.Array:
    """Mean CE over valid (label >= 0) tokens. x (B, S, D) seq-sharded;
    table (Vp, D) vocab-sharded; labels (B, S). Columns >= valid_vocab
    (Megatron-style vocab padding) are masked out of the softmax.

    The sharded path uses a hand-written backward (custom_vjp): the forward
    never materializes full logits (sequence-chunked, per-vocab-shard), and
    the backward recomputes the chunk softmax instead of saving it —
    d logits = (softmax - onehot) * mask / N. This is both the memory-optimal
    schedule and sidesteps JAX's linearize-through-shard_map residual
    limitations.
    """
    v, _ = table.shape
    valid = valid_vocab or v
    axis = _vocab_axis(v)
    if axis is None:
        return _ce_chunked(x, table, labels, valid, seq_chunk)
    return _lm_loss_sharded(x, table, labels, valid, seq_chunk, axis)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _lm_loss_sharded(x, table, labels, valid, seq_chunk, axis):
    return _lm_loss_fwd_impl(x, table, labels, valid, seq_chunk, axis)[0]


def _plan(x, table, axis):
    ctx = current_ctx()
    b, s, d = x.shape
    tp = ctx.mesh.shape[axis]
    bspec = ctx.spec(("batch",), (b,))[0]
    batch_axes = () if bspec is None else (
        bspec if isinstance(bspec, tuple) else tuple(bspec) if isinstance(bspec, (list,)) else (bspec,))
    seq_sharded = s % tp == 0
    xspec = P(bspec, axis if seq_sharded else None, None)
    return ctx, bspec, batch_axes, seq_sharded, xspec


def _chunks(xx, lab, d, seq_chunk):
    bl, s = lab.shape
    n_chunk = max(s // min(seq_chunk, s), 1)
    cs = s // n_chunk
    xs = xx.reshape(bl, n_chunk, cs, d).transpose(1, 0, 2, 3)
    ls = lab.reshape(bl, n_chunk, cs).transpose(1, 0, 2)
    return xs, ls, n_chunk, cs


def _lm_loss_fwd_impl(x, table, labels, valid, seq_chunk, axis):
    ctx, bspec, batch_axes, seq_sharded, xspec = _plan(x, table, axis)
    d = x.shape[-1]

    def f(xx, tbl, lab):
        if seq_sharded:
            xx = jax.lax.all_gather(xx, axis, axis=1, tiled=True)
        lo = jax.lax.axis_index(axis) * tbl.shape[0]
        col_ok = (lo + jnp.arange(tbl.shape[0])) < valid
        tbl32 = tbl.astype(jnp.float32)
        xs, ls, _, _ = _chunks(xx, lab, d, seq_chunk)

        def chunk_nll(_, inp):
            xc, lc = inp
            logits = xc.astype(jnp.float32) @ tbl32.T  # (B, cs, V_local)
            logits = jnp.where(col_ok[None, None], logits, _NEG)
            gm = jax.lax.pmax(logits.max(axis=-1), axis)
            se = jnp.where(col_ok[None, None],
                           jnp.exp(logits - gm[..., None]), 0.0).sum(axis=-1)
            se = jax.lax.psum(se, axis)
            ids = lc - lo
            ok = (ids >= 0) & (ids < tbl.shape[0])
            gold = jnp.take_along_axis(
                logits, jnp.clip(ids, 0, tbl.shape[0] - 1)[..., None], axis=-1
            )[..., 0]
            gold = jax.lax.psum(jnp.where(ok, gold, 0.0), axis)
            nll = gm + jnp.log(se) - gold
            mask = (lc >= 0).astype(jnp.float32)
            return None, (jnp.sum(nll * mask), jnp.sum(mask))

        _, (nll_sum, cnt) = jax.lax.scan(chunk_nll, None, (xs, ls))
        tot, n = jnp.sum(nll_sum), jnp.sum(cnt)
        if batch_axes:  # global token mean across the data shards
            tot = jax.lax.psum(tot, batch_axes)
            n = jax.lax.psum(n, batch_axes)
        return tot / jnp.maximum(n, 1.0)

    loss = shard_map(
        f, mesh=ctx.mesh,
        in_specs=(xspec, P(axis, None), P(bspec, None)),
        out_specs=P())(x, table, labels)
    return loss, (x, table, labels)


def _lm_loss_bwd_impl(valid, seq_chunk, axis, res, g):
    x, table, labels = res
    ctx, bspec, batch_axes, seq_sharded, xspec = _plan(x, table, axis)
    d = x.shape[-1]

    def f(xx, tbl, lab, gg):
        if seq_sharded:
            xx = jax.lax.all_gather(xx, axis, axis=1, tiled=True)
        lo = jax.lax.axis_index(axis) * tbl.shape[0]
        col_ok = (lo + jnp.arange(tbl.shape[0])) < valid
        tbl32 = tbl.astype(jnp.float32)
        xs, ls, n_chunk, cs = _chunks(xx, lab, d, seq_chunk)
        n = jnp.sum((lab >= 0).astype(jnp.float32))
        if batch_axes:
            n = jax.lax.psum(n, batch_axes)
        scale = gg / jnp.maximum(n, 1.0)

        def chunk_bwd(gt_acc, inp):
            xc, lc = inp
            xc32 = xc.astype(jnp.float32)
            logits = xc32 @ tbl32.T
            logits = jnp.where(col_ok[None, None], logits, _NEG)
            gm = jax.lax.pmax(logits.max(axis=-1), axis)
            e = jnp.where(col_ok[None, None],
                          jnp.exp(logits - gm[..., None]), 0.0)
            se = jax.lax.psum(e.sum(axis=-1), axis)
            p = e / se[..., None]
            ids = lc - lo
            ok = (ids >= 0) & (ids < tbl.shape[0])
            onehot = jax.nn.one_hot(jnp.where(ok, ids, tbl.shape[0]),
                                    tbl.shape[0], dtype=jnp.float32)
            mask = (lc >= 0).astype(jnp.float32)[..., None]
            dlog = (p - onehot) * mask * scale      # (B, cs, V_local)
            gx_c = dlog @ tbl32                      # partial over vocab
            gt_acc = gt_acc + jnp.einsum("bcv,bcd->vd", dlog, xc32)
            return gt_acc, gx_c

        gt0 = jnp.zeros_like(tbl, dtype=jnp.float32) + 0.0 * xs[0, :1, :1, 0].sum()
        gt, gx_chunks = jax.lax.scan(chunk_bwd, gt0, (xs, ls))
        bl = xs.shape[1]
        gx = gx_chunks.transpose(1, 0, 2, 3).reshape(bl, n_chunk * cs, d)
        if seq_sharded:  # vjp of all_gather = reduce-scatter onto seq
            gx = jax.lax.psum_scatter(gx, axis, scatter_dimension=1,
                                      tiled=True)
        else:
            gx = jax.lax.psum(gx, axis)
        if batch_axes:  # table grads sum over the data shards
            gt = jax.lax.psum(gt, batch_axes)
        return gx.astype(x.dtype), gt.astype(table.dtype)

    gx, gt = shard_map(
        f, mesh=ctx.mesh,
        in_specs=(xspec, P(axis, None), P(bspec, None), P()),
        out_specs=(xspec, P(axis, None)))(x, table, labels,
                                          jnp.asarray(g, jnp.float32))
    return gx, gt, None


_lm_loss_sharded.defvjp(
    lambda x, t, l, valid, sc, ax: _lm_loss_fwd_impl(x, t, l, valid, sc, ax),
    _lm_loss_bwd_impl)


def greedy(x: jax.Array, table: jax.Array,
           valid_vocab: int | None = None) -> jax.Array:
    """Greedy next-token ids. x (B, D); table (Vp, D) vocab-sharded."""
    v, d = table.shape
    valid = valid_vocab or v
    axis = _vocab_axis(v)
    if axis is None:
        logits = x.astype(jnp.float32) @ table.astype(jnp.float32).T
        logits = jnp.where(jnp.arange(v)[None] < valid, logits, -jnp.inf)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ctx = current_ctx()
    bspec = ctx.spec(("batch",), (x.shape[0],))[0]

    def f(xx, tbl):
        lo = jax.lax.axis_index(axis) * tbl.shape[0]
        logits = xx.astype(jnp.float32) @ tbl.astype(jnp.float32).T
        col_ok = (lo + jnp.arange(tbl.shape[0])) < valid
        logits = jnp.where(col_ok[None], logits, -jnp.inf)
        best = jnp.argmax(logits, axis=-1)
        val = jnp.take_along_axis(logits, best[:, None], axis=-1)[:, 0]
        gbest = jax.lax.pmax(val, axis)
        tok = jnp.where(val >= gbest, best + lo, -1)
        return jax.lax.pmax(tok, axis).astype(jnp.int32)

    return shard_map(f, mesh=ctx.mesh,
                         in_specs=(P(bspec, None), P(axis, None)),
                         out_specs=P(bspec))(x, table)


def _ce_chunked(x, table, labels, valid, seq_chunk):
    """Local (unsharded) chunked CE — bounds the logits transient."""
    b, s, d = x.shape
    v = table.shape[0]
    tbl32 = table.astype(jnp.float32)
    col_ok = jnp.arange(v) < valid
    n_chunk = max(s // min(seq_chunk, s), 1)
    cs = s // n_chunk
    rem = s - n_chunk * cs
    xs = x[:, : n_chunk * cs].reshape(b, n_chunk, cs, d).transpose(1, 0, 2, 3)
    ls = labels[:, : n_chunk * cs].reshape(b, n_chunk, cs).transpose(1, 0, 2)

    def chunk_nll(_, inp):
        xc, lc = inp
        logits = xc.astype(jnp.float32) @ tbl32.T
        logits = jnp.where(col_ok[None, None], logits, -jnp.inf)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.clip(lc, 0, v - 1)[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        return None, (jnp.sum((lse - gold) * mask), jnp.sum(mask))

    _, (nll_sum, cnt) = jax.lax.scan(chunk_nll, None, (xs, ls))
    tot, n = jnp.sum(nll_sum), jnp.sum(cnt)
    if rem:
        xc, lc = x[:, n_chunk * cs:], labels[:, n_chunk * cs:]
        logits = xc.astype(jnp.float32) @ tbl32.T
        logits = jnp.where(col_ok[None, None], logits, -jnp.inf)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.clip(lc, 0, v - 1)[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        tot = tot + jnp.sum((lse - gold) * mask)
        n = n + jnp.sum(mask)
    return tot / jnp.maximum(n, 1.0)
