"""The model zoo: one generic implementation covering all assigned families.

Families (ModelConfig.kind):
  dense / vlm      : pre-norm decoder transformer (RoPE, GQA, SwiGLU);
                     vlm splices precomputed patch embeddings (frontend stub).
  moe              : dense skeleton with expert-parallel MoE FFN
                     (+ optional dense residual MLP — arctic).
  gemma-style      : `window > 0` — superblocks of (global_every-1) local
                     sliding-window layers + 1 global layer, single outer
                     scan; rolling window KV caches for local layers.
  ssm              : Mamba2 (SSD) stack.
  hybrid           : one Mamba2 stack with attention applied after some of
                     its layers, one body for two forms: zamba2 — one
                     *shared* attention block after every
                     `shared_attn_every` layers; granite — a GQA attention
                     layer of its own at `attn_offset` of every
                     `attn_every` layers, each layer with its own MLP.
  encdec / audio   : whisper — encoder (non-causal) + decoder with
                     cross-attention; frame embeddings from the frontend stub.

Layer stacks are scanned (`lax.scan`) with per-layer remat, so the lowered
HLO holds one layer body however deep the model. All activations follow the
context-parallel layout (batch over 'data'/'pod', sequence over 'model') in
train/prefill, and the Megatron/flash-decoding layout in decode
(`repro.sharding` maps the logical axes to the mesh).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import embedloss
from repro.models.attention import context_attention, decode_attention
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, rms_norm, rope_table
from repro.models.moe import moe_apply
from repro.models.ssm import mamba_block
from repro.sharding import shard

Params = Any


def _dt(name: str):
    return jnp.dtype(name)


# =========================================================== initialization
def _norm_init(rng, shape, dtype):
    return jnp.zeros(shape, dtype)


def _dense_init(rng, shape, dtype, in_axis=0):
    fan_in = shape[in_axis] if in_axis >= 0 else int(np.prod(shape[:-1]))
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(rng, shape, jnp.float32) * std).astype(dtype)


class _Maker:
    """Collects (leaf init, logical axes) declarations."""

    def __init__(self, rng, dtype):
        self.rng = rng
        self.dtype = dtype
        self.leaves: dict[str, Any] = {}
        self.axes: dict[str, Any] = {}

    def dense(self, name, shape, axes, in_axis=0):
        self.rng, sub = jax.random.split(self.rng)
        self.leaves[name] = _dense_init(sub, shape, self.dtype, in_axis)
        self.axes[name] = axes

    def norm(self, name, shape, axes):
        self.leaves[name] = jnp.zeros(shape, self.dtype)
        self.axes[name] = axes

    def const(self, name, value, axes):
        self.leaves[name] = value.astype(self.dtype) if value.dtype != jnp.int32 \
            else value
        self.axes[name] = axes


def _attn_leaves(m: _Maker, cfg: ModelConfig, stack: tuple[int, ...],
                 cross: bool = False):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    pre = "c" if cross else ""
    m.norm(pre + "ln_attn", stack + (d,), (None,) * len(stack) + ("embed",))
    m.dense(pre + "wq", stack + (d, hq * hd),
            (None,) * len(stack) + ("embed", "q_heads"), in_axis=len(stack))
    m.dense(pre + "wk", stack + (d, hkv * hd),
            (None,) * len(stack) + ("embed", "kv_heads"), in_axis=len(stack))
    m.dense(pre + "wv", stack + (d, hkv * hd),
            (None,) * len(stack) + ("embed", "kv_heads"), in_axis=len(stack))
    m.dense(pre + "wo", stack + (hq * hd, d),
            (None,) * len(stack) + ("q_heads", "embed"), in_axis=len(stack))


def _mlp_leaves(m: _Maker, cfg: ModelConfig, stack: tuple[int, ...]):
    d, f = cfg.d_model, cfg.d_ff
    m.norm("ln_mlp", stack + (d,), (None,) * len(stack) + ("embed",))
    m.dense("w_gate", stack + (d, f), (None,) * len(stack) + ("embed", "ff"),
            in_axis=len(stack))
    m.dense("w_up", stack + (d, f), (None,) * len(stack) + ("embed", "ff"),
            in_axis=len(stack))
    m.dense("w_down", stack + (f, d), (None,) * len(stack) + ("ff", "embed"),
            in_axis=len(stack))


def _moe_leaves(m: _Maker, cfg: ModelConfig, stack: tuple[int, ...]):
    d = cfg.d_model
    mo = cfg.moe
    ns = len(stack)
    m.norm("ln_mlp", stack + (d,), (None,) * ns + ("embed",))
    m.dense("router", stack + (d, mo.n_experts),
            (None,) * ns + ("embed", None), in_axis=ns)
    m.dense("moe_gate", stack + (mo.n_experts, d, mo.d_ff_expert),
            (None,) * ns + ("experts", "embed", "expert_ff"), in_axis=ns + 1)
    m.dense("moe_up", stack + (mo.n_experts, d, mo.d_ff_expert),
            (None,) * ns + ("experts", "embed", "expert_ff"), in_axis=ns + 1)
    m.dense("moe_down", stack + (mo.n_experts, mo.d_ff_expert, d),
            (None,) * ns + ("experts", "expert_ff", "embed"), in_axis=ns + 1)
    if mo.dense_residual:
        m.dense("w_gate", stack + (d, cfg.d_ff),
                (None,) * ns + ("embed", "ff"), in_axis=ns)
        m.dense("w_up", stack + (d, cfg.d_ff),
                (None,) * ns + ("embed", "ff"), in_axis=ns)
        m.dense("w_down", stack + (cfg.d_ff, d),
                (None,) * ns + ("ff", "embed"), in_axis=ns)


def _mamba_leaves(m: _Maker, cfg: ModelConfig, stack: tuple[int, ...],
                  with_mlp: bool):
    d = cfg.d_model
    s = cfg.ssm
    di, n, h, w = s.d_inner(d), s.d_state, s.n_heads(d), s.conv_width
    ns = len(stack)
    m.norm("ln_ssm", stack + (d,), (None,) * ns + ("embed",))
    m.dense("in_proj", stack + (d, 2 * di + 2 * n + h),
            (None,) * ns + ("embed", "ff"), in_axis=ns)
    m.dense("conv_w", stack + (w, di + 2 * n), (None,) * (ns + 2), in_axis=ns)
    m.rng, sub = jax.random.split(m.rng)
    m.leaves["dt_bias"] = jnp.broadcast_to(
        jnp.log(jnp.expm1(jnp.linspace(0.001, 0.1, h))), stack + (h,)
    ).astype(m.dtype)
    m.axes["dt_bias"] = (None,) * (ns + 1)
    m.leaves["A_log"] = jnp.broadcast_to(
        jnp.log(jnp.linspace(1.0, 16.0, h)), stack + (h,)).astype(m.dtype)
    m.axes["A_log"] = (None,) * (ns + 1)
    m.leaves["D"] = jnp.ones(stack + (h,), m.dtype)
    m.axes["D"] = (None,) * (ns + 1)
    m.norm("ssm_norm", stack + (di,), (None,) * ns + ("ff",))
    m.dense("out_proj", stack + (di, d), (None,) * ns + ("ff", "embed"),
            in_axis=ns)
    if with_mlp:
        _mlp_leaves(m, cfg, stack)


@dataclasses.dataclass(frozen=True)
class HybridLayout:
    """A hybrid's layers: one stack of ``n_mamba`` Mamba2 layers, with an
    attention block applied after Mamba2 layer ``attn_after[k]`` for each
    k. ``shared``: one attention block serves every application and the
    Mamba2 layers have no MLP (zamba2); else application k has attention
    weights of its own and every layer its own MLP (granite)."""
    n_mamba: int
    attn_after: tuple[int, ...]
    shared: bool

    def attn_index(self) -> np.ndarray:
        """Per Mamba2 layer, the attention application that follows it, or
        -1."""
        out = np.full((self.n_mamba,), -1, np.int32)
        out[list(self.attn_after)] = np.arange(len(self.attn_after))
        return out


@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------ structure
    @property
    def n_super(self) -> int:
        c = self.cfg
        return c.n_layers // c.global_every if c.window > 0 else 0

    @property
    def n_tail(self) -> int:
        c = self.cfg
        return c.n_layers % c.global_every if c.window > 0 else 0

    @property
    def hybrid(self) -> HybridLayout:
        c = self.cfg
        if c.shared_attn_every:
            per = c.shared_attn_every
            return HybridLayout(c.n_layers, tuple(
                range(per - 1, c.n_layers - c.n_layers % per, per)),
                shared=True)
        kinds = c.layer_types()
        if not c.attn_every or kinds[0] == "attention":
            raise ValueError(f"{c.name}: a hybrid needs shared_attn_every, "
                             f"or attn_every with a Mamba2 layer first "
                             f"(attn_offset >= 1)")
        after = [kinds[:i].count("mamba") - 1
                 for i, k in enumerate(kinds) if k == "attention"]
        return HybridLayout(kinds.count("mamba"), tuple(after), shared=False)

    # ---------------------------------------------------------------- init
    def init(self, seed: int = 0) -> Params:
        params, _ = self._build(jax.random.PRNGKey(seed))
        return params

    def param_axes(self):
        """Logical-axis names mirroring the param pytree (no allocation)."""
        closure = {}

        def run():
            p, a = self._build(jax.random.PRNGKey(0))
            closure["axes"] = a
            return p

        jax.eval_shape(run)
        return closure["axes"]

    def abstract_params(self):
        return jax.eval_shape(lambda: self._build(jax.random.PRNGKey(0))[0])

    def _build(self, rng):
        c = self.cfg
        dtype = _dt(c.param_dtype)
        m = _Maker(rng, dtype)
        m.dense("embed", (c.padded_vocab, c.d_model), ("vocab", "embed"),
                in_axis=1)
        m.norm("ln_final", (c.d_model,), ("embed",))
        top = dict(m.leaves)
        top_axes = dict(m.axes)
        L = c.n_layers
        if c.kind in ("dense", "moe", "vlm") and c.window <= 0:
            mm = _Maker(m.rng, dtype)
            _attn_leaves(mm, c, (L,))
            (_moe_leaves if c.kind == "moe" else _mlp_leaves)(mm, c, (L,))
            top["layers"], top_axes["layers"] = mm.leaves, mm.axes
        elif c.window > 0:  # gemma-style pattern
            ns, nt, per = self.n_super, self.n_tail, c.global_every
            mm = _Maker(m.rng, dtype)
            _attn_leaves(mm, c, (ns, per - 1))
            _mlp_leaves(mm, c, (ns, per - 1))
            top["local"], top_axes["local"] = mm.leaves, mm.axes
            mm = _Maker(mm.rng, dtype)
            _attn_leaves(mm, c, (ns,))
            _mlp_leaves(mm, c, (ns,))
            top["global"], top_axes["global"] = mm.leaves, mm.axes
            if nt:
                mm = _Maker(mm.rng, dtype)
                _attn_leaves(mm, c, (nt,))
                _mlp_leaves(mm, c, (nt,))
                top["tail"], top_axes["tail"] = mm.leaves, mm.axes
        elif c.kind == "ssm":
            mm = _Maker(m.rng, dtype)
            _mamba_leaves(mm, c, (L,), with_mlp=False)
            top["layers"], top_axes["layers"] = mm.leaves, mm.axes
        elif c.kind == "hybrid":
            hy = self.hybrid
            mm = _Maker(m.rng, dtype)
            _mamba_leaves(mm, c, (hy.n_mamba,), with_mlp=not hy.shared)
            top["mamba"], top_axes["mamba"] = mm.leaves, mm.axes
            mm = _Maker(mm.rng, dtype)
            stack = () if hy.shared else (len(hy.attn_after),)
            _attn_leaves(mm, c, stack)
            _mlp_leaves(mm, c, stack)
            key = "shared_attn" if hy.shared else "attn"
            top[key], top_axes[key] = mm.leaves, mm.axes
        elif c.kind in ("encdec", "audio"):
            mm = _Maker(m.rng, dtype)
            _attn_leaves(mm, c, (c.n_enc_layers,))
            _mlp_leaves(mm, c, (c.n_enc_layers,))
            top["enc"], top_axes["enc"] = mm.leaves, mm.axes
            mm = _Maker(mm.rng, dtype)
            _attn_leaves(mm, c, (L,))
            _attn_leaves(mm, c, (L,), cross=True)
            _mlp_leaves(mm, c, (L,))
            top["dec"], top_axes["dec"] = mm.leaves, mm.axes
            top["ln_enc_final"] = jnp.zeros((c.d_model,), dtype)
            top_axes["ln_enc_final"] = ("embed",)
        else:
            raise ValueError(f"unknown kind {c.kind}")
        return top, top_axes

    # ------------------------------------------------------ shared pieces
    @property
    def _attn_scale(self):
        return self.cfg.attention_multiplier or None

    def _residual(self, x, y):
        """``x + y``, the branch ``y`` scaled by the residual multiplier."""
        m = self.cfg.residual_multiplier
        return x + (y if m == 1.0 else y * m)

    def _embed(self, params, tokens):
        c = self.cfg
        x = embedloss.embed_in(params["embed"], tokens, _dt(c.compute_dtype))
        m = c.embedding_multiplier
        return x if m == 1.0 else x * m

    def _head_in(self, params, x):
        """The final norm, with the logit scale folded in: the tied head
        then gives the published logits (``logits_scaling`` divides
        them)."""
        c = self.cfg
        x = rms_norm(x, params["ln_final"], c.norm_eps)
        return x if c.logits_scaling == 1.0 else x * (1.0 / c.logits_scaling)

    def _attn_train(self, p, x, sin, cos, window, prefix=""):
        c = self.cfg
        b, s, d = x.shape
        h = rms_norm(x, p[prefix + "ln_attn"], c.norm_eps)
        q = (h @ p[prefix + "wq"]).reshape(b, s, c.n_heads, c.hd)
        k = (h @ p[prefix + "wk"]).reshape(b, s, c.n_kv_heads, c.hd)
        v = (h @ p[prefix + "wv"]).reshape(b, s, c.n_kv_heads, c.hd)
        if c.rope:
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
        o = context_attention(q, k, v, causal=True, window=window,
                              scale=self._attn_scale)
        o = o.reshape(b, s, -1) @ p[prefix + "wo"]
        return self._residual(x, shard(o, "batch", "seq", None)), (k, v)

    def _attn_nocausal(self, p, x, prefix="", kv_from=None):
        """Encoder self-attention / decoder cross-attention (no RoPE)."""
        c = self.cfg
        b, s, d = x.shape
        h = rms_norm(x, p[prefix + "ln_attn"], c.norm_eps)
        src = h if kv_from is None else kv_from
        q = (h @ p[prefix + "wq"]).reshape(b, s, c.n_heads, c.hd)
        k = (src @ p[prefix + "wk"]).reshape(b, src.shape[1], c.n_kv_heads, c.hd)
        v = (src @ p[prefix + "wv"]).reshape(b, src.shape[1], c.n_kv_heads, c.hd)
        o = context_attention(q, k, v, causal=False, window=0,
                              scale=self._attn_scale)
        o = o.reshape(b, s, -1) @ p[prefix + "wo"]
        return self._residual(x, shard(o, "batch", "seq", None)), (k, v)

    def _ffn(self, p, x):
        c = self.cfg
        h = rms_norm(x, p["ln_mlp"], c.norm_eps)
        if c.kind == "moe" and "router" in p:
            y = moe_apply(h, {"router": p["router"], "w_gate": p["moe_gate"],
                              "w_up": p["moe_up"], "w_down": p["moe_down"]},
                          c.moe)
            if c.moe.dense_residual:
                y = y + self._dense_mlp(p, h)
        else:
            y = self._dense_mlp(p, h)
        return self._residual(x, shard(y, "batch", "seq", None))

    def _dense_mlp(self, p, h):
        hh = jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])
        hh = shard(hh, "batch", "seq", "ff")
        return hh @ p["w_down"]

    def _maybe_remat(self, f):
        return jax.checkpoint(f) if self.cfg.remat else f

    # ------------------------------------------------------------- forward
    def forward(self, params: Params, batch: dict,
                collect: bool = False):
        """Full-sequence forward -> final hidden states (B, S, D).

        With ``collect=True`` also returns the per-layer cache material
        (KV stacks / SSM states) harvested from the scan outputs."""
        c = self.cfg
        cdt = _dt(c.compute_dtype)
        if c.kind in ("encdec", "audio"):
            return self._forward_encdec(params, batch, collect)
        tokens = batch["tokens"]
        x = self._embed(params, tokens)
        if c.kind == "vlm" and "patches" in batch:
            patches = batch["patches"].astype(cdt)
            x = jnp.concatenate([patches, x[:, patches.shape[1]:]], axis=1)
        x = shard(x, "batch", "seq", None)
        s = x.shape[1]
        sin, cos = rope_table(jnp.arange(s), c.hd, c.rope_theta)
        col: dict[str, Any] = {}

        if c.kind == "ssm":
            def body(xx, p):
                h = rms_norm(xx, p["ln_ssm"], c.norm_eps)
                y, st = mamba_block(p, h, c.ssm)
                return xx + shard(y, "batch", "seq", None), \
                    st if collect else None
            x, ys = jax.lax.scan(self._maybe_remat(body), x, params["layers"])
            if collect:
                col["conv"], col["state"] = ys
        elif c.kind == "hybrid":
            x, col = self._forward_hybrid(params, x, sin, cos, collect)
        elif c.window > 0:
            x, col = self._forward_windowed(params, x, sin, cos, collect)
        else:
            def body(xx, p):
                xx, kv = self._attn_train(p, xx, sin, cos, window=0)
                xx = self._ffn(p, xx)
                return xx, kv if collect else None
            x, ys = jax.lax.scan(self._maybe_remat(body), x, params["layers"])
            if collect:
                col["k"], col["v"] = ys
        out = self._head_in(params, x)
        return (out, col) if collect else out

    def _forward_windowed(self, params, x, sin, cos, collect=False):
        c = self.cfg

        def local_body(xx, p):
            xx, kv = self._attn_train(p, xx, sin, cos, window=c.window)
            xx = self._ffn(p, xx)
            return xx, kv if collect else None

        def super_body(xx, p):
            xx, kvl = jax.lax.scan(self._maybe_remat(local_body), xx,
                                   p["local"])
            xx, kvg = self._attn_train(p["global"], xx, sin, cos, window=0)
            xx = self._ffn(p["global"], xx)
            return xx, (kvl, kvg) if collect else None

        col: dict[str, Any] = {}
        stacked = {"local": params["local"], "global": params["global"]}
        x, ys = jax.lax.scan(self._maybe_remat(super_body), x, stacked)
        if collect:
            (col["k_local"], col["v_local"]), (col["k_global"],
                                               col["v_global"]) = ys
        if self.n_tail:
            x, ys = jax.lax.scan(self._maybe_remat(local_body), x,
                                 params["tail"])
            if collect:
                col["k_tail"], col["v_tail"] = ys
        return x, col

    def _forward_hybrid(self, params, x, sin, cos, collect=False):
        """The Mamba2 stack in runs, each run one scan, with the attention
        applications between them."""
        c = self.cfg
        hy = self.hybrid

        def mamba_body(xx, p):
            h = rms_norm(xx, p["ln_ssm"], c.norm_eps)
            y, st = mamba_block(p, h, c.ssm)
            xx = self._residual(xx, shard(y, "batch", "seq", None))
            if not hy.shared:
                xx = self._ffn(p, xx)
            return xx, st if collect else None

        ends = hy.attn_after + (hy.n_mamba - 1,)
        states, kvs = [], []
        for k, (lo, hi) in enumerate(zip((-1,) + hy.attn_after, ends)):
            if hi > lo:
                run = jax.tree.map(lambda a: a[lo + 1:hi + 1], params["mamba"])
                x, st = jax.lax.scan(self._maybe_remat(mamba_body), x, run)
                states.append(st)
            if k < len(hy.attn_after):
                a = params["shared_attn"] if hy.shared else jax.tree.map(
                    lambda w: w[k], params["attn"])
                x, kv = self._attn_train(a, x, sin, cos, window=0)
                x = self._ffn(a, x)
                kvs.append(kv)
        col: dict[str, Any] = {}
        if collect:
            col["conv"], col["state"] = (
                jnp.concatenate(leaves) for leaves in zip(*states))
            col["k"], col["v"] = (jnp.stack(leaves) for leaves in zip(*kvs))
        return x, col

    def _forward_encdec(self, params, batch, collect=False):
        c = self.cfg
        cdt = _dt(c.compute_dtype)
        frames = batch["frames"].astype(cdt)          # (B, enc_len, D) stub
        enc_pos = _sinusoid(frames.shape[1], c.d_model).astype(cdt)
        h = shard(frames + enc_pos[None], "batch", None, None)

        def enc_body(xx, p):
            xx, _ = self._attn_nocausal(p, xx)
            xx = self._ffn(p, xx)
            return xx, None

        h, _ = jax.lax.scan(self._maybe_remat(enc_body), h, params["enc"])
        h = rms_norm(h, params["ln_enc_final"], c.norm_eps)

        tokens = batch["tokens"]
        x = shard(self._embed(params, tokens), "batch", "seq", None)
        s = x.shape[1]
        sin, cos = rope_table(jnp.arange(s), c.hd, c.rope_theta)

        def dec_body(xx, p):
            xx, kvs = self._attn_train(p, xx, sin, cos, window=0)
            xx, kvc = self._attn_nocausal(p, xx, prefix="c", kv_from=h)
            xx = self._ffn(p, xx)
            return xx, (kvs, kvc) if collect else None

        x, ys = jax.lax.scan(self._maybe_remat(dec_body), x, params["dec"])
        out = self._head_in(params, x)
        if collect:
            col = {}
            (col["k_self"], col["v_self"]), (col["k_cross"],
                                             col["v_cross"]) = ys
            return out, col
        return out

    # ---------------------------------------------------------------- loss
    def loss(self, params: Params, batch: dict) -> jax.Array:
        x = self.forward(params, batch)
        return embedloss.lm_loss(x, params["embed"], batch["labels"],
                                  valid_vocab=self.cfg.vocab)

    # ================================================================ decode
    def encode(self, params: Params, frames: jax.Array) -> jax.Array:
        """Encoder-only pass (whisper): frames (B, T, D) -> enc states."""
        c = self.cfg
        cdt = _dt(c.compute_dtype)
        enc_pos = _sinusoid(frames.shape[1], c.d_model).astype(cdt)
        h = shard(frames.astype(cdt) + enc_pos[None], "batch", None, None)

        def enc_body(xx, p):
            xx, _ = self._attn_nocausal(p, xx)
            xx = self._ffn(p, xx)
            return xx, None

        h, _ = jax.lax.scan(self._maybe_remat(enc_body), h, params["enc"])
        return rms_norm(h, params["ln_enc_final"], c.norm_eps)

    def cross_kv(self, params: Params, enc_out: jax.Array):
        """Per-decoder-layer cross-attention K/V from encoder states, each
        (L, B, T, Hkv·hd) as the decode cache holds them."""
        k = jnp.einsum("btd,lde->lbte", enc_out, params["dec"]["cwk"])
        v = jnp.einsum("btd,lde->lbte", enc_out, params["dec"]["cwv"])
        return k, v

    def init_cache(self, batch_size: int, seq_len: int, abstract: bool = False,
                   params: Params | None = None, batch: dict | None = None):
        """Zeroed (or abstract) decode cache for a max context of seq_len.

        A KV leaf is (..., B, S, Hkv·hd): each position's heads side by
        side, so the device's default layout tiles it unpadded and the
        decode step reads and writes it in place (see
        :func:`~repro.models.attention.decode_attention_local`).

        For encoder-decoder models, pass ``params`` and a ``batch`` with
        'frames' to populate the cross-attention K/V from the encoder."""
        c = self.cfg
        cdt = _dt(c.compute_dtype)
        make = (lambda sh, dt=cdt: jax.ShapeDtypeStruct(sh, dt)) if abstract \
            else (lambda sh, dt=cdt: jnp.zeros(sh, dt))
        b = batch_size
        kvd = c.n_kv_heads * c.hd
        kvshape = lambda n, s: (n, b, s, kvd)  # noqa: E731
        # per-slot positions: each batch lane advances independently, so a
        # serving engine can admit a request mid-run by resetting one lane
        cache: dict[str, Any] = {"pos": make((b,), jnp.int32)}
        if c.kind in ("dense", "moe", "vlm") and c.window <= 0:
            cache["k"] = make(kvshape(c.n_layers, seq_len))
            cache["v"] = make(kvshape(c.n_layers, seq_len))
        elif c.window > 0:
            ns, nt, per = self.n_super, self.n_tail, c.global_every
            w = min(c.window, seq_len)
            cache["k_local"] = make((ns, per - 1, b, w, kvd))
            cache["v_local"] = make((ns, per - 1, b, w, kvd))
            cache["k_global"] = make(kvshape(ns, seq_len))
            cache["v_global"] = make(kvshape(ns, seq_len))
            if nt:
                cache["k_tail"] = make(kvshape(nt, w))
                cache["v_tail"] = make(kvshape(nt, w))
        elif c.kind == "ssm":
            s = c.ssm
            di, n = s.d_inner(c.d_model), s.d_state
            cache["conv"] = make((c.n_layers, b, s.conv_width - 1, di + 2 * n))
            cache["state"] = make(
                (c.n_layers, b, s.n_heads(c.d_model), s.head_dim, n),
                jnp.float32)
        elif c.kind == "hybrid":
            # the Mamba2 layers' conv windows and float32 states beside the
            # attention applications' KV stacks
            s, hy = c.ssm, self.hybrid
            di, n = s.d_inner(c.d_model), s.d_state
            cache["conv"] = make((hy.n_mamba, b, s.conv_width - 1, di + 2 * n))
            cache["state"] = make(
                (hy.n_mamba, b, s.n_heads(c.d_model), s.head_dim, n),
                jnp.float32)
            cache["k"] = make(kvshape(len(hy.attn_after), seq_len))
            cache["v"] = make(kvshape(len(hy.attn_after), seq_len))
        elif c.kind in ("encdec", "audio"):
            cache["k_self"] = make(kvshape(c.n_layers, seq_len))
            cache["v_self"] = make(kvshape(c.n_layers, seq_len))
            if params is not None and batch is not None and not abstract:
                enc_out = self.encode(params, batch["frames"])
                kc, vc = self.cross_kv(params, enc_out)
                cache["k_cross"] = kc.astype(cdt)
                cache["v_cross"] = vc.astype(cdt)
                return cache
            cache["k_cross"] = make(kvshape(c.n_layers, c.enc_len))
            cache["v_cross"] = make(kvshape(c.n_layers, c.enc_len))
        return cache

    def cache_axes(self):
        """Logical axes for the cache pytree (kv seq axis sharded)."""
        c = self.cfg
        ax: dict[str, Any] = {"pos": ("batch",)}
        kv = (None, "batch", "kv_seq", None)
        if c.kind in ("dense", "moe", "vlm") and c.window <= 0:
            ax["k"] = kv
            ax["v"] = kv
        elif c.window > 0:
            ax["k_local"] = (None, None, "batch", "kv_seq", None)
            ax["v_local"] = (None, None, "batch", "kv_seq", None)
            ax["k_global"] = kv
            ax["v_global"] = kv
            if self.n_tail:
                ax["k_tail"] = kv
                ax["v_tail"] = kv
        elif c.kind in ("ssm", "hybrid"):
            ax["conv"] = (None, "batch", None, "ff")
            ax["state"] = (None, "batch", "q_heads", None, None)
            if c.kind == "hybrid":
                ax["k"] = kv
                ax["v"] = kv
        elif c.kind in ("encdec", "audio"):
            ax["k_self"] = kv
            ax["v_self"] = kv
            ax["k_cross"] = kv
            ax["v_cross"] = kv
        return ax

    def reset_cache_lane(self, cache, slot):
        """Zero one batch lane of a decode cache (``pos[slot] = 0`` and
        every leaf's ``slot`` row along its batch axis).

        The result is exactly what :meth:`init_cache` would have produced
        for that lane, so a serving engine admitting a new request mid-run
        resets only the freed slot while the other lanes keep decoding —
        attention masks already hide entries past each lane's own ``pos``,
        but SSM conv/state leaves carry history unconditionally, so the
        wipe must be unconditional too. ``slot`` may be a traced int32
        (the helper is jit-friendly; donate the cache for in-place
        updates)."""
        axes = self.cache_axes()
        new = {}
        for key, val in cache.items():
            ax = axes.get(key)
            bi = ax.index("batch") if ax and "batch" in ax else 0
            idx = (slice(None),) * bi + (slot,)
            new[key] = val.at[idx].set(jnp.zeros((), val.dtype))
        return new

    def _attn_decode(self, p, x, cache_kv, pos, *, layer=None, rolling=False,
                     prefix="", cross=False):
        """Attention of one decode token: x (B, 1, D); cache_kv = (k, v),
        each (B, S, Hkv·hd), or with ``layer`` the whole stacks
        (L, B, S, Hkv·hd) carried through the layer scan, read and written
        at ``[layer]`` in place: a stack the scan took in as per-layer
        slices and stacked back out would be copied whole every step.

        The new token's K/V are written at each lane's own position
        (``pos % S`` for a rolling window). Returns (x', (k', v')). For
        cross attention the cache is read-only."""
        c = self.cfg
        b = x.shape[0]
        k_cache, v_cache = cache_kv
        at = () if layer is None else (layer,)
        s_len = k_cache.shape[-2]
        with jax.named_scope("attn"):
            h = rms_norm(x, p[prefix + "ln_attn"], c.norm_eps)
            q = (h @ p[prefix + "wq"]).reshape(b, 1, c.n_heads, c.hd)
            if not cross:
                k = (h @ p[prefix + "wk"]).reshape(b, 1, c.n_kv_heads, c.hd)
                v = h @ p[prefix + "wv"]
                # pos is per-slot (B,): each lane rotates and writes at its
                # own position, so mid-run admissions decode exactly as if
                # solo
                pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
                if c.rope:
                    sin, cos = rope_table(pos_b[:, None], c.hd, c.rope_theta)
                    q = apply_rope(q, sin, cos)
                    k = apply_rope(k, sin, cos)
                k = k.reshape(b, 1, -1)
        if not cross:
            with jax.named_scope("kv_write"):
                if rolling:
                    slot = pos_b % s_len
                else:
                    slot = jnp.minimum(pos_b, s_len - 1)
                idx = at + (jnp.arange(b), slot)
                k_cache = k_cache.at[idx].set(k[:, 0].astype(k_cache.dtype))
                v_cache = v_cache.at[idx].set(v[:, 0].astype(v_cache.dtype))
            att_pos = pos_b
        else:
            att_pos = jnp.int32(s_len - 1)  # attend to all enc kv
        with jax.named_scope("attn"):
            o = decode_attention(q[:, 0], k_cache[at], v_cache[at],
                                 pos=att_pos, scale=self._attn_scale)
            o = o.reshape(b, 1, -1) @ p[prefix + "wo"]
            return self._residual(x, o), (k_cache, v_cache)

    def _ssm_decode(self, p, x, conv_state, layer):
        """Mamba2 of one decode token: x (B, 1, D); conv_state = (conv,
        state), the whole stacks (L, B, W-1, C) and float32 (L, B, H, P, N)
        carried through the layer scan, read at ``[layer]`` and written
        back there in place: stacks the scan took in as per-layer slices
        and stacked back out would be copied whole every step. Returns
        (x', (conv', state'))."""
        c = self.cfg
        conv, state = conv_state
        with jax.named_scope("ssm"):
            h = rms_norm(x, p["ln_ssm"], c.norm_eps)
            y, (cv, st) = mamba_block(p, h, c.ssm, conv_cache=conv[layer],
                                      ssd_state=state[layer])
            x = self._residual(x, y)
        with jax.named_scope("state_write"):
            conv = jax.lax.dynamic_update_index_in_dim(
                conv, cv.astype(conv.dtype), layer, 0)
            state = jax.lax.dynamic_update_index_in_dim(state, st, layer, 0)
        return x, (conv, state)

    def decode_step(self, params: Params, cache, tokens: jax.Array):
        """tokens (B,) int32 -> (next_tokens (B,), cache').

        Named scopes mark the planner's tasks in the HLO metadata, where a
        profiler shows each op's scope path; they leave the compiled code
        as it is: ``embed``; ``layer`` (a scan body) holding ``attn``,
        ``kv_write`` (the cache writes) and ``ffn``, or ``ssm`` and
        ``state_write`` (the conv window and state writes; then ``ffn``
        where the layer has its own MLP); ``head`` (final norm and
        greedy pick)."""
        c = self.cfg
        pos = cache["pos"]
        with jax.named_scope("embed"):
            x = shard(self._embed(params, tokens[:, None]), "batch", None,
                      None)
        newc = dict(cache)

        if c.kind in ("dense", "moe", "vlm") and c.window <= 0:
            def body(carry, xs):
                xx, kv = carry
                p, i = xs
                with jax.named_scope("layer"):
                    xx, kv = self._attn_decode(p, xx, kv, pos, layer=i)
                    with jax.named_scope("ffn"):
                        xx = self._ffn(p, xx)
                return (xx, kv), None
            (x, (newc["k"], newc["v"])), _ = jax.lax.scan(
                body, (x, (cache["k"], cache["v"])),
                (params["layers"], jnp.arange(c.n_layers)))
        elif c.window > 0:
            x = self._decode_windowed(params, x, cache, newc, pos)
        elif c.kind == "ssm":
            def body(carry, xs):
                xx, cs = carry
                p, i = xs
                with jax.named_scope("layer"):
                    xx, cs = self._ssm_decode(p, xx, cs, i)
                return (xx, cs), None
            (x, (newc["conv"], newc["state"])), _ = jax.lax.scan(
                body, (x, (cache["conv"], cache["state"])),
                (params["layers"], jnp.arange(c.n_layers)))
        elif c.kind == "hybrid":
            x = self._decode_hybrid(params, x, cache, newc, pos)
        elif c.kind in ("encdec", "audio"):
            def body(carry, xs):
                xx, kv = carry
                p, kc, vc, i = xs
                with jax.named_scope("layer"):
                    xx, kv = self._attn_decode(p, xx, kv, pos, layer=i)
                    xx, _ = self._attn_decode(p, xx, (kc, vc), pos,
                                              prefix="c", cross=True)
                    with jax.named_scope("ffn"):
                        xx = self._ffn(p, xx)
                return (xx, kv), None
            (x, (newc["k_self"], newc["v_self"])), _ = jax.lax.scan(
                body, (x, (cache["k_self"], cache["v_self"])),
                (params["dec"], cache["k_cross"], cache["v_cross"],
                 jnp.arange(c.n_layers)))
        with jax.named_scope("head"):
            x = self._head_in(params, x)
            nxt = embedloss.greedy(x[:, 0], params["embed"],
                                   valid_vocab=self.cfg.vocab)
        newc["pos"] = pos + 1
        return nxt, newc

    def _decode_windowed(self, params, x, cache, newc, pos):
        c = self.cfg

        def local_body(xx, xs):
            p, kc, vc = xs
            with jax.named_scope("layer"):
                xx, (kc, vc) = self._attn_decode(p, xx, (kc, vc), pos,
                                                 rolling=True)
                with jax.named_scope("ffn"):
                    xx = self._ffn(p, xx)
            return xx, (kc, vc)

        def super_body(carry, xs):
            xx, kvg = carry
            p, kl, vl, i = xs
            xx, (kl, vl) = jax.lax.scan(local_body, xx, (p["local"], kl, vl))
            with jax.named_scope("layer"):
                xx, kvg = self._attn_decode(p["global"], xx, kvg, pos,
                                            layer=i)
                with jax.named_scope("ffn"):
                    xx = self._ffn(p["global"], xx)
            return (xx, kvg), (kl, vl)

        stacked = {"local": params["local"], "global": params["global"]}
        (x, (newc["k_global"], newc["v_global"])), (
            newc["k_local"], newc["v_local"]) = jax.lax.scan(
            super_body, (x, (cache["k_global"], cache["v_global"])),
            (stacked, cache["k_local"], cache["v_local"],
             jnp.arange(self.n_super)))
        if self.n_tail:
            x, (newc["k_tail"], newc["v_tail"]) = jax.lax.scan(
                local_body, x, (params["tail"], cache["k_tail"],
                                cache["v_tail"]))
        return x

    def _decode_hybrid(self, params, x, cache, newc, pos):
        """One token through a hybrid: one scan over the Mamba2 stack, whose
        conv windows and states ride the scan's carry and are updated in
        place at the layer's index (see :meth:`_ssm_decode`); after a
        Mamba2 layer that an attention application follows, a ``lax.cond``
        runs it. The KV stacks ride the carry too and take the new token in
        place at the application's index (see :meth:`_attn_decode`)."""
        hy = self.hybrid

        def attend(xx, kv, k):
            p = params["shared_attn"] if hy.shared else jax.tree.map(
                lambda w: w[k], params["attn"])
            with jax.named_scope("layer"):
                xx, kv = self._attn_decode(p, xx, kv, pos, layer=k)
                with jax.named_scope("ffn"):
                    xx = self._ffn(p, xx)
            return xx, kv

        def body(carry, xs):
            xx, kv, cs = carry
            p, i, k = xs
            with jax.named_scope("layer"):
                xx, cs = self._ssm_decode(p, xx, cs, i)
                if not hy.shared:
                    with jax.named_scope("ffn"):
                        xx = self._ffn(p, xx)
            xx, kv = jax.lax.cond(k >= 0, attend, lambda xx, kv, k: (xx, kv),
                                  xx, kv, k)
            return (xx, kv, cs), None

        init = (x, (cache["k"], cache["v"]), (cache["conv"], cache["state"]))
        xs = (params["mamba"], jnp.arange(hy.n_mamba),
              jnp.asarray(hy.attn_index()))
        (x, (newc["k"], newc["v"]), (newc["conv"], newc["state"])), _ = \
            jax.lax.scan(body, init, xs)
        return x

    # -------------------------------------------------------------- prefill
    def prefill(self, params: Params, batch: dict, cache_len: int):
        """Full-sequence forward building a decode cache from the scan
        outputs. Returns (cache, last_hidden (B, D))."""
        c = self.cfg
        if c.kind in ("encdec", "audio"):
            tokens = batch["tokens"]
        else:
            tokens = batch["tokens"]
        b, s = tokens.shape
        x, col = self.forward(params, batch, collect=True)
        cache = self.init_cache(b, cache_len)
        cache["pos"] = jnp.full((b,), s, jnp.int32)

        def place_full(dst, src):
            # src (..., B, S, Hkv·hd) -> write into dst (..., B, Smax, ...)
            return jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), 0, axis=src.ndim - 2)

        def place_rolling(dst, src, window):
            # keep the last `window` positions arranged so slot = pos % window
            if s <= window:
                return jax.lax.dynamic_update_slice_in_dim(
                    dst, src.astype(dst.dtype), 0, axis=src.ndim - 2)
            last = jax.lax.slice_in_dim(src, s - window, s, axis=src.ndim - 2)
            return jnp.roll(last, s % window, axis=src.ndim - 2).astype(
                dst.dtype)

        for key, src in col.items():
            if key.startswith(("conv", "state")):
                cache[key] = src.astype(cache[key].dtype)
                continue
            # the forward's (..., B, S, Hkv, hd), heads merged
            src = src.reshape(src.shape[:-2] + (-1,))
            if key in ("k_local", "v_local", "k_tail", "v_tail"):
                w = cache[key].shape[-2]
                cache[key] = place_rolling(cache[key], src, w)
            else:
                cache[key] = place_full(cache[key], src)
        return cache, x[:, -1]


def _sinusoid(n: int, d: int) -> jax.Array:
    pos = np.arange(n)[:, None]
    i = np.arange(d // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * i / d)
    out = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(out, jnp.float32)
