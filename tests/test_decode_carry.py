"""The decode step that carries its caches through the layer scan gives
what the step that scanned them in as per-layer slices and stacked them
back out gave: the same tokens and the same caches, lane by lane, across
lanes at different positions and a lane reset mid-run. For attention the
KV cache, each position's heads side by side, against per-layer
(B, S, Hkv, hd) slices; for Mamba2 layers the conv windows and float32
states, updated in place at the layer's index."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import embedloss
from repro.models.attention import decode_attention_local
from repro.models.config import get_smoke_config
from repro.models.layers import apply_rope, rms_norm, rope_table
from repro.models.ssm import mamba_block
from repro.models.transformer import Model

SLOTS, MAX_LEN, STEPS = 3, 16, 8
# before each of these steps, the lane is reset (a new request admitted)
RESETS = {2: 1, 5: 0}


def _per_head_attention(q, k, v, pos, window=0):
    """Masked softmax attention of q (B, Hq, hd) over per-head caches
    k, v (B, S, Hkv, hd), query head h reading KV head h // G."""
    b, hq, d = q.shape
    g = hq // k.shape[2]
    k = jnp.repeat(k.astype(jnp.float32), g, axis=2)
    v = jnp.repeat(v.astype(jnp.float32), g, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), k) / math.sqrt(d)
    kv_pos = jnp.arange(k.shape[1])
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
    msk = kv_pos[None, :] <= pos_b[:, None]
    if window > 0:
        msk &= kv_pos[None, :] > pos_b[:, None] - window
    s = jnp.where(msk[:, None, :], s, -jnp.inf)
    return jnp.einsum("bhk,bkhd->bhd", jax.nn.softmax(s, axis=-1), v)


def _scanned_slice_step(model, params, cache, tokens):
    """The reference: caches (L, B, S, Hkv, hd); each layer's slice is a
    scan input, written at each lane's position and stacked back out."""
    c = model.cfg
    b = tokens.shape[0]
    pos = cache["pos"]
    x = embedloss.embed_in(params["embed"], tokens[:, None],
                           jnp.dtype(c.compute_dtype))
    sin, cos = rope_table(pos[:, None], c.hd, c.rope_theta)
    lanes, slot = jnp.arange(b), jnp.minimum(pos, MAX_LEN - 1)

    def body(xx, xs):
        p, kc, vc = xs
        h = rms_norm(xx, p["ln_attn"], c.norm_eps)
        q = apply_rope((h @ p["wq"]).reshape(b, 1, c.n_heads, c.hd), sin, cos)
        k = apply_rope((h @ p["wk"]).reshape(b, 1, c.n_kv_heads, c.hd),
                       sin, cos)
        v = (h @ p["wv"]).reshape(b, 1, c.n_kv_heads, c.hd)
        kc = kc.at[lanes, slot].set(k[:, 0].astype(kc.dtype))
        vc = vc.at[lanes, slot].set(v[:, 0].astype(vc.dtype))
        o = _per_head_attention(q[:, 0], kc, vc, pos).astype(xx.dtype)
        xx = xx + o.reshape(b, 1, -1) @ p["wo"]
        return model._ffn(p, xx), (kc, vc)

    x, (k, v) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                       cache["v"]))
    x = rms_norm(x, params["ln_final"], c.norm_eps)
    nxt = embedloss.greedy(x[:, 0], params["embed"], valid_vocab=c.vocab)
    return nxt, {"pos": pos + 1, "k": k, "v": v}


def _reset_lane(cache, lane):
    return {"pos": cache["pos"].at[lane].set(0),
            "k": cache["k"].at[:, lane].set(0),
            "v": cache["v"].at[:, lane].set(0)}


def _assert_within_one_ulp(a, b, dtype=jnp.bfloat16):
    """Equal caches of ``dtype``, or apart by at most one unit in the last
    place."""
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype == dtype
    bits = {2: np.int16, 4: np.int32}[a.dtype.itemsize]
    ia = a.view(bits).astype(np.int64)
    ib = b.view(bits).astype(np.int64)
    same_sign = (ia < 0) == (ib < 0)
    assert np.all((a == b) | (same_sign & (np.abs(ia - ib) <= 1)))


@pytest.mark.parametrize("arch", ["stablelm-3b", "arctic-480b",
                                  "internvl2-26b"])
def test_carried_cache_matches_scanned_slices(arch):
    cfg = dataclasses.replace(get_smoke_config(arch),
                              param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    assert cfg.kind in ("dense", "moe", "vlm") and cfg.window <= 0
    model = Model(cfg)
    params = model.init(0)
    # the step and the lane reset as the serving engine jits them
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    reset = jax.jit(model.reset_cache_lane, donate_argnums=(0,))
    ref_step = jax.jit(lambda p, ca, t: _scanned_slice_step(model, p, ca, t))
    ref_reset = jax.jit(_reset_lane)

    cache = model.init_cache(SLOTS, MAX_LEN)
    split = (cfg.n_layers, SLOTS, MAX_LEN, cfg.n_kv_heads, cfg.hd)
    assert cache["k"].shape == split[:3] + (cfg.n_kv_heads * cfg.hd,)
    ref = {"pos": jnp.zeros((SLOTS,), jnp.int32),
           "k": jnp.zeros(split, cache["k"].dtype),
           "v": jnp.zeros(split, cache["v"].dtype)}
    tokens = jnp.asarray([3, 17, 101], jnp.int32)
    for t in range(STEPS):
        if t in RESETS:
            cache = reset(cache, jnp.int32(RESETS[t]))
            ref = ref_reset(ref, jnp.int32(RESETS[t]))
        nxt, cache = step(params, cache, tokens)
        want, ref = ref_step(params, ref, tokens)
        np.testing.assert_array_equal(np.asarray(nxt), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(cache["pos"]),
                                      np.asarray(ref["pos"]))
        _assert_within_one_ulp(cache["k"].reshape(split), ref["k"])
        _assert_within_one_ulp(cache["v"].reshape(split), ref["v"])
        tokens = nxt
    # the lanes ended at different positions, each with its own history
    assert len(set(np.asarray(cache["pos"]).tolist())) == SLOTS
    assert np.any(np.asarray(cache["k"], np.float32) != 0)


@pytest.mark.parametrize("hq,hkv,hd,window", [(4, 4, 80, 0), (8, 2, 16, 0),
                                              (6, 3, 8, 5), (4, 1, 32, 0)])
def test_merged_head_attention_matches_per_head(hq, hkv, hd, window):
    """Decode attention over a (B, S, Hkv·hd) cache is attention over the
    same cache split per head, for MHA and grouped queries, per-lane
    positions and a sliding window."""
    b, s = 3, 12
    rng = np.random.default_rng(hq * 100 + hkv * 10 + window)
    q = jnp.asarray(rng.normal(size=(b, hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), jnp.float32)
    pos = jnp.asarray([0, 6, 11], jnp.int32)
    o, _, _ = decode_attention_local(q, k.reshape(b, s, -1),
                                     v.reshape(b, s, -1), pos=pos,
                                     window=window)
    want = _per_head_attention(q, k, v, pos, window)
    np.testing.assert_allclose(np.asarray(o).reshape(b, hq, hd),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def _scanned_state_step(model, params, cache, tokens):
    """The reference: each layer's conv window and state are scan inputs,
    updated and stacked back out as the scan's outputs; a hybrid's KV
    stacks ride the carry as the served step's do."""
    c = model.cfg
    pos = cache["pos"]
    x = model._embed(params, tokens[:, None])

    def mamba(p, xx, conv, st):
        h = rms_norm(xx, p["ln_ssm"], c.norm_eps)
        y, (conv, st) = mamba_block(p, h, c.ssm, conv_cache=conv,
                                    ssd_state=st)
        return model._residual(xx, y), (conv, st)

    out = {"pos": pos + 1}
    if c.kind == "ssm":
        x, (out["conv"], out["state"]) = jax.lax.scan(
            lambda xx, xs: mamba(xs[0], xx, *xs[1:]), x,
            (params["layers"], cache["conv"], cache["state"]))
    else:
        hy = model.hybrid

        def attend(xx, kv, k):
            p = params["shared_attn"] if hy.shared else jax.tree.map(
                lambda w: w[k], params["attn"])
            xx, kv = model._attn_decode(p, xx, kv, pos, layer=k)
            return model._ffn(p, xx), kv

        def body(carry, xs):
            xx, kv = carry
            p, conv, st, k = xs
            xx, (conv, st) = mamba(p, xx, conv, st)
            if not hy.shared:
                xx = model._ffn(p, xx)
            xx, kv = jax.lax.cond(k >= 0, attend, lambda xx, kv, k: (xx, kv),
                                  xx, kv, k)
            return (xx, kv), (conv, st)

        (x, (out["k"], out["v"])), (out["conv"], out["state"]) = \
            jax.lax.scan(body, (x, (cache["k"], cache["v"])),
                         (params["mamba"], cache["conv"], cache["state"],
                          jnp.asarray(hy.attn_index())))
    x = model._head_in(params, x)
    nxt = embedloss.greedy(x[:, 0], params["embed"], valid_vocab=c.vocab)
    return nxt, out


def _reset_state_lane(cache, lane):
    """Zero ``lane`` of every stack (its axis 1) and its position."""
    return {key: val.at[lane].set(0) if key == "pos" else
            val.at[:, lane].set(0) for key, val in cache.items()}


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "granite-4.0-h-micro",
                                  "zamba2-7b"])
def test_carried_state_matches_scanned_slices(arch):
    """Mamba2 layers whose conv windows and float32 states ride the layer
    scan's carry and are written back in place at the layer's index."""
    cfg = dataclasses.replace(get_smoke_config(arch),
                              param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    assert cfg.kind in ("ssm", "hybrid")
    model = Model(cfg)
    params = model.init(0)
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    reset = jax.jit(model.reset_cache_lane, donate_argnums=(0,))
    ref_step = jax.jit(lambda p, ca, t: _scanned_state_step(model, p, ca, t))
    ref_reset = jax.jit(_reset_state_lane)

    cache = model.init_cache(SLOTS, MAX_LEN)
    assert cache["conv"].dtype == jnp.bfloat16
    assert cache["state"].dtype == jnp.float32
    ref = model.init_cache(SLOTS, MAX_LEN)
    tokens = jnp.asarray([3, 17, 101], jnp.int32)
    for t in range(STEPS):
        if t in RESETS:
            cache = reset(cache, jnp.int32(RESETS[t]))
            ref = ref_reset(ref, jnp.int32(RESETS[t]))
        nxt, cache = step(params, cache, tokens)
        want, ref = ref_step(params, ref, tokens)
        np.testing.assert_array_equal(np.asarray(nxt), np.asarray(want))
        assert cache.keys() == ref.keys()
        np.testing.assert_array_equal(np.asarray(cache["pos"]),
                                      np.asarray(ref["pos"]))
        _assert_within_one_ulp(cache["conv"], ref["conv"])
        _assert_within_one_ulp(cache["state"], ref["state"], jnp.float32)
        for key in set(cache) - {"pos", "conv", "state"}:
            _assert_within_one_ulp(cache[key], ref[key])
        tokens = nxt
    # the lanes ended at different positions, each with its own history
    assert len(set(np.asarray(cache["pos"]).tolist())) == SLOTS
    state = np.asarray(cache["state"])
    assert np.all(np.any(state != 0, axis=(0, 2, 3, 4)))
    assert not np.array_equal(state[:, 0], state[:, 1])
