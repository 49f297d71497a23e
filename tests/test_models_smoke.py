"""Per-architecture smoke tests (reduced configs): one forward/train step and
one decode step on CPU, asserting shapes and finiteness; plus exact
prefill/decode consistency for each family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import MoEConfig, get_smoke_config, list_archs
from repro.models.transformer import Model

pytestmark = pytest.mark.slow

RNG = np.random.default_rng(0)


def make_batch(cfg, B=2, S=32):
    b = {"tokens": jnp.asarray(RNG.integers(0, cfg.vocab, (B, S)), jnp.int32),
         "labels": jnp.asarray(RNG.integers(0, cfg.vocab, (B, S)), jnp.int32)}
    if cfg.kind == "vlm":
        b["patches"] = jnp.asarray(
            RNG.normal(size=(B, cfg.n_patches, cfg.d_model)), jnp.float32)
    if cfg.kind in ("audio", "encdec"):
        b["frames"] = jnp.asarray(
            RNG.normal(size=(B, cfg.enc_len, cfg.d_model)), jnp.float32)
    return b


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_forward_and_grad(arch):
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    params = model.init(0)
    batch = make_batch(cfg)
    x = model.forward(params, batch)
    assert x.shape == (2, 32, cfg.d_model)
    assert bool(jnp.isfinite(x).all())
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert bool(jnp.isfinite(loss))
    # loss should be near ln(padded_vocab) at init
    assert 0.5 * np.log(cfg.padded_vocab) < float(loss) \
        < 2.0 * np.log(cfg.padded_vocab)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_decode_step(arch):
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    params = model.init(0)
    cache = model.init_cache(2, 64)
    step = jax.jit(model.decode_step)
    tok = jnp.zeros((2,), jnp.int32)
    for _ in range(3):
        tok, cache = step(params, cache, tok)
    assert tok.shape == (2,)
    # pos is per-slot: every lane advanced together here
    assert cache["pos"].shape == (2,)
    assert np.all(np.asarray(cache["pos"]) == 3)
    assert bool((tok >= 0).all()) and bool((tok < cfg.vocab).all())


@pytest.mark.parametrize("arch", ["stablelm-3b", "gemma3-1b", "mamba2-1.3b",
                                  "zamba2-7b", "granite-4.0-h-micro",
                                  "whisper-small", "arctic-480b"])
def test_decode_matches_forward(arch):
    """Streaming tokens through decode_step must reproduce the greedy token
    the full forward pass would pick at every position (exact cache check).
    """
    cfg = get_smoke_config(arch)
    if cfg.moe is not None:  # avoid capacity-drop mismatches
        cfg = dataclasses.replace(
            cfg, moe=MoEConfig(cfg.moe.n_experts, cfg.moe.top_k,
                               cfg.moe.d_ff_expert, cfg.moe.dense_residual,
                               capacity_factor=float(cfg.moe.n_experts)))
    model = Model(cfg)
    params = model.init(0)
    B, S = 2, 17
    batch = make_batch(cfg, B, S)
    from repro.models import embedloss
    x = model.forward(params, batch)
    fwd_greedy = np.stack([
        np.asarray(embedloss.greedy(x[:, t], params["embed"],
                                    valid_vocab=cfg.vocab))
        for t in range(S)], axis=1)

    cache = model.init_cache(B, 32, params=params, batch=batch)
    step = jax.jit(model.decode_step)
    toks = np.asarray(batch["tokens"])
    dec = []
    for t in range(S):
        nxt, cache = step(params, cache, jnp.asarray(toks[:, t]))
        dec.append(np.asarray(nxt))
    dec = np.stack(dec, axis=1)
    match = (dec == fwd_greedy).mean()
    assert match == 1.0, f"decode/forward greedy mismatch: {match:.2%}"


def test_param_count_matches_init():
    for arch in list_archs():
        cfg = get_smoke_config(arch)
        model = Model(cfg)
        analytic, _ = cfg.param_count()
        actual = sum(int(np.prod(s.shape))
                     for s in jax.tree.leaves(model.abstract_params()))
        # embedding padding is the only allowed discrepancy
        pad = (cfg.padded_vocab - cfg.vocab) * cfg.d_model
        assert actual == analytic + pad, arch
