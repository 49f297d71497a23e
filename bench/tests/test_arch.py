import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import spec
from bench.tests.conftest import REGISTRY, smoke_model


def test_dense_step_cost_by_hand():
    # L=2, d=64, 4 heads of 16, d_ff=128, vocab 256, float32
    # per layer 64*64 (q) + 2*64*64 (k, v) + 64*64 (o) + 3*64*128 = 40960
    # matmul params 2*40960 + 256*64 (head) = 98304
    flops, bytes_ = spec.arch("dense").step_cost(
        smoke_model("dense"), weight_bytes=1000, n_active=3, ctx_sum=10)
    assert flops == 2 * 98304 * 3 + 4 * 2 * 64 * 10
    # keys and values: 2 layers x 2 x 64 x 4 bytes per position, 10 read
    # and 3 written
    assert bytes_ == 1000 + 2 * 2 * 64 * 4 * (10 + 3)


def test_ssm_step_cost_by_hand():
    # L=3, d=64, d_inner 128, 8 heads of 16, d_state 16, conv 4, vocab 256
    # conv channels 128 + 2*16 = 160; in_proj 64 x (2*128 + 2*16 + 8)
    # = 64 x 296, out_proj 128 x 64
    flops, bytes_ = spec.arch("ssm").step_cost(
        smoke_model("ssm"), weight_bytes=1000, n_active=5, ctx_sum=99)
    matmul = 3 * (64 * 296 + 128 * 64) + 256 * 64
    per_lane = 3 * (6 * 8 * 16 * 16 + 2 * 4 * 160)
    assert flops == (2 * matmul + per_lane) * 5
    # f32 state 8x16x16 and a 3 x 160 conv window, read and written
    lane = 3 * 2 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert bytes_ == 1000 + lane * 5


@pytest.mark.parametrize("kind", ["dense", "ssm"])
def test_weights_match_program_tree(kind):
    from repro.models.transformer import Model
    model = smoke_model(kind)
    make = jax.jit(functools.partial(spec.arch(kind).make_params, model))
    got = jax.eval_shape(make, jax.random.PRNGKey(0))
    cfg = spec.model_config({"model": model})
    want = Model(cfg).abstract_params()
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)


@pytest.mark.parametrize("kind", ["dense", "ssm"])
def test_reference_agrees_with_program_forward(kind):
    """At smoke size in float32 the reference and the program's own
    full-sequence forward give the same logits."""
    from repro.models.transformer import Model
    model = smoke_model(kind)
    arch = spec.arch(kind)
    params = jax.jit(functools.partial(arch.make_params, model))(
        jax.random.PRNGKey(3))
    prog = Model(spec.model_config({"model": model}))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 24), 0,
                              model["vocab"])
    with jax.default_matmul_precision("highest"):
        x = prog.forward(params, {"tokens": toks})
        want = x.astype(jnp.float32) @ params["embed"][:model["vocab"]].T
    h = arch.forward(model, params, toks)
    got = arch.logits(model, params, h.reshape(-1, h.shape[-1]))
    np.testing.assert_allclose(np.asarray(got).reshape(want.shape),
                               np.asarray(want), atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("name", sorted(REGISTRY.values()))
def test_config_files_are_the_registry_configs(name):
    from repro.models.config import get_config
    config = spec.load_json(spec.BENCH_DIR / "configs" / f"{name}.json")
    assert spec.model_config(config) == get_config(name)
