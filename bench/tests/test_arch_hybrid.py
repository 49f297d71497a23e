"""``bench/arch/hybrid.py`` (the granite form of ``kind: hybrid``) at smoke
size on the CPU: its weight tree is the program's, its step cost is
checked by hand, the configuration file is the registry's, and a whole
run through ``bench/run.py`` is correct, while a broken timed path and the
fp8 control are not.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_arch_hybrid.py
"""
import dataclasses
import functools
import time

import jax
import pytest

from bench import control, run as bench_run
from bench.harness import spec
from bench.tests.conftest import SMALL_LENS
from bench.tests.test_run import _broken

NAME = "granite-4.0-h-micro"


def smoke_model(dtype: str = "float32") -> dict:
    """The registry's smoke granite as a configuration file's ``model``:
    two periods of (Mamba2, attention, Mamba2), 4 query heads over 2 KV
    heads, every multiplier away from 1."""
    from repro.models.config import get_smoke_config
    c = get_smoke_config(NAME)
    model = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)}
    model["ssm"] = dataclasses.asdict(model["ssm"])
    model["param_dtype"] = model["compute_dtype"] = dtype
    return model


def smoke_cell(mix: str = "chat", dtype: str = "float32",
               limit: float = 1e-3) -> spec.Cell:
    """A cell at smoke widths: 4 slots x 64 positions, short requests."""
    traffic = {"name": mix, "arrival": "poisson" if mix == "chat"
               else "backlog", "rate_per_s": 20.0, "backlog_per_slot": 64,
               "lead_s": 0.3, "prompt_len": SMALL_LENS,
               "output_len": SMALL_LENS, "limits": {"logit_gap": limit}}
    config = {"name": NAME, "kind": "hybrid", "model": smoke_model(dtype),
              "serve": {"slots": 4, "max_len": 64}}
    e2e = [{"name": n, "unit": "ms"} for n in
           ("setup_s", "ttft_p50_ms", "ttft_p90_ms", "itl_p99_ms",
            "output_tokens_per_s")]
    return spec.Cell(name=f"{NAME}.{mix}", chips=1, config=config,
                     traffic=traffic, end_to_end=e2e, per_layer=[])


def test_hybrid_step_cost_by_hand():
    # 6 layers: 4 Mamba2 + 2 attention, d=64, d_ff 128, vocab 256
    # Mamba2: d_inner 128, 8 heads of 16, d_state 16, conv 4: conv channels
    # 160; in_proj 64 x (128 + 160 + 8) = 64 x 296, out_proj 128 x 64
    # attention: 4 q heads and 2 kv heads of 16: q 64 x 64, k and v 64 x 32
    # each, o 64 x 64; every layer an MLP of 3 x 64 x 128
    flops, bytes_ = spec.arch("hybrid").step_cost(
        smoke_model(), weight_bytes=1000, n_active=3, ctx_sum=10)
    mlp = 3 * 64 * 128
    matmul = 4 * (64 * 296 + 128 * 64 + mlp) \
        + 2 * (64 * 64 + 2 * 64 * 32 + 64 * 64 + mlp) + 256 * 64
    per_lane = 4 * (6 * 8 * 16 * 16 + 2 * 4 * 160)
    assert flops == (2 * matmul + per_lane) * 3 + 4 * 2 * 64 * 10
    # per lane, each Mamba2 layer's f32 state 8x16x16 and 3 x 160 conv
    # window read and written; keys and values: 2 layers x 2 x 32 x 4
    # bytes a position, 10 read and 3 written
    lane = 4 * 2 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert bytes_ == 1000 + lane * 3 + 2 * 2 * 32 * 4 * (10 + 3)


@pytest.mark.parametrize("size", ["smoke", "published"])
def test_hybrid_weights_match_program_tree(size):
    from repro.models.transformer import Model
    model = smoke_model() if size == "smoke" else spec.load_json(
        spec.BENCH_DIR / "configs" / f"{NAME}.json")["model"]
    make = jax.jit(functools.partial(spec.arch("hybrid").make_params, model))
    got = jax.eval_shape(make, jax.random.PRNGKey(0))
    want = Model(spec.model_config({"model": model})).abstract_params()
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)


def test_granite_config_file_is_the_registry_config():
    """The file's model is the registry's after a round trip through JSON,
    and its published keys state the same model."""
    from repro.models.config import get_config
    config = spec.load_json(spec.BENCH_DIR / "configs" / f"{NAME}.json")
    cfg = spec.model_config(config)
    assert cfg == get_config(NAME)
    assert config["reduced"] == []
    assert cfg.layer_types() == tuple(config["layer_types"])
    s = cfg.ssm
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab) == (
        config["num_hidden_layers"], config["hidden_size"],
        config["num_attention_heads"], config["num_key_value_heads"],
        config["shared_intermediate_size"], config["vocab_size"])
    assert (s.d_state, s.head_dim, s.expand, s.conv_width, s.chunk,
            s.n_heads(cfg.d_model)) == (
        config["mamba_d_state"], config["mamba_d_head"],
        config["mamba_expand"], config["mamba_d_conv"],
        config["mamba_chunk_size"], config["mamba_n_heads"])
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling, cfg.norm_eps) == (
        config["embedding_multiplier"], config["residual_multiplier"],
        config["attention_multiplier"], config["logits_scaling"],
        config["rms_norm_eps"])
    assert not cfg.rope and config["position_embedding_type"] == "nope"


def test_hybrid_smoke_run_is_correct():
    res = bench_run.run(smoke_cell(), 2**33 + 11, 1.0, False,
                        time.perf_counter())
    assert res["correct"], res["checks"]
    assert {"setup_s", "ttft_p50_ms", "ttft_p90_ms", "itl_p99_ms"} <= \
        set(res["metrics"])
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered",
                                   "half_the_lanes"])
def test_hybrid_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.models.transformer import Model
    monkeypatch.setattr(Model, "decode_step",
                        _broken(fault, Model.decode_step))
    res = bench_run.run(smoke_cell("batch"), 5, 1.0, False,
                        time.perf_counter())
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_hybrid_fp8_control_reads_above_the_program():
    """As ``test_run.py``'s control test: at a limit set from the
    bfloat16 program's readings, the fp8 control is not correct."""
    seeds = (0, 1, 2)
    served = []
    for seed in seeds:
        reading = {}
        res = bench_run.run(smoke_cell("chat", "bfloat16", 1e9), seed, 1.0,
                            False, time.perf_counter(),
                            checker=control.checker(False, reading))
        assert res["correct"]
        served.append(reading["served"])
    limit = 2 * max(served)
    for seed in seeds:
        reading = {}
        res = bench_run.run(smoke_cell("chat", "bfloat16", limit), seed, 1.0,
                            False, time.perf_counter(),
                            checker=control.checker(True, reading))
        assert reading["served"] <= limit
        assert not res["correct"], (served, reading)
