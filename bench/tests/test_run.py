"""``bench/run.py`` end to end at smoke size on the CPU: without a TPU it
refuses; past that check a run is correct, and a run whose timed path is
broken is not."""
import os
import subprocess
import sys
import time

import pytest

from bench import control, run as bench_run
from bench.tests.conftest import ROOT, smoke_cell


def test_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "stablelm-3b.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr


@pytest.mark.parametrize("kind,mix", [("dense", "chat"), ("ssm", "chat"),
                                      ("dense", "batch"), ("ssm", "batch")])
def test_smoke_run_is_correct(kind, mix):
    res = bench_run.run(smoke_cell(kind, mix), 2**33 + 11, 1.0, False,
                        time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["device"]["platform"] == "cpu"
    want = {"setup_s", "output_tokens_per_s"} if mix == "batch" else \
        {"setup_s", "ttft_p50_ms", "ttft_p90_ms", "itl_p99_ms"}
    assert want <= set(res["metrics"])
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0


def _broken(fault, orig):
    def decode_step(self, params, cache, tokens):
        nxt, new = orig(self, params, cache, tokens)
        if fault == "state_unchanged":
            return nxt, cache
        if fault == "token_altered":
            return (nxt + 1) % self.cfg.vocab, new
        if fault == "half_the_lanes":
            half = nxt.shape[0] // 2
            return nxt.at[half:].set(tokens[half:]), new
        raise ValueError(fault)
    return decode_step


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered",
                                   "half_the_lanes"])
@pytest.mark.parametrize("kind", ["dense", "ssm"])
def test_broken_timed_path_is_not_correct(kind, fault, monkeypatch):
    from repro.models.transformer import Model
    monkeypatch.setattr(Model, "decode_step",
                        _broken(fault, Model.decode_step))
    res = bench_run.run(smoke_cell(kind, "batch"), 5, 1.0, False,
                        time.perf_counter())
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("kind", ["dense", "ssm"])
def test_fp8_control_reads_above_the_program(kind):
    """The control (the reference in fp8, put in the program's place)
    reads a wider gap than the program in its own bfloat16, and the run's
    own comparison finds it not correct at a limit set from the program's
    readings, at smoke size on fixed seeds."""
    seeds = (0, 1, 2)
    served = []
    for seed in seeds:
        reading = {}
        res = bench_run.run(smoke_cell(kind, "chat", "bfloat16", 1e9), seed,
                            1.0, False, time.perf_counter(),
                            checker=control.checker(False, reading))
        assert res["correct"] and "control" not in reading
        served.append(reading["served"])
    limit = 2 * max(served)
    controls = []
    for seed in seeds:
        reading = {}
        res = bench_run.run(smoke_cell(kind, "chat", "bfloat16", limit), seed,
                            1.0, False, time.perf_counter(),
                            checker=control.checker(True, reading))
        assert reading["served"] <= limit
        assert res["checks"]["logit_gap"]["value"] == reading["control"]
        assert not res["correct"], (served, reading)
        controls.append(reading["control"])
    assert min(controls) >= 3 * max(served), (served, controls)
