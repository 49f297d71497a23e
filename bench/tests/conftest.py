"""CPU tests of the benchmark at smoke size.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import dataclasses
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.harness import spec  # noqa: E402

REGISTRY = {"dense": "stablelm-3b", "ssm": "mamba2-1.3b"}
SMALL_LENS = {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 2,
              "max": 24}


def smoke_model(kind: str, dtype: str = "float32") -> dict:
    """The registry's smoke configuration of ``kind`` as a config file's
    ``model`` entry."""
    from repro.models.config import get_smoke_config
    c = get_smoke_config(REGISTRY[kind])
    model = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)}
    if model["ssm"] is not None:
        model["ssm"] = dataclasses.asdict(model["ssm"])
    model["param_dtype"] = model["compute_dtype"] = dtype
    return model


def smoke_cell(kind: str, mix: str = "chat", dtype: str = "float32",
               limit: float = 1e-3) -> spec.Cell:
    """A cell at smoke widths: 4 slots x 64 positions, short requests."""
    traffic = {"name": mix, "arrival": "poisson" if mix == "chat"
               else "backlog", "rate_per_s": 20.0, "backlog_per_slot": 64,
               "lead_s": 0.3, "prompt_len": SMALL_LENS,
               "output_len": SMALL_LENS, "limits": {"logit_gap": limit}}
    config = {"name": REGISTRY[kind], "kind": kind,
              "model": smoke_model(kind, dtype),
              "serve": {"slots": 4, "max_len": 64}}
    e2e = [{"name": n, "unit": "ms"} for n in
           ("setup_s", "ttft_p50_ms", "ttft_p90_ms", "itl_p99_ms",
            "output_tokens_per_s")]
    return spec.Cell(name=f"{REGISTRY[kind]}.{mix}", chips=1, config=config,
                     traffic=traffic, end_to_end=e2e, per_layer=[])
