"""Find a cell's pieces by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. Each lives in files of its
own under ``bench/``, so a later change adds a cell by adding files:

- ``configs/<config>.json``: the model as it is run, its serving shape
  (slots x positions) and its source;
- ``traffic/<mix>.json``: the mix's parameters, read by ``traffic.py``;
- ``cells/<workload>.json`` (optional): parameters of this one cell (its
  arrival rate, the limits of its output check), laid over the mix's;
- ``arch/<kind>.py``: the plain reference and the cost of one decode step
  for the configuration's architecture kind;
- ``metrics/<metric>.py``: one reader per per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, nested dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<mix>.json laid under cells/<name>.json
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def kind(self) -> str:
        return self.config["kind"]


def _reports(metric: dict, workload: str, e2e_names: set[str]) -> bool:
    cells = metric.get("workloads")
    if cells is not None:
        return workload in cells
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(workload: str, bench_json: Path | None = None) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json``, with its files read.
    Raises ``KeyError`` for a name the file does not hold."""
    spec = load_json(bench_json or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    cell_file = BENCH_DIR / "cells" / f"{workload}.json"
    if cell_file.exists():
        traffic = merged(traffic, load_json(cell_file))
    traffic["name"] = w["traffic"]
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _reports(m, workload, names)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig, SSMConfig
    kw = dict(config["model"])
    if kw.get("ssm") is not None:
        kw["ssm"] = SSMConfig(**kw["ssm"])
    return ModelConfig(**kw)


def arch(kind: str):
    """``bench/arch/<kind>.py``: reference and step cost for a kind."""
    return importlib.import_module(f"bench.arch.{kind}")


def metric_reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind`` from ``peaks.json``. A device
    that is not in the table is an error, not a default."""
    table = load_json(BENCH_DIR / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
