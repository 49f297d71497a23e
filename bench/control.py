"""Readings that set a cell's ``logit_gap`` limit: the program's widest
gap on many seeds, and the fp8 control's on some of them.

    python3 bench/control.py --config stablelm-3b --mixes chat batch \
        --seeds 11 12 13 --control-seeds 11 12 --seconds 20 \
        --out chiprun_out/control.jsonl

Each seed serves the cell's own traffic at its own slots x positions
through ``bench/run.py``'s run (a short window, as ``--seconds`` says) and
compares the sample as a run does. On the control seeds the same positions
also go through the reference in fp8 (``harness/numerics.py``), whose
first choices are read under the float32 reference, and that reading takes
the program's place in the run's ``logit_gap`` check: the run's own
comparison with its limit then decides ``correct``, which has to come out
false. One JSON line per cell and seed goes to ``--out`` and to standard
output, with the program's reading as ``served``. Needs the chip, as a run
does; the benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as bench_run  # noqa: E402
from bench.harness import check, spec  # noqa: E402


def checker(control: bool, reading: dict):
    """``bench/run.py``'s check, with the fp8 control's gap in the place
    of the program's where ``control``; fills ``reading`` with the
    program's gap (``served``) and the control's."""
    def check_run(cell, log, seed, model, arch):
        out = bench_run.correctness(cell, log, seed, model, arch)
        reading["served"] = out["logit_gap"]["value"]
        if control:
            picked = check.sample(log, seed)
            params = bench_run.make_params(cell, seed, model)
            reading.update(check.logit_gaps(
                arch, cell.config["model"], params, picked,
                cell.config["serve"]["max_len"], control=True))
            out["logit_gap"]["value"] = reading["control"]
        return out
    return check_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mixes", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    bench_run.enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    for seed in args.seeds:
        for mix in args.mixes:
            cell = spec.load_cell(f"{args.config}.{mix}")
            reading = {}
            t0 = time.perf_counter()
            res = bench_run.run(cell, seed, args.seconds, False, t0,
                                checker=checker(seed in args.control_seeds,
                                                reading))
            line = {"cell": cell.name, "seed": seed,
                    "served": reading["served"],
                    "control": reading.get("control"),
                    "tokens": reading.get("tokens"),
                    "correct": res["correct"], "metrics": res["metrics"],
                    "checks": res["checks"],
                    "wall_s": time.perf_counter() - t0}
            print(json.dumps(line), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
