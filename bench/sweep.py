"""Find a chat cell's knee: the highest arrival rate the engine sustains.

    python3 bench/sweep.py --workload stablelm-3b.chat --seed 5 \
        --seconds 30 --rates backlog 0.5 0.7

``backlog`` serves the cell's request sizes as a backlog that keeps every
slot full; its completed requests per second is the capacity. Each rate
serves the cell's traffic open loop at that rate. For each point the line
gives completed requests per second, the queue at the window's open and
end (a queue that grows over the window is past the knee), and the TTFT
median and 90th percentile. The cell's rate is then fixed in
``cells/<workload>.json`` at about four fifths of the knee.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as bench_run  # noqa: E402
from bench.harness import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    bench_run.enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    base = spec.load_cell(args.workload)
    for rate in args.rates:
        over = ({"arrival": "backlog", "backlog_per_slot": 64}
                if rate == "backlog" else {"rate_per_s": float(rate)})
        cell = dataclasses.replace(
            base, traffic=spec.merged(base.traffic, over),
            end_to_end=[{"name": n, "unit": "ms"} for n in
                        ("ttft_p50_ms", "ttft_p90_ms", "itl_p99_ms")])
        done = {}

        def checker(cell, log, seed, model, arch, done=done):
            w1 = log.steps[-1].end
            w0 = w1 - args.seconds
            fin = [r for r in log.records if r.token_t and r.req.done
                   and w0 < r.token_t[-1] <= w1]
            done.update(completed_per_s=len(fin) / args.seconds,
                        queued_open=log.queued_at(w0),
                        queued_end=log.queued_at(w1),
                        mean_step_ms=1e3 * sum(
                            s.end - s.start for s in log.steps
                            if s.end > w0) / max(1, sum(
                                1 for s in log.steps if s.end > w0)))
            return {}

        res = bench_run.run(cell, args.seed, args.seconds, False,
                            time.perf_counter(), checker=checker)
        print(json.dumps({"workload": args.workload, "rate": rate, **done,
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
