"""Reduce a profiler trace to device busy time, module time and the
longest idle gaps.

``load_events`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain tuples, so that the reduction below reads nothing but lists and can
be checked on a small recorded trace. Times are nanoseconds on the
profiler's clock, which the host spans and the device events share.
"""
from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Events:
    """Per device: op intervals and module intervals as (start, end, name);
    host spans of every host thread as (start, end, name)."""
    ops: dict[str, list]
    modules: dict[str, list]
    host: list


def load_events(trace_dir: str) -> Events:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events]
            if plane.name.startswith(DEVICE_PREFIX):
                if line.name == OPS_LINE:
                    ops[plane.name] = evs
                elif line.name == MODULES_LINE:
                    modules[plane.name] = evs
            elif plane.name.startswith("/host:"):
                host += evs
    return Events(ops, modules, host)


def clip(ivs, lo: float, hi: float) -> list:
    """Intervals cut to [lo, hi]; those outside dropped."""
    return [(max(s, lo), min(e, hi), n) for s, e, n in ivs
            if e > lo and s < hi]


def union(ivs) -> list[tuple[float, float]]:
    """Merged (start, end) pairs covering the intervals."""
    out: list[list[float]] = []
    for s, e, *_ in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ivs, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(ivs, lo, hi)))


def gaps(ivs, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle stretches in [lo, hi] between the merged intervals."""
    out, t = [], lo
    for s, e in union(clip(ivs, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span(host, name: str) -> tuple[float, float] | None:
    """First and last instant of the host spans called ``name``."""
    hits = [(s, e) for s, e, n in host if n == name]
    if not hits:
        return None
    return min(s for s, _ in hits), max(e for _, e in hits)


def short_name(name: str) -> str:
    """``%copy.55 = bf16[32,8]{...} copy(...)`` -> ``%copy.55 bf16[32,8]``:
    the instruction and its result type without layout."""
    instr, _, rest = name.partition(" = ")
    kind = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{instr} {kind}".strip()[:96]


def self_times(ivs) -> list:
    """Each interval with its self time: its length less the parts that
    intervals nested inside it cover (a loop op holds its body's ops)."""
    out, stack = [], []     # stack of [end, index into out]
    for s, e, n in sorted(ivs, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= e - s
        out.append([n, e - s])
        stack.append([e, len(out) - 1])
    return out


def top_ops(ivs, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` ops (by short name) with the most device self seconds in
    [lo, hi]."""
    tot: dict[str, float] = {}
    for n, t in self_times(clip(ivs, lo, hi)):
        n = short_name(n)
        tot[n] = tot.get(n, 0.0) + t
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in best]


def labelled_gaps(ivs, host, lo: float, hi: float, labels: set,
                  k: int = 10) -> list:
    """The ``k`` longest idle gaps in [lo, hi], each named by the host
    span among ``labels`` that covers most of it ("other" where none)."""
    spans = [(s, e, n) for s, e, n in host if n in labels]
    out = []
    for gs, ge in sorted(gaps(ivs, lo, hi), key=lambda g: g[0] - g[1])[:k]:
        cover: dict[str, float] = {}
        for s, e, n in spans:
            o = min(e, ge) - max(s, gs)
            if o > 0:
                cover[n] = cover.get(n, 0.0) + o
        name = max(cover, key=cover.get) if cover else "other"
        out.append([name, (ge - gs) * 1e-9])
    return out


def module_durations(mods, lo: float, hi: float, needle: str) -> list:
    """Seconds of each module run in [lo, hi] whose name holds ``needle``."""
    return [(e - s) * 1e-9 for s, e, n in mods
            if needle in n and s >= lo and e <= hi]


HOST_LABELS = {"engine.step", "load_generator", "wait_arrival"}
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class TracedWindow:
    """What the per-layer readers read: the trace of the traced window,
    the engine steps the harness logged in it, the engine's own mean step
    time over it, and the step cost and peaks to price them with."""
    events: Events
    steps: list             # serve.Step entries inside the traced window
    engine_step_s: float | None
    step_cost: object       # (Step) -> (flops, bytes)
    peaks: dict

    @property
    def bounds(self) -> tuple[float, float] | None:
        return span(self.events.host, WINDOW_SPAN)

    def busy_s(self) -> float | None:
        """Device busy seconds in the window, averaged over devices."""
        b = self.bounds
        if b is None or not self.events.ops:
            return None
        per = [busy_ns(ivs, *b) for ivs in self.events.ops.values()]
        return sum(per) / len(per) * 1e-9

    def window_s(self) -> float | None:
        b = self.bounds
        return None if b is None else (b[1] - b[0]) * 1e-9

    def module_s(self, needle: str = "decode_step") -> list:
        b = self.bounds
        if b is None:
            return []
        return [d for mods in self.events.modules.values()
                for d in module_durations(mods, *b, needle)]

    def least_step_s(self) -> tuple[float, str] | None:
        """Mean over the logged steps of the least time each could take
        (the larger of FLOPs at peak and bytes at HBM bandwidth), and
        which bound applies to most steps."""
        if not self.steps or not self.peaks:
            return None
        tf, tb = [], []
        for st in self.steps:
            f, by = self.step_cost(st)
            tf.append(f / self.peaks["bf16_flops_per_s"])
            tb.append(by / self.peaks["hbm_bytes_per_s"])
        least = [max(a, b) for a, b in zip(tf, tb)]
        bound = "bytes" if sum(b >= a for a, b in zip(tf, tb)) * 2 >= len(
            tf) else "flops"
        return sum(least) / len(least), bound

    def breakdown(self) -> dict | None:
        b = self.bounds
        if b is None or not self.events.ops:
            return None
        ivs = next(iter(sorted(self.events.ops.items())))[1]
        return {"device_ops": top_ops(ivs, *b),
                "idle_gaps": labelled_gaps(ivs, self.events.host, *b,
                                           HOST_LABELS)}
