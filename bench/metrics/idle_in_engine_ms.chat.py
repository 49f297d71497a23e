"""Device-idle time inside the engine's steps, per step, in ms: the
stretches of the traced window in which no operation ran on the device
and the host was inside a ``serve/step`` span, over the number of those
spans (averaged over devices). The rest of the window's idle time lies in
the serving loop between steps, outside the engine."""
from bisect import bisect_right

from bench.harness.trace import gaps

STEP = "serve/step"


def read(tw):
    b = tw.bounds
    if b is None or not tw.events.ops:
        return None
    lo, hi = b
    steps = sorted((s, e) for s, e, n in tw.events.host
                   if n == STEP and lo <= s and e <= hi)
    if not steps:
        return None
    per_device = []
    for ivs in tw.events.ops.values():
        idle = gaps(ivs, lo, hi)
        starts = [s for s, _ in idle]
        ns = 0
        for s, e in steps:
            i = max(bisect_right(starts, s) - 1, 0)
            while i < len(idle) and idle[i][0] < e:
                ns += max(0, min(e, idle[i][1]) - max(s, idle[i][0]))
                i += 1
        per_device.append(ns)
    return sum(per_device) / len(per_device) / len(steps) * 1e-6
