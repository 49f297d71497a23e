"""Mean host time of an engine step that no device time can hide, in ms:
over the engine's ``serve/step`` spans inside the traced window, each
step's length less its ``serve/sync`` (the wait for the step's tokens).
What is left is admission and lane resets, building the token vector,
enqueueing the jitted step and the output loop, all on the host."""
from bench.harness.trace import clip

STEP, SYNC = "serve/step", "serve/sync"


def read(tw):
    b = tw.bounds
    if b is None:
        return None
    lo, hi = b
    host = tw.events.host
    steps = [(s, e) for s, e, n in host if n == STEP and lo <= s and e <= hi]
    syncs = [h for h in host if h[2] == SYNC]
    if not steps or not syncs:
        return None
    host_ns = sum((e - s) - sum(ye - ys for ys, ye, _ in clip(syncs, s, e))
                  for s, e in steps)
    return host_ns / len(steps) * 1e-6
