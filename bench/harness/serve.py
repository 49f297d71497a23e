"""Drive ``ServeEngine`` with a schedule of requests on the wall clock and
record, from outside the engine, what each request saw.

The loop is the engine's own: submit what is due, call ``engine.step()``,
which ends in a host sync on the step's tokens. After each step it notes,
for every request that held a lane in it, the tokens that step emitted and
the position the lane processed. Host spans go into the profiler's trace
(``TraceAnnotation``; no cost while no trace is running): ``engine.step``
around each step, ``load_generator`` around submitting, ``wait_arrival``
while the engine is idle and the next request is not yet due.
"""
from __future__ import annotations

import dataclasses
import time

import jax

from bench.harness.traffic import Arrival


@dataclasses.dataclass
class Record:
    arrival: Arrival
    req: object                 # repro.serve.Request
    submitted: float = 0.0      # host clock (perf_counter), absolute
    admitted: float = 0.0       # start of the step that took it into a lane
    token_t: list = dataclasses.field(default_factory=list)
    processed: int = 0          # positions this request's lane has filled
    lane: int = -1
    lane_reused: bool = False   # admitted into a lane another request used


@dataclasses.dataclass
class Step:
    start: float
    end: float
    active: int                 # lanes holding a request in this step
    ctx_sum: int                # live positions attended, over those lanes
    emitted: int                # output tokens the step produced


@dataclasses.dataclass
class RunLog:
    records: list[Record]
    steps: list[Step] = dataclasses.field(default_factory=list)
    t0: float = 0.0             # host clock of schedule time 0
    next_arrival: int = 0
    lanes_used: set = dataclasses.field(default_factory=set)
    max_lateness: float = 0.0   # worst submit - due, seconds

    def in_window(self) -> list[Record]:
        return [r for r in self.records if r.arrival.in_window]

    def queued_at(self, t: float) -> int:
        """Requests submitted by host time ``t`` and not yet in a lane."""
        return sum(1 for r in self.records[:self.next_arrival]
                   if r.submitted <= t and not 0 < r.admitted <= t)


def new_log(arrivals: list[Arrival], make_request) -> RunLog:
    """``make_request(rid, arrival)`` builds the engine's request."""
    return RunLog([Record(a, make_request(i, a))
                   for i, a in enumerate(arrivals)])


def drive(engine, log: RunLog, until: float, stop=None) -> None:
    """Serve ``log``'s schedule until the host clock reaches ``until``, or
    until ``stop()`` holds after a step. ``log.t0`` must be set."""
    recs = log.records
    n = len(recs)
    while True:
        now = time.perf_counter()
        if now >= until:
            return
        i = log.next_arrival
        if i < n and log.t0 + recs[i].arrival.due_s <= now:
            with jax.profiler.TraceAnnotation("load_generator"):
                while i < n and log.t0 + recs[i].arrival.due_s <= now:
                    rec = recs[i]
                    rec.submitted = now
                    log.max_lateness = max(
                        log.max_lateness, now - log.t0 - rec.arrival.due_s)
                    engine.submit(rec.req)
                    i += 1
                log.next_arrival = i
        if engine.queue or any(s is not None for s in engine.slots):
            _step(engine, log, len(log.steps))
            if stop is not None and stop():
                return
            continue
        nxt = log.t0 + recs[i].arrival.due_s if i < n else until
        with jax.profiler.TraceAnnotation("wait_arrival"):
            time.sleep(max(0.0, min(nxt, until) - time.perf_counter()))


def _step(engine, log: RunLog, k: int) -> None:
    before = [r for r in engine.slots if r is not None]
    t0 = time.perf_counter()
    with jax.profiler.StepTraceAnnotation("engine.step", step_num=k):
        engine.step()
    t1 = time.perf_counter()
    after = engine.slots
    seen = {r.rid for r in before}
    for lane, r in enumerate(after):
        if r is not None and r.rid not in seen:
            rec = log.records[r.rid]
            rec.lane = lane
            rec.admitted = t0
            rec.lane_reused = lane in log.lanes_used
            log.lanes_used.add(lane)
            before.append(r)
    ctx = emitted = 0
    for r in before:
        rec = log.records[r.rid]
        rec.processed += 1
        ctx += rec.processed
        new = len(r.out) - len(rec.token_t)
        if new > 0:
            rec.token_t.extend([t1] * new)
            emitted += new
    log.steps.append(Step(t0, t1, len(before), ctx, emitted))
