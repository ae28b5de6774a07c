"""Open loop: independent users, each query sent once it is due, whatever
the service is doing.

    "loop": {"kind": "open", "rate_per_s": r}

A run of ``seconds`` gets exactly ``round(r * seconds)`` arrivals, placed
as the sorted draws of a uniform distribution over the window: a Poisson
process conditioned on its count, so every seed offers the same work at
different instants.  Each arrival draws a fresh query of the family and
picks one of the ``tenants`` at random.  Latencies run from the due
instant, so a generator that falls behind does not hide a slow service.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench import drive
from bench import traffic


def widths(loop: dict, max_batch: int) -> list:
    """Every number of queries a window can hold: any, up to the cap."""
    return list(range(1, max_batch + 1))


def arrivals(workload: dict, family, seed_seq: np.random.SeedSequence,
             seconds: float) -> List[traffic.Query]:
    """The whole schedule: arrival instants, tenants and queries."""
    rng = np.random.default_rng(seed_seq)
    n = int(round(workload["loop"]["rate_per_s"] * seconds))
    times = np.sort(rng.uniform(0.0, seconds, n))
    tenants = traffic.tenant_names(workload["tenants"])
    draw = traffic.drawer(workload, family)
    out = []
    for t in times:
        p = draw(rng, None)
        out.append(traffic.Query(tenants[int(rng.integers(len(tenants)))], p,
                                 family.expression(p), t_due=float(t)))
    return out


def run(svc, workload: dict, family, seed_seq, seconds: float,
        calib_iters: int, compiles) -> drive.WindowRecord:
    """Submit each arrival once it is due; step whenever work is queued."""
    import time

    todo = arrivals(workload, family, seed_seq, seconds)
    c0 = compiles.events
    client = drive.Client(svc, calib_iters, time.perf_counter())
    steps: List[drive.StepRecord] = []
    i, late = 0, 0.0
    while True:
        now = client.now()
        if i < len(todo) and todo[i].t_due <= now:
            with client.span("submit"):
                while i < len(todo) and todo[i].t_due <= now:
                    late = max(late, now - todo[i].t_due)
                    client.submit(todo[i], todo[i].t_due)
                    i += 1
        if svc.scheduler.n_pending:
            drive.step(svc, client, "step" if now < seconds else "drain",
                       steps)
        elif i < len(todo):
            with client.span("await_arrival"):
                time.sleep(max(0.0, todo[i].t_due - client.now()))
        else:
            break
    return drive.record(svc, "open", seconds, client, steps,
                        compiles.events - c0, late)
