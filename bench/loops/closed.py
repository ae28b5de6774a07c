"""Closed loop: a fixed number of clients, each waiting for its final
before it thinks and asks again.

    "loop": {"kind": "closed", "clients": c, "think_s": t}

Client ``i`` belongs to tenant ``i % tenants``.  Its ``j``-th query is
fixed by the seed whatever the timing, and is drawn around its last one
(the family's ``draw(rng, prev)``).  Latencies run from the submission.
No client starts a query once ``seconds`` have passed; the window ends
with the last final.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench import drive
from bench import traffic


def widths(loop: dict, max_batch: int) -> list:
    """Every client waits on its own query, so a window holds them all."""
    return [min(loop["clients"], max_batch)]


class Clients:
    """The clients' queries, each client on a stream of its own."""

    def __init__(self, workload: dict, family,
                 seed_seq: np.random.SeedSequence):
        loop = workload["loop"]
        self.family = family
        self.think_s = float(loop.get("think_s", 0.0))
        n = loop["clients"]
        tenants = traffic.tenant_names(workload["tenants"])
        self.tenants = [tenants[i % len(tenants)] for i in range(n)]
        self.rngs = [np.random.default_rng(s) for s in seed_seq.spawn(n)]
        self.prev: List[Optional[dict]] = [None] * n
        self.draw = traffic.drawer(workload, family)

    @property
    def n(self) -> int:
        return len(self.rngs)

    def next(self, client: int) -> traffic.Query:
        p = self.draw(self.rngs[client], self.prev[client])
        self.prev[client] = p
        return traffic.Query(self.tenants[client], p,
                             self.family.expression(p), client=client)


def run(svc, workload: dict, family, seed_seq, seconds: float,
        calib_iters: int, compiles) -> drive.WindowRecord:
    import time

    clients = Clients(workload, family, seed_seq)
    c0 = compiles.events
    client = drive.Client(svc, calib_iters, time.perf_counter())
    steps: List[drive.StepRecord] = []
    owner: Dict[int, int] = {}
    ready_at = [0.0] * clients.n
    idle = list(range(clients.n))
    while True:
        now = client.now()
        due = [c for c in idle if ready_at[c] <= now] if now < seconds else []
        if due:
            with client.span("submit"):
                for c in due:
                    rec = client.submit(clients.next(c), client.now())
                    rec.t_start = rec.t_submit
                    if svc.result(rec.ticket).status == "REJECTED":
                        # refused at the door: the client tries again
                        ready_at[c] = rec.t_submit + clients.think_s
                        continue
                    owner[rec.ticket] = c
                    idle.remove(c)
        if svc.scheduler.n_pending:
            drive.step(svc, client, "step" if now < seconds else "drain",
                       steps)
        elif idle and now < seconds:
            with client.span("await_arrival"):
                time.sleep(max(0.0, min(ready_at[c] for c in idle)
                               - client.now()))
        else:
            break
        for tid in client.finished:
            c = owner.pop(tid, None)
            if c is not None:
                ready_at[c] = client.records[tid].t_final + clients.think_s
                idle.append(c)
        client.finished.clear()
    return drive.record(svc, "closed", seconds, client, steps,
                        compiles.events - c0, 0.0)
