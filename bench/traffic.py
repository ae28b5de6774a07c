"""The queries of a cell's traffic, drawn from the seed.

A query family (``bench/queries/<family>.py``) draws one query's
parameters; a loop (``bench/loops/<kind>.py``) decides when each is sent.
What lies between is the workload's:

- ``tenants``: that many tenant names, ``tenant0`` ... ;
- ``unique_conjuncts``: when true, a query that repeats any conjunct an
  earlier query of the run used is drawn again, so no window shares a
  fragment the planner could materialize and no query repeats into the
  result cache.  Absent or false, the family's draws stand as they come.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

import numpy as np

#: Redraws allowed before a family is judged unable to give unique cuts.
MAX_REDRAWS = 1000


@dataclasses.dataclass
class Query:
    """One submission: its tenant, the family's parameters and the text."""
    tenant: str
    params: dict
    expr: str
    client: int = -1
    t_due: Optional[float] = None   # open loop: seconds into the window


class UniqueCuts:
    """Draws from a family, redrawing any query that repeats a conjunct
    an earlier query of the run used."""

    def __init__(self, family):
        self.family = family
        self.used: set = set()

    def __call__(self, rng: np.random.Generator, prev: Optional[dict] = None
                 ) -> dict:
        for _ in range(MAX_REDRAWS):
            p = self.family.draw(rng, prev)
            parts = self.family.expression(p).split(" && ")
            if not self.used.intersection(parts):
                self.used.update(parts)
                return p
        raise RuntimeError(f"{self.family.__name__}: no unique cut in "
                           f"{MAX_REDRAWS} draws")


def drawer(workload: dict, family) -> Callable:
    """``draw(rng, prev) -> params`` for one run of the workload."""
    if workload.get("unique_conjuncts", False):
        return UniqueCuts(family)
    return family.draw


def tenant_names(n: int) -> List[str]:
    return [f"tenant{i}" for i in range(n)]


def draw_queries(workload: dict, family, seed_seq: np.random.SeedSequence,
                 n: int) -> List[Query]:
    """``n`` independent queries of the workload's family and tenants (the
    warm-up's)."""
    rng = np.random.default_rng(seed_seq)
    draw = drawer(workload, family)
    names = tenant_names(workload.get("tenants", 1))
    out = []
    for i in range(n):
        p = draw(rng, None)
        out.append(Query(names[i % len(names)], p, family.expression(p)))
    return out
