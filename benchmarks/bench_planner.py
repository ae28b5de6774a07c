"""Shared-aggregate planner benchmark: common-subexpression factoring on a
near-duplicate workload.

The claim under test: interactive HEP analysis traffic is dominated by
*near*-duplicate queries — the same expensive track aggregates under
different outer scalar filters.  PR 1's coalescing dedups only identical
canonical queries, so each of the 64 distinct near-duplicates below still
evaluates its own copy of the shared ``count(pt > B)`` / ``sum(pt)``
fragments on every resident packet.  The planner hash-conses every
subexpression across the window and evaluates each unique fragment once
per packet, so per-brick fragment evaluations drop >= 2x while per-query
results stay bit-identical to independent execution.

Run: ``PYTHONPATH=src python benchmarks/bench_planner.py``
"""
from __future__ import annotations

import os
import time

import numpy as np

from repro.configs.geps_events import reduced
from repro.core import events as ev
from repro.core.brick import create_store
from repro.core.catalog import MetadataCatalog
from repro.core.jse import JobSubmissionEngine
from repro.core.merge import results_identical
from repro.service import plan_window

N_EVENTS = 2048
N_NODES = 4
K = 64

# three hot aggregate fragments shared across the window, each under a
# distinct outer scalar filter per query -> 64 distinct canonical queries
# (PR 1 coalescing dedups none of them)
SHARED = ["count(pt > 15) >= 2", "sum(pt) < 350", "count(pt > 25) >= 1"]


def near_duplicate_workload(k: int):
    return [f"e_total > {20 + i} && {SHARED[i % len(SHARED)]}"
            for i in range(k)]


def run_batch(store, exprs, *, shared: bool, failure_script=None):
    cat = MetadataCatalog(store.n_nodes)
    jse = JobSubmissionEngine(cat, store)
    jids = [jse.submit(e) for e in exprs]
    plan = plan_window(exprs, shared=shared, materialize=shared)
    t0 = time.perf_counter()
    merged, st = jse.run_job_batch_simulated(jids, plan=plan,
                                             failure_script=failure_script)
    return merged, st, time.perf_counter() - t0


def run_singles(store, exprs, *, failure_script=None):
    out = []
    for e in exprs:
        cat = MetadataCatalog(store.n_nodes)
        jse = JobSubmissionEngine(cat, store)
        merged, _ = jse.run_job_simulated(jse.submit(e),
                                          failure_script=failure_script)
        out.append(merged)
    return out


def main():
    # CI smoke mode: tiny workload, bit-rot detection only (the factoring
    # ratio is scale-dependent, so the >=2x assert is skipped)
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    k, n_events = (12, 512) if smoke else (K, N_EVENTS)
    schema = ev.EventSchema.from_config(reduced())
    store = create_store(schema, n_events=n_events, n_nodes=N_NODES,
                         events_per_brick=128, replication=2, seed=11)
    exprs = near_duplicate_workload(k)

    base_merged, base_st, base_wall = run_batch(store, exprs, shared=False)
    plan_merged, plan_st, plan_wall = run_batch(store, exprs, shared=True)

    n_bricks = len(store.bricks)
    base_per_brick = base_st.fragment_evals / n_bricks
    plan_per_brick = plan_st.fragment_evals / n_bricks
    ratio = base_st.fragment_evals / max(1, plan_st.fragment_evals)

    print(f"workload: K={k} near-duplicate queries, "
          f"{n_events} events / {n_bricks} bricks / {N_NODES} nodes")
    print("mode,fragment_evals,per_brick,events_scanned,wall_s")
    print(f"pr1_coalescing,{base_st.fragment_evals},"
          f"{base_per_brick:.0f},{base_st.events_scanned},{base_wall:.2f}")
    print(f"planner_factored,{plan_st.fragment_evals},"
          f"{plan_per_brick:.0f},{plan_st.events_scanned},{plan_wall:.2f}")
    print(f"reduction: {ratio:.2f}x fewer per-brick fragment evaluations "
          f"({len(plan_st.fragment_results)} shared fragments materialized "
          f"into the cache for free)")

    if not smoke:
        assert ratio >= 2.0, \
            f"planner must factor >= 2x fragment evals, got {ratio:.2f}x"

    # bit-identity: factored per-query results == independent execution,
    # clean run and under a node-failure script
    singles = run_singles(store, exprs)
    for got, want in zip(plan_merged, singles):
        assert results_identical(got, want), "factored result diverged"
    script = {0.5: 1}
    fail_merged, _, _ = run_batch(store, exprs, shared=True,
                                  failure_script=script)
    fail_singles = run_singles(store, exprs, failure_script=script)
    for got, want in zip(fail_merged, fail_singles):
        assert results_identical(got, want), \
            "factored result diverged under failure script"
    print("bit-identity: OK (clean + node-failure script)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
