"""Multi-tenant shared-scan benchmark: queries/sec and events-scanned-
per-query for K concurrent queries, shared-scan coalescing vs. the
one-job-at-a-time baseline, plus the cache-hit path.

The claim under test (the DIAL/LHC interactive-analysis regime): at high
query concurrency the dominant cost is re-reading brick-resident events,
so coalescing K compatible queries into one sweep drops the per-query scan
volume ~K x, and a repeated query should return from the result cache with
ZERO brick I/O.
"""
from __future__ import annotations

import time

from repro.configs.geps_events import reduced
from repro.core import events as ev
from repro.core.brick import create_store
from repro.core.catalog import MetadataCatalog
from repro.core.jse import JobSubmissionEngine
from repro.service import QueryScheduler, QueryService

N_EVENTS = 2048
N_NODES = 4


def _store(schema, seed=11):
    return create_store(schema, n_events=N_EVENTS, n_nodes=N_NODES,
                        events_per_brick=128, replication=2, seed=seed)


def _exprs(k):
    # distinct thresholds -> distinct canonical queries (no dedup shortcut)
    return [f"e_total > {20 + i} && count(pt > {5 + i % 11}) >= 1"
            for i in range(k)]


def run_k(store, k):
    exprs = _exprs(k)

    # baseline: one job at a time, each its own full sweep
    cat = MetadataCatalog(store.n_nodes)
    jse = JobSubmissionEngine(cat, store)
    t0 = time.perf_counter()
    seq_scanned = seq_makespan = 0
    for e in exprs:
        _, st = jse.run_job_simulated(jse.submit(e))
        seq_scanned += st.events_scanned
        seq_makespan += st.makespan_s
    seq_wall = time.perf_counter() - t0

    # shared scan: all K coalesced into one sweep
    cat2 = MetadataCatalog(store.n_nodes)
    jse2 = JobSubmissionEngine(cat2, store)
    jids = [jse2.submit(e) for e in exprs]
    t0 = time.perf_counter()
    _, st2 = jse2.run_job_batch_simulated(jids)
    shared_wall = time.perf_counter() - t0

    return {
        "k": k,
        "seq_scanned_per_q": seq_scanned / k,
        "shared_scanned_per_q": st2.events_scanned / k,
        "seq_qps_wall": k / seq_wall,
        "shared_qps_wall": k / shared_wall,
        "seq_makespan_s": seq_makespan,
        "shared_makespan_s": st2.makespan_s,
    }


def run_cache(store):
    svc = QueryService(store, scheduler=QueryScheduler(max_batch=8))
    expr = "e_total > 40 && count(pt > 15) >= 2"
    svc.submit(expr, tenant="a")
    svc.drain()
    scanned_cold = svc.stats.events_scanned
    t0 = time.perf_counter()
    tid = svc.submit(expr, tenant="b")   # repeat -> served at submit time
    hit_wall = time.perf_counter() - t0
    ticket = svc.result(tid)
    assert ticket.from_cache, "repeat query must hit the cache"
    assert svc.stats.events_scanned == scanned_cold, \
        "cache hit must not scan any brick"
    return {"cold_scanned": scanned_cold, "hit_scanned": 0,
            "hit_wall_us": hit_wall * 1e6}


def main():
    import os
    schema = ev.EventSchema.from_config(reduced())
    store = _store(schema)
    # CI smoke mode trims the widest batch (the amortization invariant is
    # scale-free, so the asserts stay on)
    ks = (1, 8) if os.environ.get("BENCH_SMOKE") == "1" else (1, 8, 64)
    print("k,seq_scanned_per_q,shared_scanned_per_q,"
          "seq_qps_wall,shared_qps_wall,seq_makespan_s,shared_makespan_s")
    for k in ks:
        r = run_k(store, k)
        print(f"{r['k']},{r['seq_scanned_per_q']:.0f},"
              f"{r['shared_scanned_per_q']:.1f},{r['seq_qps_wall']:.1f},"
              f"{r['shared_qps_wall']:.1f},{r['seq_makespan_s']:.2f},"
              f"{r['shared_makespan_s']:.2f}")
        assert r["shared_scanned_per_q"] <= r["seq_scanned_per_q"] / k + 1, \
            "shared scan must amortize the sweep ~K x"

    c = run_cache(store)
    print(f"cache_hit,cold_scanned={c['cold_scanned']},"
          f"hit_scanned={c['hit_scanned']},"
          f"hit_wall={c['hit_wall_us']:.0f}us")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
