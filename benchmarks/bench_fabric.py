"""Coherence-fabric benchmark: what the shared tier buys a fleet.

Three claims under test, on a fleet of 4 front-ends over one brick store:

1. **Fleet hit rate** — on a skewed multi-tenant workload (a hot pool of
   repeated queries spread round-robin over the fleet, plus a distinct
   long tail), the shared-L2 fleet's cache hit rate is STRICTLY above
   the same fleet with independent per-front-end caches: with
   independent caches every front-end pays its own cold miss for every
   hot query; with the shared tier only the first front-end does.

2. **Cross-front-end first-result latency** — a tenant asking front-end
   B for a query front-end A already answered gets its (streamed) final
   result immediately from the shared tier (zero scan latency on the
   virtual grid clock), where the independent-cache fleet re-runs the
   scan and the tenant waits for the first partial of a fresh sweep.

3. **Registry pre-warming** — with the persistent fragment registry
   seeding each window's planner, a conjunct that is hot ACROSS windows
   (but referenced only once per window) is materialized into the cache,
   so later whole-query submissions of it never scan; total per-brick
   fragment evaluations drop below per-window factoring alone.

4. **Single-flight execution** — on a near-duplicate workload (every
   window one canonical submitted at EVERY front-end — the duplicate
   the shared L2 cannot close, because same-window duplicates miss
   independently and each runs its own scan), scan-intent leases
   (``fabric/leases.py``) collapse the fleet to ONE scan per canonical:
   fleet-wide scanned events drop >= 3x against the no-lease fleet,
   every per-ticket final stays bit-identical, and the remote
   first-result latency is unchanged.

Plus the observability acceptance pass: the same workload replayed with
``Fleet(obs=True)`` must produce a schema-valid fleet trace (written as
Perfetto-loadable ``BENCH_fabric_trace.json`` outside smoke) whose
fleet-merged metric counters reconcile EXACTLY with the service-stats
aggregation (L1 + L2 hit counters vs ``fleet_stats``).

And the flight-recorder acceptance pass: arming ``Fleet(flight=True)``
on the obs workload must leave the virtual timeline EXACTLY unchanged
(bit-identical finals AND identical comparable trace records — every
virtual makespan included — vs the recorder-less run), and the recorded
log must replay bit-identically through ``repro.obs.replay``.

Run: ``PYTHONPATH=src python benchmarks/bench_fabric.py``
(writes a ``BENCH_fabric.json`` snapshot next to this file;
``BENCH_SMOKE=1`` shrinks sizes and skips the snapshot + perf asserts).
"""
from __future__ import annotations

import json
import os
import pathlib

from repro.configs.geps_events import reduced
from repro.core import events as ev
from repro.core import merge as merge_lib
from repro.core.brick import create_store
from repro.fabric import Fleet, FragmentRegistry
from repro.obs import trace as trace_lib
from repro.service import QueryService

OUT = pathlib.Path(__file__).resolve().parent / "BENCH_fabric.json"
TRACE_OUT = pathlib.Path(__file__).resolve().parent / \
    "BENCH_fabric_trace.json"

N_EVENTS = 4096
N_NODES = 8
EVENTS_PER_BRICK = 256
N_FRONTENDS = 4
N_TENANTS = 8
N_QUERIES = 96
WINDOW = 8

HOT_POOL = [
    "e_total > 40 && count(pt > 15) >= 2",
    "e_t_miss > 30",
    "pt_lead > 60 || n_tracks >= 8",
    "e_total > 55 && sum(pt) < 400",
    "count(pt > 25) >= 1",
    "e_total + 2 * e_t_miss > 120",
]


def smoke() -> bool:
    """True when the CI benchmark smoke job is running (tiny sizes, no
    snapshot writes, no perf asserts — bit-rot detection only)."""
    return os.environ.get("BENCH_SMOKE") == "1"


def skewed_workload(n: int):
    """(tenant, expr) pairs: ~2/3 draws from the hot pool (the
    interactive-analysis regime), the rest a distinct long tail."""
    out = []
    for i in range(n):
        tenant = f"tenant{i % N_TENANTS}"
        if i % 3 != 2:
            expr = HOT_POOL[(i * 7) % len(HOT_POOL)]
        else:
            expr = (f"e_total > {20 + (i % 13) * 5} && "
                    f"count(pt > 15) >= {1 + i % 3}")
        out.append((tenant, expr))
    return out


def run_fleet(store, *, shared_cache: bool) -> dict:
    """Replay the skewed workload over a fleet; returns aggregate stats."""
    fleet = Fleet(store, N_FRONTENDS, shared_cache=shared_cache)
    for i, (tenant, expr) in enumerate(skewed_workload(N_QUERIES)):
        fleet.submit(expr, tenant=tenant)  # round-robin over front-ends
        if (i + 1) % WINDOW == 0:
            fleet.step()
    fleet.drain()
    stats = fleet.fleet_stats()
    fleet.close()
    return stats


def run_obs_fleet(store) -> dict:
    """The skewed workload again with the observability plane ON: the
    fleet-merged metrics must reconcile exactly with ``fleet_stats``,
    the trace must schema-validate, and (outside smoke) the Chrome
    trace lands next to the snapshot, Perfetto-loadable."""
    fleet = Fleet(store, N_FRONTENDS, obs=True)
    for i, (tenant, expr) in enumerate(skewed_workload(N_QUERIES)):
        fleet.submit(expr, tenant=tenant)
        if (i + 1) % WINDOW == 0:
            fleet.step()
    fleet.drain()
    stats = fleet.fleet_stats()
    snap = fleet.metrics_snapshot()
    recs = fleet.trace_records()
    problems = trace_lib.validate_records(recs)
    assert not problems, f"fleet trace invalid: {problems[:5]}"
    l1, l2 = snap.value("cache.hits_l1"), snap.value("cache.hits_l2")
    assert l1 + l2 == stats["cache_hits"], \
        f"obs cache counters {l1}+{l2} != fleet_stats " \
        f"{stats['cache_hits']}"
    assert l2 == stats["l2_hits"], \
        f"obs L2 counter {l2} != fleet_stats {stats['l2_hits']}"
    assert snap.value("tickets.served") == stats["served"], \
        "obs tickets.served != fleet_stats served"
    out = {"trace_records": len(recs), "cache_hits_l1": l1,
           "cache_hits_l2": l2,
           "tickets_served": snap.value("tickets.served")}
    if not smoke():
        fleet.save_chrome_trace(TRACE_OUT)
        out["trace_file"] = TRACE_OUT.name
    fleet.close()
    return out


def run_flight_fleet(store) -> dict:
    """Recording must be free on the virtual clock: the obs workload
    with the flight recorder armed yields bit-identical finals and an
    IDENTICAL comparable trace (every virtual makespan included) vs the
    recorder-less run, and the log replays bit-identically."""
    from repro.obs import replay as replay_lib

    def one(flight: bool):
        fleet = Fleet(store, N_FRONTENDS, obs=True, flight=flight)
        gtids = []
        for i, (tenant, expr) in enumerate(skewed_workload(N_QUERIES)):
            gtids.append(fleet.submit(expr, tenant=tenant))
            if (i + 1) % WINDOW == 0:
                fleet.step()
        fleet.drain()
        results = [fleet.result(g).result for g in gtids]
        recs = trace_lib.comparable_records(fleet.trace_records())
        log = list(fleet.flight.records) if flight else None
        fleet.close()
        return results, recs, log

    res_on, trace_on, log = one(True)
    res_off, trace_off, _ = one(False)
    assert all(merge_lib.results_identical(a, b)
               for a, b in zip(res_on, res_off)), \
        "flight recording changed a final result"
    assert trace_on == trace_off, \
        "flight recording perturbed the virtual timeline"
    # this workload never mutates the store (no deaths/re-replication),
    # so replaying over the same store object is sound
    rep = replay_lib.replay_run(log, store=store)
    assert rep.identical, \
        f"replay diverged: {rep.mismatches[:3]} {rep.bus_divergences[:3]}"
    return {"flight_records": len(log), "finals": rep.n_finals,
            "replay_identical": rep.identical}


def near_duplicate_workload(windows: int):
    """One canonical per window, near-duplicates of each other (same
    structure, shifted cut) so no window hits a previous window's cache
    entry — every window is the same-window duplicate-scan race."""
    return [f"e_total > {30 + w} && count(pt > 15) >= 2"
            for w in range(windows)]


def run_single_flight(store, *, single_flight: bool):
    """The duplicate-work race at benchmark scale: every window's
    canonical is submitted at EVERY front-end simultaneously.  Returns
    (aggregate stats, per-ticket final results in submission order)."""
    windows = 4 if smoke() else 8
    fleet = Fleet(store, N_FRONTENDS, single_flight=single_flight)
    gtids = []
    for expr in near_duplicate_workload(windows):
        gtids.extend(fleet.submit(expr, tenant=f"tenant{i}", frontend=i)
                     for i in range(N_FRONTENDS))
        fleet.step()
    fleet.drain()
    results = [fleet.result(g).result for g in gtids]
    assert all(r is not None for r in results), "unserved duplicate ticket"
    stats = fleet.fleet_stats()
    fleet.close()
    return stats, results


def remote_first_result_latency(store, *, shared_cache: bool,
                                single_flight: bool = False) -> float:
    """Virtual-clock latency until a tenant at front-end 1 holds a final
    result for a query front-end 0 already answered."""
    fleet = Fleet(store, 2, shared_cache=shared_cache,
                  single_flight=single_flight)
    fleet.submit(HOT_POOL[0], tenant="a", frontend=0)
    fleet.drain()
    g = fleet.submit(HOT_POOL[0], tenant="b", frontend=1, stream=True)
    rs = fleet.stream(g)
    fleet.drain()
    snap = rs.latest()
    assert snap is not None and snap.final, "remote query never finished"
    fleet.close()
    return snap.t_virtual


def run_registry(store, *, use_registry: bool) -> dict:
    """Cross-window workload: a conjunct hot across windows (once per
    window), later submitted as a whole query.  Returns fragment-eval
    accounting."""
    registry = FragmentRegistry(hot_min_windows=2) if use_registry else None
    svc = QueryService(store, registry=registry)
    frag = "count(pt > 15) >= 2"
    windows = 4 if smoke() else 8
    for w in range(windows):
        svc.submit(f"e_total > {30 + w} && {frag}", tenant="a")
        svc.submit(f"e_t_miss > {10 + w}", tenant="b")
        svc.step()
        if w >= 2:  # after warmup, tenants start asking for the conjunct
            t = svc.submit(frag, tenant=f"c{w}")
            svc.drain()
            assert svc.result(t).status == "SERVED"
    out = {
        "fragment_evals": svc.stats.fragment_evals,
        "per_brick": svc.stats.fragment_evals / len(store.bricks),
        "events_scanned": svc.stats.events_scanned,
        "cache_hits": svc.stats.cache_hits,
    }
    svc.close()
    return out


def main():
    global N_EVENTS, N_QUERIES
    if smoke():
        N_EVENTS, N_QUERIES = 1024, 24
    schema = ev.EventSchema.from_config(reduced())
    store = create_store(schema, n_events=N_EVENTS, n_nodes=N_NODES,
                         events_per_brick=EVENTS_PER_BRICK,
                         replication=2, seed=17)
    print(f"workload: fleet of {N_FRONTENDS}, {N_QUERIES} queries / "
          f"{N_TENANTS} tenants (skewed), store {N_EVENTS} events / "
          f"{len(store.bricks)} bricks / {N_NODES} nodes")

    shared = run_fleet(store, shared_cache=True)
    indep = run_fleet(store, shared_cache=False)
    print("mode,hit_rate,cache_hits,l2_hits,events_scanned")
    print(f"shared_l2,{shared['hit_rate']:.3f},{shared['cache_hits']},"
          f"{shared['l2_hits']},{shared['events_scanned']}")
    print(f"independent,{indep['hit_rate']:.3f},{indep['cache_hits']},"
          f"{indep['l2_hits']},{indep['events_scanned']}")

    obs = run_obs_fleet(store)
    print(f"obs_fleet,trace_records={obs['trace_records']},"
          f"hits_l1={obs['cache_hits_l1']:.0f},"
          f"hits_l2={obs['cache_hits_l2']:.0f},"
          f"served={obs['tickets_served']:.0f},reconciled=exact")

    fl = run_flight_fleet(store)
    print(f"flight_fleet,records={fl['flight_records']},"
          f"finals={fl['finals']},virtual_makespan=unchanged,"
          f"replay_identical={fl['replay_identical']}")

    lat_shared = remote_first_result_latency(store, shared_cache=True)
    lat_indep = remote_first_result_latency(store, shared_cache=False)
    lat_single = remote_first_result_latency(store, shared_cache=True,
                                             single_flight=True)
    print(f"remote_first_result_s,shared={lat_shared:.3f},"
          f"independent={lat_indep:.3f},single_flight={lat_single:.3f}")

    sf, sf_results = run_single_flight(store, single_flight=True)
    nl, nl_results = run_single_flight(store, single_flight=False)
    reduction = nl["events_scanned"] / max(1, sf["events_scanned"])
    identical = all(merge_lib.results_identical(a, b)
                    for a, b in zip(sf_results, nl_results))
    print("single_flight,mode,events_scanned,adopted,fallbacks")
    print(f"single_flight,lease,{sf['events_scanned']},{sf['adopted']},"
          f"{sf['lease_fallbacks']}")
    print(f"single_flight,no_lease,{nl['events_scanned']},0,0")
    print(f"single_flight,scan_reduction={reduction:.2f}x,"
          f"finals_identical={identical}")
    assert identical, "adopted finals must be bit-identical to no-lease"

    reg = run_registry(store, use_registry=True)
    plain = run_registry(store, use_registry=False)
    print("registry,fragment_evals,per_brick,events_scanned,cache_hits")
    print(f"prewarmed,{reg['fragment_evals']},{reg['per_brick']:.0f},"
          f"{reg['events_scanned']},{reg['cache_hits']}")
    print(f"window_only,{plain['fragment_evals']},{plain['per_brick']:.0f},"
          f"{plain['events_scanned']},{plain['cache_hits']}")

    if not smoke():
        assert shared["hit_rate"] > indep["hit_rate"], \
            f"shared L2 hit rate {shared['hit_rate']:.3f} must beat " \
            f"independent {indep['hit_rate']:.3f}"
        assert lat_shared < lat_indep, \
            "shared tier must answer the remote tenant faster"
        assert reg["fragment_evals"] < plain["fragment_evals"], \
            "registry pre-warming must reduce per-brick fragment evals"
        assert reduction >= 3.0, \
            f"single-flight must cut fleet-wide scanned events >= 3x " \
            f"on the near-duplicate workload (got {reduction:.2f}x)"
        assert sf["adopted"] > 0, "no adoptions happened"
        assert lat_single == lat_shared, \
            f"single-flight must not change remote first-result " \
            f"latency ({lat_single:.3f}s vs {lat_shared:.3f}s)"
        OUT.write_text(json.dumps({
            "bench": "fabric",
            "config": {"n_events": N_EVENTS, "n_nodes": N_NODES,
                       "events_per_brick": EVENTS_PER_BRICK,
                       "n_frontends": N_FRONTENDS, "n_tenants": N_TENANTS,
                       "n_queries": N_QUERIES, "window": WINDOW,
                       "replication": 2},
            "fleet_hit_rate": {"shared_l2": shared,
                               "independent": indep},
            "remote_first_result_s": {"shared_l2": lat_shared,
                                      "independent": lat_indep,
                                      "single_flight": lat_single},
            "registry_prewarming": {"prewarmed": reg,
                                    "window_only": plain},
            "single_flight": {"lease": sf, "no_lease": nl,
                              "scan_reduction_x": reduction,
                              "finals_identical": identical},
        }, indent=2) + "\n")
        print(f"snapshot written: {OUT.name}")
        print(f"shared-L2 fleet hit rate {shared['hit_rate']:.3f} > "
              f"independent {indep['hit_rate']:.3f}; registry "
              f"{plain['fragment_evals'] / max(1, reg['fragment_evals']):.2f}x"
              f" fewer fragment evals: OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
