"""Streaming result delivery benchmark: time-to-first-partial vs
time-to-final on a multi-brick workload.

The claim under test: with per-packet partial-merge streaming, a tenant
reads an exact progressive histogram long before the job completes —
time-to-first-partial must be <= 1/4 of time-to-final (both on the
simulated grid clock, the same clock as ``JobStats.makespan_s``) — and the
final streamed snapshot stays bit-identical to the batch JSE merge.

Two streaming-friendly sizings are measured: the PR 3 workaround (fixed
small packets — PROOF-adaptive sizing optimizes makespan by handing each
node ~1/(4·nodes) of the store up front, which is exactly wrong for
time-to-first-partial) and the stream-aware RAMP (PROOF-adaptive sizing
kept ON, with early packets capped small and growing geometrically —
``QueryService(stream_ramp=...)``).  The ramp must not regress
time-to-first-partial vs. the fixed workaround while retaining adaptive
sizing for the bulk of the scan.

Run: ``PYTHONPATH=src python benchmarks/bench_streaming.py``
(writes a ``BENCH_streaming.json`` snapshot next to this file;
``BENCH_SMOKE=1`` shrinks the store and skips asserts + the snapshot).
"""
from __future__ import annotations

import json
import os
import pathlib
import time

from repro.configs.geps_events import reduced
from repro.core import events as ev
from repro.core.brick import create_store
from repro.core.catalog import MetadataCatalog
from repro.core.jse import JobSubmissionEngine
from repro.core.merge import results_identical
from repro.service import QueryService

N_EVENTS = 32768
N_NODES = 8
EVENTS_PER_BRICK = 256
OUT = pathlib.Path(__file__).resolve().parent / "BENCH_streaming.json"

BATCH = ["e_total > 40 && count(pt > 15) >= 2",
         "e_total > 30 && count(pt > 15) >= 1",
         "e_t_miss > 25 && count(pt > 15) >= 2",
         "pt_lead > 60 || n_tracks >= 8",
         "e_total > 55 && sum(pt) < 400",
         "e_total > 35 && sum(pt) < 400",
         "e_t_miss > 40",
         "e_total + 2 * e_t_miss > 120"]


def smoke() -> bool:
    """True under the CI benchmark smoke job (tiny store, no asserts or
    snapshot writes — bit-rot detection only)."""
    return os.environ.get("BENCH_SMOKE") == "1"


def run_streamed(store, exprs, *, ramp=None):
    """One streamed shared-scan window; returns per-run metrics.

    ``ramp=None`` reproduces the PR 3 workaround (adaptive packets
    disabled, small fixed packets); an integer enables stream-aware
    sizing: adaptive packets stay ON and the service caps the streamed
    window's early packets at ``ramp`` events."""
    svc = QueryService(store, use_cache=False, stream_ramp=ramp)
    if ramp is None:
        svc.jse.adaptive_packets = False  # fixed packets: the workaround
    recorder = {"first": None, "snaps": 0}

    def record(snap):
        if recorder["first"] is None:
            recorder["first"] = snap.t_virtual
        recorder["snaps"] += 1

    tids = [svc.submit(e, tenant=f"t{i}", stream=True)
            for i, e in enumerate(exprs)]
    svc.stream(tids[0]).subscribe(record)
    t0 = time.perf_counter()
    svc.step()
    wall = time.perf_counter() - t0

    finals = [svc.stream(t).latest() for t in tids]
    assert all(f is not None and f.final for f in finals)
    t_final = finals[0].t_virtual
    return {
        "queries": len(exprs),
        "t_first_partial_s": round(recorder["first"], 4),
        "t_final_s": round(t_final, 4),
        "ratio": round(recorder["first"] / t_final, 4),
        "snapshots": recorder["snaps"],
        "coverage_complete": all(f.coverage.complete for f in finals),
        "wall_s": round(wall, 2),
    }, [f.result for f in finals]


def main():
    global N_EVENTS
    if smoke():
        N_EVENTS = 4096
    schema = ev.EventSchema.from_config(reduced())
    store = create_store(schema, n_events=N_EVENTS, n_nodes=N_NODES,
                         events_per_brick=EVENTS_PER_BRICK,
                         replication=2, seed=13)
    print(f"workload: {N_EVENTS} events / {len(store.bricks)} bricks / "
          f"{N_NODES} nodes")
    print("name,queries,t_first_partial_s,t_final_s,ratio,snapshots,wall_s")

    rows = {}
    finals = {}
    for name, exprs, ramp in (("single_query", BATCH[:1], None),
                              ("batch8", BATCH, None),
                              ("batch8_ramp", BATCH, 16)):
        row, merged = run_streamed(store, exprs, ramp=ramp)
        rows[name] = row
        finals[name] = merged
        print(f"{name},{row['queries']},{row['t_first_partial_s']},"
              f"{row['t_final_s']},{row['ratio']},{row['snapshots']},"
              f"{row['wall_s']}")

    if not smoke():
        for name, row in rows.items():
            assert row["ratio"] <= 0.25, \
                f"{name}: first partial at {row['ratio']:.2f}x of final " \
                f"(need <= 0.25)"
        print(f"time-to-first-partial <= 1/4 time-to-final: OK "
              f"(single {rows['single_query']['ratio']:.3f}, "
              f"batch {rows['batch8']['ratio']:.3f}, "
              f"ramp {rows['batch8_ramp']['ratio']:.3f})")
        # stream-aware ramp must not regress first-partial latency vs the
        # fixed-packet workaround (it keeps adaptive sizing for the bulk)
        assert (rows["batch8_ramp"]["t_first_partial_s"]
                <= rows["batch8"]["t_first_partial_s"] * 1.05), \
            "packet ramp regressed time-to-first-partial"
        print("stream-aware ramp: first partial "
              f"{rows['batch8_ramp']['t_first_partial_s']}s <= fixed "
              f"{rows['batch8']['t_first_partial_s']}s, OK")

    # bit-identity spot check: streamed finals == an independent batch run
    # merging only at job end (same store, fixed packets)
    cat = MetadataCatalog(store.n_nodes)
    jse = JobSubmissionEngine(cat, store, adaptive_packets=False)
    want, _ = jse.run_job_batch_simulated([jse.submit(e) for e in BATCH])
    for got, ref in zip(finals["batch8"], want):
        assert results_identical(got, ref), "streamed final diverged"
    print("bit-identity: streamed finals == batch JSE merge, OK")

    if not smoke():
        OUT.write_text(json.dumps({
            "bench": "streaming",
            "config": {"n_events": N_EVENTS, "n_nodes": N_NODES,
                       "events_per_brick": EVENTS_PER_BRICK,
                       "packet_events": 64, "ramp_start": 16,
                       "replication": 2},
            "rows": rows,
        }, indent=2) + "\n")
        print(f"snapshot written: {OUT.name}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
