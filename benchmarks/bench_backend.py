"""Execution-backend benchmark: the SPMD chunked streaming scan vs the
simulated grid, under ONE contract.

Claims under test (the unified-backend acceptance bar):

1. **Equivalence** — a dispatch window executed through
   ``SpmdBackend.run_batch`` produces final results bit-identical to
   ``SimulatedBackend.run_batch`` for the same window and packetization
   (both backends run the same fragment-factored
   ``eval_plan_slice`` primitive in the same merge order), and every
   per-chunk partial matches packet-for-packet.
2. **Streaming** — the SPMD path streams per-chunk partials: wall-clock
   time-to-first-partial must be <= 1/2 of time-to-final (it lands far
   below; the step-end-merge SPMD path it replaces had ratio 1.0 by
   construction), and the stream-aware ramp (``packet_ramp``) pushes the
   first partial earlier still without changing results.

3. **Raw speed** — the perf-pass acceptance bar: the ``(block_e,
   block_t)`` autotune sweep never loses to the fixed ``DEFAULT_SHAPE``
   (the default is itself a candidate) and records a roofline point per
   tuned shape; a MIXED window (some targets out-of-family)
   still pushes events through the kernel sub-batch
   (``stats.kernel_events > 0``) bit-identically; and the mesh-sharded
   scan's lockstep critical-path makespan scales near-linearly —
   >= 1.7x at 2 mesh devices over the same measured per-chunk compute.

Run: ``PYTHONPATH=src python benchmarks/bench_backend.py``
(writes a ``BENCH_backend.json`` snapshot next to this file;
``BENCH_SMOKE=1`` shrinks the store and skips asserts + the snapshot;
``--autotune`` runs the block-shape sweep alone).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import time

from repro.configs.geps_events import reduced
from repro.core import events as ev
from repro.core.backend import SimulatedBackend, SpmdBackend
from repro.core.brick import create_store
from repro.core.catalog import MetadataCatalog
from repro.core.jse import eval_plan_slice
from repro.core.merge import results_identical
from repro.service import plan_window

N_EVENTS = 16384
N_NODES = 8
EVENTS_PER_BRICK = 256
CHUNK = 64  # fixed packet/chunk size on BOTH backends (identity requires
            # matching packetization; the sim runs adaptive_packets=False)
OUT = pathlib.Path(__file__).resolve().parent / "BENCH_backend.json"

BATCH = ["e_total > 40 && count(pt > 15) >= 2",
         "e_total > 30 && count(pt > 15) >= 2",
         "e_t_miss > 25 && count(pt > 15) >= 2",
         "pt_lead > 60 || n_tracks >= 8",
         "e_total > 55 && sum(pt) < 400",
         "e_t_miss > 40"]


def smoke() -> bool:
    """True under the CI benchmark smoke job (tiny store, no asserts or
    snapshot writes — bit-rot detection only)."""
    return os.environ.get("BENCH_SMOKE") == "1"


def run_window(backend, store, exprs, *, ramp=None):
    """Execute one shared-scan window on ``backend``; returns
    ``(merged, stats, partials, row)`` with wall/stream metrics."""
    plan = plan_window(exprs)
    jids = [backend.catalog.submit(e, 0, tuple(sorted(store.bricks)))
            for e in exprs]
    partials = []
    t0 = time.perf_counter()
    merged, stats = backend.run_batch(jids, plan=plan,
                                      on_partial=partials.append,
                                      packet_ramp=ramp)
    wall = time.perf_counter() - t0
    t_first = partials[0].t_virtual if partials else float("nan")
    t_final = stats.makespan_s
    return merged, stats, partials, {
        "queries": len(exprs),
        "packets": stats.packets,
        "t_first_partial_s": round(t_first, 4),
        "t_final_s": round(t_final, 4),
        "ratio": round(t_first / t_final, 4) if t_final else None,
        "wall_s": round(wall, 2),
    }


def autotune_pass(store):
    """The ``(block_e, block_t)`` sweep on a real chunk of this store's
    workload: returns the snapshot section (winner + measurements +
    roofline point) and asserts the tuned shape never loses to the fixed
    default."""
    import jax.numpy as jnp

    from repro.kernels.event_filter import ops as ef_ops
    from repro.kernels.event_filter import tune as ef_tune
    from repro.service import plan_window

    plan = plan_window([e for e in BATCH
                        if ef_ops.match_epilogue(e, store.schema)])
    params = [ef_ops.match_epilogue(t, store.schema)
              for t in plan.targets()]
    thresholds, var_idx = ef_ops.batch_kernel_params(params)
    batch = store.bricks[0]
    n = min(CHUNK, batch["scalars"].shape[0])
    ef_tune.clear_cache()
    tuned = ef_tune.autotune_block_shapes(
        jnp.asarray(batch["scalars"][:n]),
        jnp.asarray(batch["tracks"][:n]),
        jnp.asarray(batch["n_tracks"][:n]),
        thresholds, var_idx=var_idx, calib_iters=0, repeats=3)
    print(f"autotune: chunk ({n} ev), K={thresholds.shape[1]} -> "
          f"({tuned.block_e}, {tuned.block_t}) at {tuned.best_ms:.2f}ms "
          f"(default {ef_tune.DEFAULT_SHAPE} at {tuned.default_ms:.2f}ms, "
          f"{tuned.speedup_vs_default:.2f}x), "
          f"{tuned.roofline['gbytes_per_s']:.2f} GB/s")
    assert tuned.speedup_vs_default >= 1.0, \
        "tuned shape lost to the fixed default shape"
    return tuned.as_dict()


def mixed_window_pass(store, ref_merged, ref_partials):
    """A mixed window (kernel + jnp targets) through the split path:
    asserts the kernel sub-batch actually ran and everything stays
    bit-identical to the pure-jnp reference with the same chunking."""
    fused = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                        chunk_events=CHUNK, use_pallas=True)
    merged, stats, partials, row = run_window(fused, store, BATCH)
    assert stats.kernel_events == N_EVENTS, \
        f"mixed window fell back to pure jnp (kernel_events=" \
        f"{stats.kernel_events})"
    for got, ref in zip(merged, ref_merged):
        assert results_identical(got, ref), "mixed-split final diverged"
    for pa, pb in zip(ref_partials, partials):
        assert all(results_identical(a, b)
                   for a, b in zip(pa.partials, pb.partials)), \
            "mixed-split partial diverged"
    row["kernel_events"] = stats.kernel_events
    print(f"mixed window: kernel_events={stats.kernel_events} "
          f"(of {N_EVENTS} scanned), finals + partials bit-identical, OK")
    return row


def mesh_scaling_pass(store):
    """SPMD final-time scaling with mesh width, on the lockstep
    critical-path clock: D=1 measures the serial per-chunk walls, D=2/4
    group the SAME compute onto an emulated mesh where each group costs
    its slowest member.  Near-linear scaling (>= 1.7x at D=2) is the
    acceptance bar; the model is honest — it is exactly the makespan a
    D-wide lockstep mesh pays for the measured per-shard compute (the
    shard_map fast path takes over when the host really has D devices)."""
    section = {}
    base_makespan = None
    for d in (1, 2, 4):
        be = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                         chunk_events=CHUNK, use_pallas=True,
                         mesh_devices=d, double_buffer=False)
        # warm the kernel dispatch for every chunk shape this run sees,
        # so group walls measure the scan, not jax compile
        _, _, _, row = run_window(be, store, BATCH)
        _, stats, _, row = run_window(
            SpmdBackend(MetadataCatalog(store.n_nodes), store,
                        chunk_events=CHUNK, use_pallas=True,
                        mesh_devices=d, double_buffer=False),
            store, BATCH)
        if base_makespan is None:
            base_makespan = row["t_final_s"]
        speedup = base_makespan / max(row["t_final_s"], 1e-9)
        section[f"mesh{d}"] = {
            "mesh_devices": d,
            "t_final_s": row["t_final_s"],
            "speedup_vs_1": round(speedup, 3),
        }
        print(f"mesh scaling: D={d} final {row['t_final_s']:.3f}s "
              f"(speedup {speedup:.2f}x)")
    if not smoke():
        assert section["mesh2"]["speedup_vs_1"] >= 1.7, \
            f"mesh D=2 speedup {section['mesh2']['speedup_vs_1']} < 1.7"
    return section


def main():
    global N_EVENTS
    args = argparse.ArgumentParser()
    args.add_argument("--autotune", action="store_true",
                      help="run only the block-shape autotune sweep")
    args = args.parse_args()
    if smoke():
        N_EVENTS = 2048
    schema = ev.EventSchema.from_config(reduced())
    store = create_store(schema, n_events=N_EVENTS, n_nodes=N_NODES,
                         events_per_brick=EVENTS_PER_BRICK,
                         replication=2, seed=17)
    print(f"workload: {N_EVENTS} events / {len(store.bricks)} bricks / "
          f"{N_NODES} nodes / chunk {CHUNK}")
    if args.autotune:
        autotune_pass(store)
        return

    # warm the jnp dispatch path OUTSIDE the timed runs — one pass per
    # chunk shape the runs will see (ramp: 16, 32; steady state: 64) —
    # so the SPMD first-partial latency measures the scan, not jax
    # per-shape warmup
    for size in (16, 32, CHUNK):
        eval_plan_slice(store, plan_window(BATCH), 0, 0, size, 0)

    sim = SimulatedBackend(MetadataCatalog(store.n_nodes), store,
                           adaptive_packets=False)
    sim.engine.packet_ramp = None
    spmd = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                       chunk_events=CHUNK)
    spmd_ramp = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                            chunk_events=CHUNK)

    rows = {}
    print("name,queries,packets,t_first_partial_s,t_final_s,ratio,wall_s")
    runs = (("sim", sim, None), ("spmd", spmd, None),
            ("spmd_ramp", spmd_ramp, 16))
    merged_by, parts_by = {}, {}
    for name, backend, ramp in runs:
        merged, stats, partials, row = run_window(backend, store, BATCH,
                                                  ramp=ramp)
        merged_by[name], parts_by[name] = merged, partials
        rows[name] = row
        print(f"{name},{row['queries']},{row['packets']},"
              f"{row['t_first_partial_s']},{row['t_final_s']},"
              f"{row['ratio']},{row['wall_s']}")

    # equivalence: spmd finals bit-identical to sim (same packetization —
    # bit-identity is a per-packetization guarantee: a different chunking
    # regroups the float sum_var additions), and partial streams
    # packet-for-packet identical.  The ramp run repacketizes, so its
    # finals must agree exactly on every decomposition-invariant field
    # (counts, histogram, id sample) and to fp tolerance on sum_var.
    for got, ref in zip(merged_by["spmd"], merged_by["sim"]):
        assert results_identical(got, ref), "spmd final diverged"
    import numpy as np
    for got, ref in zip(merged_by["spmd_ramp"], merged_by["sim"]):
        assert (got.n_selected == ref.n_selected
                and got.n_processed == ref.n_processed
                and np.array_equal(got.hist, ref.hist)
                and np.array_equal(got.selected_ids, ref.selected_ids)
                and np.isclose(got.sum_var, ref.sum_var, rtol=1e-6)), \
            "spmd_ramp final diverged"
    assert len(parts_by["sim"]) == len(parts_by["spmd"])
    for pa, pb in zip(parts_by["sim"], parts_by["spmd"]):
        assert (pa.brick_id, pa.start, pa.size) == \
               (pb.brick_id, pb.start, pb.size)
        assert all(results_identical(a, b)
                   for a, b in zip(pa.partials, pb.partials))
    print("equivalence: spmd finals + per-packet partials bit-identical "
          "to sim, OK")

    # observability no-overhead guard: one more sim window with a live
    # obs bundle attached must produce bit-identical results and the
    # exact same virtual makespan as the disabled run above (obs never
    # touches the simulation clock), and the disabled path itself is
    # just `obs is None` checks.  Runs in --smoke too (size-independent).
    from repro.obs import Observability
    sim_obs = SimulatedBackend(MetadataCatalog(store.n_nodes), store,
                               adaptive_packets=False)
    sim_obs.engine.packet_ramp = None
    sim_obs.obs = Observability(origin="bench")
    merged_o, stats_o, parts_o, row_o = run_window(sim_obs, store, BATCH)
    assert all(results_identical(a, b)
               for a, b in zip(merged_o, merged_by["sim"])), \
        "obs-enabled sim results diverged"
    assert row_o["t_final_s"] == rows["sim"]["t_final_s"], \
        "obs-enabled sim changed the virtual makespan"
    print(f"obs guard: enabled run identical "
          f"(makespan {row_o['t_final_s']}s, wall {row_o['wall_s']}s vs "
          f"disabled {rows['sim']['wall_s']}s), OK")

    # perf pass: kernel autotune, mixed-window split, mesh scaling.
    # Correctness asserts (bit-identity, kernel_events, tuned >= default)
    # run in smoke too; only the timing gate (mesh 1.7x) is full-run.
    autotune = autotune_pass(store)
    rows["spmd_mixed"] = mixed_window_pass(store, merged_by["spmd"],
                                           parts_by["spmd"])
    scaling = mesh_scaling_pass(store)

    if not smoke():
        # regression pin: disabled-path final times must stay within 2%
        # of the committed snapshot.  The sim makespan is deterministic
        # (drift there means real code change), so it hard-fails; the
        # spmd final is wall-clock on the measuring host, so cross-host
        # drift is reported but only the deterministic path gates.
        if OUT.exists():
            old = json.loads(OUT.read_text())
            if old.get("config", {}).get("n_events") == N_EVENTS:
                for name in ("sim", "spmd"):
                    prev = old["rows"][name]["t_final_s"]
                    cur = rows[name]["t_final_s"]
                    drift = abs(cur - prev) / max(prev, 1e-9)
                    if name == "sim":
                        assert drift < 0.02, \
                            f"sim final time drifted {drift:.1%} vs " \
                            f"BENCH_backend.json (obs-disabled path " \
                            f"overhead?)"
                    print(f"obs guard: {name} final time drift vs "
                          f"snapshot {drift:.1%} "
                          f"({'gated <2%' if name == 'sim' else 'host-dependent, informational'})")
        for name in ("spmd", "spmd_ramp"):
            r = rows[name]
            assert r["ratio"] <= 0.5, \
                f"{name}: first partial at {r['ratio']:.2f}x of final " \
                f"(need <= 0.5)"
        assert (rows["spmd_ramp"]["t_first_partial_s"]
                <= rows["spmd"]["t_first_partial_s"] * 1.05), \
            "packet ramp regressed SPMD time-to-first-partial"
        print(f"spmd streaming: first partial at "
              f"{rows['spmd']['ratio']:.3f}x of final "
              f"(ramp {rows['spmd_ramp']['ratio']:.3f}x), OK")
        OUT.write_text(json.dumps({
            "bench": "backend",
            "config": {"n_events": N_EVENTS, "n_nodes": N_NODES,
                       "events_per_brick": EVENTS_PER_BRICK,
                       "chunk_events": CHUNK, "ramp_start": 16,
                       "replication": 2, "queries": len(BATCH)},
            "rows": rows,
            "autotune": autotune,
            "scaling": scaling,
        }, indent=2) + "\n")
        print(f"snapshot written: {OUT.name}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
