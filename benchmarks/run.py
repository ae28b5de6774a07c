"""Benchmark harness: one entry per paper table/figure.  Prints ``name,value,derived`` CSV blocks.

  crossover    - paper Fig 7 (single node vs grid-brick parallel)
  granularity  - paper section 6 packet-size effect
  straggler    - PROOF-style adaptive packets vs fixed + the failure
                 policy's speculative re-execution pass (p99 time-to-final
                 ratio; BENCH_straggler.json)
  failover     - node death with/without replication (paper future work)
                 + failure-policy pass: seeded evidence bans the sick
                 node, zero packets route to it, bricks re-replicate
  multiquery   - K-query shared scan vs one-job-at-a-time + cache hits
  planner      - common-subexpression factoring on near-duplicate queries
  streaming    - time-to-first-partial vs time-to-final (progressive
                 delivery incl. the stream-aware packet ramp; writes the
                 BENCH_streaming.json snapshot)
  fabric       - fleet shared-L2 hit rate, cross-frontend first-result
                 latency, registry pre-warming (BENCH_fabric.json)
  query_spmd   - SPMD grid-brick query step micro-benchmark (real compute)

Everything runs in this one process, and a failing phase fails the run.
These are CPU reproductions of the paper's claims, not speed: the chip
benchmark is ``bench/`` (``BENCHMARK.json``).

``--smoke`` runs every bench in a tiny configuration with perf asserts
and snapshot writes disabled — the CI job that keeps benchmarks from
bit-rotting between measurement sessions.
"""
from __future__ import annotations

import argparse
import os
import time


def _section(name):
    print(f"\n## {name}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, no perf asserts, no snapshot writes "
                         "(CI bit-rot gate)")
    args = ap.parse_args(argv)
    if args.smoke:
        os.environ["BENCH_SMOKE"] = "1"
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")

    _section("crossover (paper Fig 7)")
    from benchmarks import bench_crossover
    bench_crossover.main()

    _section("granularity (paper section 6)")
    from benchmarks import bench_granularity
    bench_granularity.main()

    _section("straggler mitigation (PROOF rule)")
    from benchmarks import bench_straggler
    bench_straggler.main()

    _section("failover (paper future work)")
    from benchmarks import bench_failover
    bench_failover.main()

    _section("multi-query shared scan + result cache (service)")
    from benchmarks import bench_multiquery
    bench_multiquery.main()

    _section("shared-aggregate planner (fragment factoring)")
    from benchmarks import bench_planner
    bench_planner.main()

    _section("streaming partial-merge delivery (progressive histograms)")
    from benchmarks import bench_streaming
    bench_streaming.main()

    _section("coherence fabric (fleet cache tier + registry)")
    from benchmarks import bench_fabric
    bench_fabric.main()

    import jax
    import jax.numpy as jnp
    from repro.configs.geps_events import reduced
    from repro.core import events as ev
    from repro.core.brick import create_store, gather_store
    from repro.core.jse import spmd_query_step
    from repro.kernels import resolve_interpret

    _section(f"spmd query step (grid-brick job, wall time on "
             f"{jax.devices()[0].device_kind})")

    schema = ev.EventSchema.from_config(reduced())
    store = create_store(schema, n_events=1024 if args.smoke else 4096,
                         n_nodes=4, events_per_brick=256, replication=2,
                         seed=5)
    batch = {k: jnp.asarray(v) for k, v in gather_store(store).items()}
    pallas_label = ("pallas_interpret" if resolve_interpret(None)
                    else "pallas")
    for use_pallas in (False, True):
        step = jax.jit(spmd_query_step(
            "e_total > 40 && count(pt > 15) >= 2", schema, calib_iters=4,
            use_pallas=use_pallas))
        out = step(batch)  # compile + run
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(5):
            out = step(batch)
            jax.block_until_ready(out)
        us = (time.perf_counter() - t0) / 5 * 1e6
        label = pallas_label if use_pallas else "xla"
        print(f"query_spmd_{label},{us:.0f}us_per_call,"
              f"selected={int(out['n_selected'])}")

    print("\nall benchmarks done")


if __name__ == "__main__":
    main()
