"""Paper Fig 7: processing time, single node ("hobbit") vs grid-brick
parallel (GEPS), as a function of raw-event-file size.

The paper observed a watershed at ~2000 events on its fast-Ethernet
two-node grid: below it, the tightly-coupled single node wins (executable
staging + dispatch + result transfer dominate); above it, parallel brick
processing wins.  We reproduce with the virtual-time grid simulation
(REAL numpy compute per packet, modeled network/staging costs calibrated
to the paper's setup) and report the measured crossover.
"""
from __future__ import annotations

import time

import numpy as np

from repro.configs.geps_events import reduced
from repro.core import events as ev
from repro.core.brick import create_store
from repro.core.catalog import MetadataCatalog
from repro.core.jse import JobSubmissionEngine, TimeModel

EXPR = "e_total > 40 && count(pt > 15) >= 1"


def run(n_nodes: int = 2, sizes=(250, 500, 1000, 2000, 4000, 8000)):
    cfgE = reduced()
    schema = ev.EventSchema.from_config(cfgE)
    rows = []
    crossover = None
    prev = None
    for n_events in sizes:
        store = create_store(schema, n_events=n_events, n_nodes=n_nodes,
                             events_per_brick=max(64, n_events // 16),
                             replication=2, seed=1)
        cat = MetadataCatalog(n_nodes)
        jse = JobSubmissionEngine(cat, store, TimeModel())
        jid = jse.submit(EXPR)
        t0 = time.perf_counter()
        merged, stats = jse.run_job_simulated(jid)
        wall = time.perf_counter() - t0
        single = jse.single_node_time(n_events)
        rows.append({
            "n_events": n_events,
            "geps_parallel_s": stats.makespan_s,
            "single_node_s": single,
            "speedup": single / stats.makespan_s,
            "selected": merged.n_selected,
            "host_wall_s": wall,
        })
        if prev is not None and crossover is None:
            if rows[-1]["speedup"] >= 1.0 > prev["speedup"]:
                # linear interpolation between the two sizes
                x0, x1 = prev["n_events"], n_events
                y0, y1 = prev["speedup"], rows[-1]["speedup"]
                crossover = x0 + (1.0 - y0) * (x1 - x0) / (y1 - y0)
        prev = rows[-1]
    return rows, crossover


def main():
    import os
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    # smoke: fewer sizes (still spanning the watershed), no bound assert
    rows, crossover = run(sizes=(250, 1000, 2000, 4000)) if smoke else run()
    print("n_events,geps_parallel_s,single_node_s,speedup,selected")
    for r in rows:
        print(f"{r['n_events']},{r['geps_parallel_s']:.3f},"
              f"{r['single_node_s']:.3f},{r['speedup']:.3f},{r['selected']}")
    if crossover is not None:
        print(f"# crossover (watershed) ~ {crossover:.0f} events "
              f"(paper section 6: ~2000)")
    if not smoke:
        assert crossover is not None and 500 < crossover < 4000, crossover
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
