"""Paper section 6: "different granularities of event data will
dramatically affect the overall performance" — sweep the packet size and
report makespan on a heterogeneous 4-node grid.

Small packets: per-packet dispatch latency dominates.  Huge packets: load
imbalance dominates (one straggling packet holds the job).  The adaptive
scheduler should land near the hand-tuned optimum without tuning.
"""
from __future__ import annotations

from repro.configs.geps_events import reduced
from repro.core import events as ev
from repro.core.brick import create_store
from repro.core.catalog import MetadataCatalog
from repro.core.jse import JobSubmissionEngine, TimeModel

EXPR = "e_total > 40"
SPEEDS = {0: 1.0, 1: 1.0, 2: 0.5, 3: 2.0}  # heterogeneous nodes


def run_one(packet: int, adaptive: bool, n_events=4096, n_nodes=4):
    cfgE = reduced()
    schema = ev.EventSchema.from_config(cfgE)
    store = create_store(schema, n_events=n_events, n_nodes=n_nodes,
                         events_per_brick=256, replication=2, seed=2)
    cat = MetadataCatalog(n_nodes)
    for n, s in SPEEDS.items():
        cat.node(n).throughput_ema = s
    jse = JobSubmissionEngine(cat, store, TimeModel(), node_speed=SPEEDS,
                              adaptive_packets=adaptive)
    jid = jse.submit(EXPR)
    merged, stats = jse.run_job_simulated(jid)
    # patch scheduler base packet by re-running with the size
    return stats.makespan_s, merged.n_selected


def main():
    import os
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    n_ev = 1024 if smoke else 4096
    sizes = (8, 128, 512) if smoke else (8, 32, 128, 512, 2048)
    print("packet_size,adaptive,makespan_s")
    results = {}
    for packet in sizes:
        cfgE = reduced()
        schema = ev.EventSchema.from_config(cfgE)
        store = create_store(schema, n_events=n_ev, n_nodes=4,
                             events_per_brick=256, replication=2, seed=2)
        cat = MetadataCatalog(4)
        for n, s in SPEEDS.items():
            cat.node(n).throughput_ema = s
        jse = JobSubmissionEngine(cat, store, TimeModel(), node_speed=SPEEDS,
                                  adaptive_packets=False)
        jse_sched_packet = packet

        # monkey-level configuration: fixed packet size
        from repro.core.packets import AdaptivePacketScheduler
        orig_init = AdaptivePacketScheduler.__init__

        def patched(self, catalog, **kw):
            kw.update(base_packet=jse_sched_packet,
                      min_packet=jse_sched_packet,
                      max_packet=jse_sched_packet)
            orig_init(self, catalog, **kw)

        AdaptivePacketScheduler.__init__ = patched
        try:
            jid = jse.submit(EXPR)
            _, stats = jse.run_job_simulated(jid)
        finally:
            AdaptivePacketScheduler.__init__ = orig_init
        results[packet] = stats.makespan_s
        print(f"{packet},fixed,{stats.makespan_s:.3f}")

    # adaptive run
    store = create_store(
        ev.EventSchema.from_config(reduced()), n_events=n_ev, n_nodes=4,
        events_per_brick=256, replication=2, seed=2)
    cat = MetadataCatalog(4)
    for n, s in SPEEDS.items():
        cat.node(n).throughput_ema = s
    jse = JobSubmissionEngine(cat, store, TimeModel(), node_speed=SPEEDS,
                              adaptive_packets=True)
    jid = jse.submit(EXPR)
    _, stats = jse.run_job_simulated(jid)
    print(f"adaptive,adaptive,{stats.makespan_s:.3f}")
    best_fixed = min(results.values())
    print(f"# adaptive vs best fixed: {stats.makespan_s:.3f} vs "
          f"{best_fixed:.3f}")
    return results, stats.makespan_s


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
