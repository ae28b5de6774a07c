"""Straggler mitigation (PROOF rule, paper related-work + future-work):
one node runs at 0.2x speed; compare makespan with fixed uniform packets
vs throughput-adaptive packets (slower slaves get smaller packets; the
fast nodes steal the remaining work).

Each run executes with the observability plane attached, so the report
includes the per-packet virtual-latency histogram straight from the
metrics registry — the adaptive run's distribution visibly loses the
straggler's fat tail.

A second pass measures the failure policy's *speculative re-execution*
(``service/policy.py``): with fixed packets and an extreme straggler,
time-to-final (the virtual stamp of the LAST partial — honest in both
modes, unlike the default-path makespan which does not charge undelivered
tails) is compared with speculation on vs off over a straggler-speed
grid.  Outside smoke mode the p99 of the per-config ratio must be <=
0.7 and everything is committed as ``BENCH_straggler.json``."""
from __future__ import annotations

import json
import os
import pathlib

from repro.configs.geps_events import reduced
from repro.core import events as ev
from repro.core.brick import create_store
from repro.core.catalog import MetadataCatalog
from repro.core.jse import JobSubmissionEngine, TimeModel
from repro.obs import Observability

EXPR = "e_total > 40"
OUT = pathlib.Path(__file__).resolve().parent / "BENCH_straggler.json"


def run(adaptive: bool, straggler_speed=0.2, n_events=4096, n_nodes=4):
    schema = ev.EventSchema.from_config(reduced())
    store = create_store(schema, n_events=n_events, n_nodes=n_nodes,
                         events_per_brick=256, replication=2, seed=3)
    speeds = {n: 1.0 for n in range(n_nodes)}
    speeds[1] = straggler_speed
    cat = MetadataCatalog(n_nodes)
    for n, s in speeds.items():
        cat.node(n).throughput_ema = s
    jse = JobSubmissionEngine(cat, store, TimeModel(), node_speed=speeds,
                              adaptive_packets=adaptive)
    obs = Observability(origin="bench")
    jse.obs = obs
    jid = jse.submit(EXPR)
    merged, stats = jse.run_job_simulated(jid)
    return stats.makespan_s, merged.n_selected, obs


def packet_latency(obs):
    """Per-packet *virtual* latency histogram for the run, derived from
    the packet spans (the wall-clock ``packet.latency_s`` histogram also
    exists but measures this host, not the simulated grid).  Returns the
    registry histogram and the max latency."""
    durs = [r["t1_virtual"] - r["t0_virtual"]
            for r in obs.tracer.records() if r["name"] == "packet"]
    hist = obs.metrics.histogram("packet.latency_virtual_s")
    for d in durs:
        hist.observe(d)
    return hist, (max(durs) if durs else 0.0)


def run_speculative(speculate: bool, straggler_speed: float,
                    n_events=2048, n_nodes=4, seed=3):
    """Time-to-final for one fixed-packet run with an extreme straggler:
    the virtual stamp of the last delivered partial (comparable across
    speculation modes), plus the engine's speculation counters."""
    schema = ev.EventSchema.from_config(reduced())
    store = create_store(schema, n_events=n_events, n_nodes=n_nodes,
                         events_per_brick=256, replication=2, seed=seed)
    speeds = {n: 1.0 for n in range(n_nodes)}
    speeds[1] = straggler_speed
    cat = MetadataCatalog(n_nodes)
    jse = JobSubmissionEngine(cat, store, TimeModel(), node_speed=speeds,
                              adaptive_packets=False)
    jid = jse.submit(EXPR)
    stamps = []
    merged, stats = jse.run_job_batch_simulated(
        [jid], on_partial=lambda p: stamps.append(p.t_virtual),
        speculate=speculate)
    return max(stamps), merged[0].n_selected, stats


def speculation_grid(n_events):
    """Per-straggler-speed spec/no-spec time-to-final ratios (results
    asserted identical pairwise)."""
    # extreme stragglers (2-3.5% speed): speculation can only launch once
    # a fast node drains the queue, so its time-to-final floors at
    # drain + one duplicate — the win is the straggler tail ABOVE that
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    speeds = [0.03] if smoke else [0.02, 0.025, 0.03, 0.035]
    seeds = [3] if smoke else [3, 5, 11]
    rows = []
    for speed in speeds:
        for seed in seeds:
            plain, sel_p, _ = run_speculative(False, speed,
                                              n_events=n_events, seed=seed)
            spec, sel_s, stats = run_speculative(True, speed,
                                                 n_events=n_events,
                                                 seed=seed)
            assert sel_p == sel_s, "speculation must not change results"
            rows.append({"straggler_speed": speed, "seed": seed,
                         "time_to_final_s": round(plain, 4),
                         "time_to_final_spec_s": round(spec, 4),
                         "speculated": stats.speculated,
                         "spec_wins": stats.spec_wins,
                         "ratio": round(spec / plain, 4)})
    return rows


def main():
    n_ev = 1024 if os.environ.get("BENCH_SMOKE") == "1" else 4096
    fixed, sel_f, obs_f = run(adaptive=False, n_events=n_ev)
    adap, sel_a, obs_a = run(adaptive=True, n_events=n_ev)
    assert sel_f == sel_a, "mitigation must not change results"
    hist_f, max_f = packet_latency(obs_f)
    hist_a, max_a = packet_latency(obs_a)
    print("mode,makespan_s,packets,max_packet_latency_s")
    print(f"fixed,{fixed:.3f},{hist_f.count},{max_f:.3f}")
    print(f"adaptive,{adap:.3f},{hist_a.count},{max_a:.3f}")
    print(f"# straggler mitigation speedup: {fixed / adap:.2f}x")

    spec_rows = speculation_grid(min(n_ev, 2048))
    ratios = sorted(r["ratio"] for r in spec_rows)
    p99 = ratios[min(len(ratios) - 1, int(0.99 * len(ratios)))]
    print("speculation: straggler_speed,seed,time_to_final_s,"
          "with_speculation_s,ratio,wins")
    for r in spec_rows:
        print(f"spec,{r['straggler_speed']},{r['seed']},"
              f"{r['time_to_final_s']},{r['time_to_final_spec_s']},"
              f"{r['ratio']},{r['spec_wins']}")
    print(f"# speculative re-execution p99 time-to-final ratio: {p99:.3f}")
    if os.environ.get("BENCH_SMOKE") != "1":
        assert p99 <= 0.7, (
            f"speculation must cut p99 time-to-final to <=0.7x (got {p99})")
        OUT.write_text(json.dumps({
            "bench": "straggler",
            "config": {"n_events": n_ev, "n_nodes": 4,
                       "straggler_speed": 0.2, "expr": EXPR},
            "rows": {
                name: {"makespan_s": round(mk, 4),
                       "packet_latency_virtual_s": h.to_dict()}
                for name, mk, h in (("fixed", fixed, hist_f),
                                    ("adaptive", adap, hist_a))},
            "speedup": round(fixed / adap, 3),
            "speculation": {"rows": spec_rows,
                            "p99_ratio": round(p99, 4)},
        }, indent=2) + "\n")
        print(f"snapshot written: {OUT.name}")
    return fixed, adap


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
