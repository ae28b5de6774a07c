"""The knee of an open-loop cell: the highest offered rate the served path
sustains, on every seed tried, with no rejection and no growing queue.

    python bench/sweep.py --workload <cell> --seeds <n> [<n> ...] \\
        --seconds <s> --rates <r> [<r> ...]

One process builds the cell's store and warms it up once, then runs one
window per rate and seed, each on a fresh ``QueryService``, and prints one
JSON line for each: tickets rejected, the queue (tickets due and not yet
in a started window) averaged over each quarter of the window, the
windows that met the scheduler's cap, and the window's latencies.  The
queue is judged over the whole window, not at single instants: a rate is
sustained on a seed when nothing was rejected, no window met the cap
(the queue never outran a window), and the last quarter's mean queue
exceeds the first half's by less than one window's arrivals (the rate
times the median window), the most a queue that does not grow moves
with the phase of its windows.  A last line per rate says whether every
seed sustained it.  The cell file then takes 0.8 x the knee as a number;
the benchmark's runs never sweep.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
if sys.path and Path(sys.path[0]).resolve() == BENCH_DIR:
    sys.path.pop(0)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

def mean_queue(window, lo: float, hi: float) -> float:
    """Tickets queued (due, their window not started), averaged over
    ``[lo, hi)`` seconds of the window."""
    started = {tid: s.t_start for s in window.steps for tid in s.tickets}
    total = 0.0
    for r in window.tickets:
        end = started.get(r.ticket, window.t_end)
        total += max(0.0, min(end, hi) - max(r.t_start, lo))
    return total / (hi - lo)


def judge(window, seconds: float, max_batch: int, rate: float) -> dict:
    quarters = [mean_queue(window, seconds * i / 4, seconds * (i + 1) / 4)
                for i in range(4)]
    rejected = sum(1 for r in window.tickets if r.status == "REJECTED")
    capped = sum(1 for s in window.steps if s.jobs_run >= max_batch)
    step_s = statistics.median([s.t_end - s.t_start for s in window.steps])
    growth = quarters[3] - (quarters[0] + quarters[1]) / 2
    return {"rejected": rejected, "capped_windows": capped,
            "queue_by_quarter": [round(q, 3) for q in quarters],
            "sustained": rejected == 0 and capped == 0
            and growth < rate * step_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import drive, loader, measure
    from bench import run as run_lib
    from bench import store as store_lib
    workload = loader.workload(args.workload)
    if "rate_per_s" not in workload["loop"]:
        print("sweep: only an open loop has an offered rate", file=sys.stderr)
        return 2
    cfg = loader.config(workload["config"])
    family = loader.family(workload["family"])
    loop = loader.loop(workload["loop"]["kind"])
    try:
        run_lib.require_chips(workload["chips"])
    except run_lib.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    run_lib.enable_cache()
    compiles = drive.CompileCounter()
    store = store_lib.build_store(cfg, args.seeds[0])
    run_lib.warm_up(store, cfg, workload, family, loop, args.seeds[0])
    for rate in args.rates:
        wl = dict(workload, loop=dict(workload["loop"], rate_per_s=rate))
        verdicts, tails = [], []
        for seed in args.seeds:
            svc = run_lib.new_service(store, cfg)
            w = run_lib.run_window(svc, cfg, wl, family, loop, seed,
                                   args.seconds, compiles)
            verdict = judge(w, args.seconds, svc.scheduler.max_batch, rate)
            verdicts.append(verdict["sustained"])
            walls = [s.t_end - s.t_start for s in w.steps]
            line = {
                "rate_per_s": rate, "seed": seed, "tickets": len(w.tickets),
                **verdict, "windows": len(w.steps),
                "step_wall_median_s": statistics.median(walls),
                "step_wall_max_s": max(walls),
                "queries_per_window": sum(s.jobs_run for s in w.steps)
                / max(1, sum(s.batches for s in w.steps)),
                "late_s": w.late_s, "compiles": w.compiles}
            for name in ("ttfp_p95_s", "ttf_p50_s", "ttf_p95_s"):
                line[name] = measure.END_TO_END[name](w, 0.0)
            tails.append(line["ttf_p95_s"])
            print(json.dumps(line), flush=True)
            svc.close()
            del svc
            gc.collect()
        print(json.dumps({"rate_per_s": rate, "sustained_on_every_seed":
                          all(verdicts), "ttf_p95_s_by_seed": tails}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
