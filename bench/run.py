"""Chip benchmark of the brick-store query service: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order, the run:

1. finds the chips the cell asks for, or exits non-zero and prints no
   result line;
2. keeps JAX's persistent compilation cache in ``.jax_cache/`` inside
   this checkout, and caches every program there;
3. generates the cell's brick store on the device from ``--seed`` and
   hands the program host copies of the bricks, as it keeps them;
4. warms up on a throw-away ``QueryService`` over one brick of each
   size, with the family's queries drawn from another seed, at every
   window width the cell's loop can form;
5. drives a fresh ``QueryService`` (SPMD backend built with the
   configuration's ``backend_kwargs``) with the cell's loop
   (``bench/loops/<kind>.py``) for ``--seconds``, then drains it; with
   ``--trace 1`` under the profiler;
6. reads the device's peak memory, frees the service, and compares
   every final with the plain reference (``bench/reference.py``);
7. prints the numbers compared, each beside its limit, as the last lines
   of standard error, and one JSON object as the last line of standard
   output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
   cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
   ``device``, with ``--trace 1`` a ``breakdown``, and ``checks`` last.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# the script's own directory would shadow the standard library (trace.py)
if sys.path and Path(sys.path[0]).resolve() == BENCH_DIR:
    sys.path.pop(0)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
# the TPU runtime would otherwise log under a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

#: Compile cache inside the checkout, at a fixed path.
CACHE_DIR = ROOT / ".jax_cache"
#: Where a traced run's profile is written, and removed once reduced.
TRACE_DIR = ROOT / ".bench_trace"
#: Mixed into the seed for the warm-up's queries, so they differ from the
#: window's.
WARM_SEED_TAG = 0x5EED


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def host_peak_gib() -> float:
    """The process's peak resident set size on the host so far, as the
    kernel reports it (``ru_maxrss``)."""
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX has {len(devices)}")
    return devices


def enable_cache() -> None:
    """The program's own cache set-up, pointed at this checkout, with
    every compiled program kept (the program's floor drops its kernels,
    which compile in under half a second)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def new_service(store, cfg: dict):
    from repro.service import QueryService
    return QueryService(store, backend="spmd",
                        backend_kwargs=dict(cfg["backend_kwargs"]))


def warm_up(store, cfg: dict, workload: dict, family, loop, seed: int
            ) -> int:
    """Scan one brick of each size at every window width the cell's loop
    can form; returns the number of windows run."""
    import numpy as np
    from bench import store as store_lib
    from bench import traffic
    sub = store_lib.sub_store(store, store_lib.shape_bricks(store))
    svc = new_service(sub, cfg)
    widths = loop.widths(workload["loop"], svc.scheduler.max_batch)
    queries = traffic.draw_queries(
        workload, family, np.random.SeedSequence([int(seed), WARM_SEED_TAG]),
        sum(widths))
    i = 0
    for k in widths:
        for q in queries[i:i + k]:
            svc.submit(q.expr, tenant=q.tenant,
                       calib_iters=cfg["calib_iters"], stream=True)
        i += k
        svc.step()
    svc.close()
    return len(widths)


def run_window(svc, cfg: dict, workload: dict, family, loop, seed: int,
               seconds: float, compiles):
    import numpy as np
    return loop.run(svc, workload, family, np.random.SeedSequence(int(seed)),
                    seconds, cfg["calib_iters"], compiles)


def _same(a, b) -> bool:
    import numpy as np
    return (a.n_selected == b.n_selected and a.n_processed == b.n_processed
            and a.sum_var == b.sum_var and np.array_equal(a.hist, b.hist)
            and np.array_equal(a.selected_ids, b.selected_ids))


def collect_finals(svc, window) -> dict:
    """``ticket -> (status, result or None)``; a result counts only when
    the ticket is served and its stream closed on the same final."""
    out = {}
    for rec in window.tickets:
        t = svc.result(rec.ticket)
        res = t.result if t.status == "SERVED" else None
        snap = svc.stream(rec.ticket).latest()
        if res is not None and not (snap is not None and snap.final
                                    and _same(snap.result, res)):
            res = None
        out[rec.ticket] = (t.status, res)
    return out


@dataclasses.dataclass
class Final:
    """What the comparison reads of a final."""
    n_selected: int
    n_processed: int
    sum_var: float
    hist: object
    selected_ids: object


def control_finals(store, window, finals: dict, family, calib_iters: int,
                   dtype: str = "bfloat16") -> dict:
    """The control: every served final replaced by the plain reference's
    own decision computed in ``dtype``, the precision below the
    configuration's float32."""
    from bench import reference
    served = [r for r in window.tickets if finals[r.ticket][1] is not None]
    low = reference.evaluate(store, family, [r.query.params for r in served],
                             calib_iters, dtype)
    out = dict(finals)
    for r, s in zip(served, low):
        k = s.keep
        out[r.ticket] = (finals[r.ticket][0], Final(
            k.n, s.n_processed, k.sum_lo, k.hist, k.ids))
    return out


def compare(store, window, finals: dict, family, calib_iters: int,
            limits: dict) -> dict:
    """The numbers that decide ``correct``, each with its limit."""
    from bench import reference
    served = [r for r in window.tickets if finals[r.ticket][0] != "REJECTED"]
    distinct = {}
    for r in served:
        distinct.setdefault(r.query.expr, r.query.params)
    exprs = list(distinct)
    want = dict(zip(exprs, reference.evaluate(
        store, family, [distinct[e] for e in exprs], calib_iters)))
    scale = reference.value_scale(store)
    bad, selection, off = 0, 0, 0.0
    for r in served:
        res = finals[r.ticket][1]
        if res is None or res.n_processed != store.n_events:
            bad += 1
            continue
        sel, gap = reference.gaps(res.n_selected, res.sum_var, res.hist,
                                  res.selected_ids, want[r.query.expr], scale)
        selection, off = max(selection, sel), max(off, gap)
    unsure = sum(w.unsure.n for w in want.values())
    log(f"reference: {len(exprs)} distinct queries, {unsure} events "
        f"within rounding of a cut")
    return {"bad_finals": {"value": bad, "limit": limits["bad_finals"]},
            "selection_off": {"value": selection,
                              "limit": limits["selection_off"]},
            "sum_off": {"value": off, "limit": limits["sum_off"]}}


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             bench_dir: Path = BENCH_DIR, need_chip: bool = True,
             control: bool = False) -> dict:
    """One run of one cell; returns the result line's object.  With
    ``control`` the served finals are replaced by the control's
    (``control_finals``) before the comparison."""
    from bench import loader
    workload = loader.workload(cell, bench_dir)
    cfg = loader.config(workload["config"], bench_dir)
    family = loader.family(workload["family"], bench_dir)
    loop = loader.loop(workload["loop"]["kind"], bench_dir)
    try:
        bench = loader.benchmark(bench_dir)
    except FileNotFoundError:
        bench = None
    if need_chip:
        devices = require_chips(workload["chips"])
    import jax
    if not need_chip:
        devices = jax.devices()
    enable_cache()
    from bench import drive, measure, reference
    from bench import store as store_lib
    from bench import trace as trace_lib
    compiles = drive.CompileCounter()

    t = time.perf_counter()
    store = store_lib.build_store(cfg, seed)
    log(f"store: {store.n_events} events in {len(store.bricks)} bricks, "
        f"built in {time.perf_counter() - t:.3f} s; peak RSS "
        f"{host_peak_gib():.2f} GiB")
    t, c0 = time.perf_counter(), compiles.seconds
    n_warm = warm_up(store, cfg, workload, family, loop, seed)
    log(f"warm-up: {n_warm} windows in {time.perf_counter() - t:.3f} s, "
        f"compile {compiles.seconds - c0:.3f} s; peak RSS "
        f"{host_peak_gib():.2f} GiB")
    svc = new_service(store, cfg)
    # what set-up left behind stays out of the window's collections
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_PROCESS
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # the harness's spans stand in for the host trace (bench/trace.py)
        opts.host_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    t_trace = time.perf_counter()
    gc_clock = drive.GcClock()
    window = run_window(svc, cfg, workload, family, loop, seed, seconds,
                        compiles)
    gc_clock.close()
    reduction = None
    if trace:
        traced_s = time.perf_counter() - t_trace
        jax.profiler.stop_trace()
        reduction = trace_lib.reduce_trace(
            str(TRACE_DIR), drive.SPANS, window_s=traced_s,
            spans=window.spans, origin_ns=window.origin_ns)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    stats = devices[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:workload["chips"]])
    log(f"window: {len(window.tickets)} tickets, {len(window.steps)} steps, "
        f"last final at {window.t_end:.3f} s, generator late by at most "
        f"{window.late_s:.3f} s, {window.compiles} compiles inside, "
        f"device peak {peak} of {stats.get('bytes_limit', 0)} bytes; peak "
        f"RSS {host_peak_gib():.2f} GiB")
    walls = sorted(s.t_end - s.t_start for s in window.steps)
    if walls:
        log(f"steps: wall min {walls[0]:.3f} median "
            f"{walls[len(walls) // 2]:.3f} max {walls[-1]:.3f} s, queries "
            f"per step {[s.jobs_run for s in window.steps]}; garbage "
            f"collection paused {gc_clock.seconds:.3f} s (longest "
            f"{gc_clock.longest:.3f} s)")

    finals = collect_finals(svc, window)
    svc.close()
    del svc
    gc.collect()
    t = time.perf_counter()
    if control:
        finals = control_finals(store, window, finals, family,
                                cfg["calib_iters"])
    limits = workload["limits"]
    checks = compare(store, window, finals, family, cfg["calib_iters"],
                     limits)
    log(f"reference: {time.perf_counter() - t:.3f} s; peak RSS "
        f"{host_peak_gib():.2f} GiB")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    attempted = len(window.tickets)
    failed = sum(1 for r in window.tickets
                 if finals[r.ticket][0] != "SERVED")

    metrics = {}
    if not trace:
        names = ([m["name"] for m in loader.cell_metrics(
            bench, cell, "end_to_end")] if bench else list(measure.END_TO_END))
        units = ({m["name"]: m["unit"] for m in bench["end_to_end"]}
                 if bench else {})
        for name in names:
            metrics[name] = {"value": measure.END_TO_END[name](window,
                                                               setup_s),
                             "unit": units.get(name, "")}
    else:
        view = measure.RunView(
            window, reduction, family, measure.StoreShape.of(store),
            cfg["calib_iters"],
            measure.peaks(devices[0].device_kind) if need_chip else None)
        entries = (loader.cell_metrics(bench, cell, "per_layer") if bench
                   else [])
        for m in entries:
            value = loader.layer_metric(m["name"], bench_dir).read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        out["breakdown"] = {"device_ops": reduction.top_ops(10),
                            "idle_gaps": reduction.idle_gaps(10)}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
