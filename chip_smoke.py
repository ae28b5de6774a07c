"""Smoke run of the brick-store query service on a TPU, at the paper's width.

Builds the paper's deployment (``configs/geps_events.CONFIG``: 64 scalars,
4096 tracks x 63 f32 variables per event, 4 calibration passes, bricks of
256 events, replication 2) with 4096 events over 4 grid nodes, generated
from ``--seed``.  Then it drives the served path the way
``serve --mode query --backend spmd --use-pallas`` does: streamed
multi-tenant submissions through ``QueryService`` on the SPMD backend with
the fused Pallas kernel on, over three dispatch windows that mix
kernel-family queries, out-of-family queries and one repeat the L1 cache
answers.  Every final is compared with a plain oracle (``compile_query``
on ``calibrate``d tracks, brick by brick on the device) field by field.

    python chip_smoke.py              # one chip: the served path
    python chip_smoke.py --chips 4    # the 4-device shard_map mesh path,
                                      # compared with 1 device, nothing else

Everything runs in this one process.  The times printed are those of a
smoke run, not benchmark metrics.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
when JAX finds no TPU, when Pallas would run in interpret mode, or when
any phase fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs.geps_events import CONFIG  # noqa: E402
from repro.core import merge as merge_lib  # noqa: E402

N_EVENTS = 4096
N_NODES = 4
CHUNK_EVENTS = 64
TENANTS = ("atlas-higgs", "atlas-susy", "cms-top", "lhcb-b")

#: The query that window 3 repeats (answered by the L1 result cache).
REPEAT = "e_total > 40 && count(pt > 15) >= 2"

#: Three dispatch windows.  Kernel-family queries are
#: ``<scalar> > A && count(pt > B) >= C [&& sum(pt) < D]``; the ``||`` and
#: ``n_tracks`` queries are outside it, so each window is split between the
#: kernel and the jnp path.
WINDOWS = (
    (REPEAT,
     "e_total > 40 && count(pt > 15) >= 2 && sum(pt) < 20000",
     "pt_lead > 60 || n_tracks >= 8",
     "e_t_miss > 30 && count(pt > 60) >= 8"),
    ("m_inv > 25 && count(pt > 40) >= 20 && sum(pt) < 30000",
     "n_jets > 10 && count(pt > 60) >= 8",
     "e_total > 80 || count(pt > 30) >= 100",
     "eta_lead > 50 && count(pt > 25) >= 64"),
    (REPEAT,
     "phi_lead > 20 && count(pt > 50) >= 16 && sum(pt) < 15000",
     "pt_lead > 60 && n_tracks >= 2048",
     "n_leptons > 45 && count(pt > 15) >= 500"),
)


class SmokeFailure(RuntimeError):
    """A phase of the smoke run failed."""


def require_tpu(n_chips: int):
    """The devices, or a failure when JAX has no TPU, fewer than
    ``n_chips`` of them, or would interpret the Pallas kernels."""
    import jax
    from repro.kernels import resolve_interpret
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < n_chips:
        raise SmokeFailure(f"need {n_chips} TPU devices, JAX has "
                           f"{len(devices)}")
    if resolve_interpret(None):
        raise SmokeFailure("Pallas kernels would run in interpret mode "
                           "(REPRO_INTERPRET forces it)")
    return devices


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def build_store(seed: int, n_events: int = N_EVENTS, cfg=CONFIG):
    from repro.core.brick import store_from_config
    t0 = time.perf_counter()
    store = store_from_config(cfg, n_events=n_events, n_nodes=N_NODES,
                              seed=seed)
    nbytes = sum(a.nbytes for b in store.bricks.values() for a in b.values())
    print(f"store: {store.n_events} events x (scalars {cfg.n_scalars}, "
          f"tracks {cfg.max_tracks} x {cfg.track_vars}) f32, "
          f"{len(store.bricks)} bricks of {cfg.events_per_brick}, "
          f"replication {cfg.replication_factor}, {store.n_nodes} nodes, "
          f"{nbytes} bytes, built in {time.perf_counter() - t0:.2f} s "
          f"(seed {seed})")
    return store


def oracle(store, exprs, calib_iters: int, chunk: int):
    """``expr -> QueryResult`` by the plain path: each brick moved to the
    device whole, ``calibrate``d, every expression's ``compile_query``
    predicate evaluated on it, and the masks summarized over the same
    chunk boundaries the scan uses, so even the float ``sum_var`` merges
    in the same order."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import query as query_lib
    from repro.core.brick import gather_store
    preds = {e: query_lib.compile_query(e, store.schema) for e in exprs}
    parts = {e: [] for e in exprs}
    masks = {e: [] for e in exprs}
    for bid in sorted(store.bricks):
        host = gather_store(store, [bid])
        dev = {k: jnp.asarray(v) for k, v in host.items()}
        dev = dict(dev, tracks=query_lib.calibrate(dev, calib_iters))
        for e, pred in preds.items():
            mask = np.asarray(pred(dev))
            masks[e].append(mask)
            for s in range(0, mask.shape[0], chunk):
                parts[e].append(merge_lib.from_mask(
                    mask[s:s + chunk], host["scalars"][s:s + chunk, 0],
                    host["event_id"][s:s + chunk]))
    return ({e: merge_lib.tree_merge(p) for e, p in parts.items()},
            {e: np.concatenate(m) for e, m in masks.items()})


def describe(result) -> str:
    return (f"selected={result.n_selected}/{result.n_processed} "
            f"sum_var={result.sum_var!r} hist_total={int(result.hist.sum())} "
            f"ids={len(result.selected_ids)}")


def explain_mismatch(store, expr: str, oracle_mask, calib_iters: int):
    """Print each event where the compiled kernel and the oracle disagree
    on a kernel-family query, with its calibrated ``pt`` values nearest
    the threshold and its count and sum against the query's cuts."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import query as query_lib
    from repro.core.brick import gather_store
    from repro.kernels.event_filter import ops as ef_ops
    params = ef_ops.match_epilogue(expr, store.schema)
    if params is None:
        print(f"    {expr!r} is outside the kernel family: no kernel to "
              f"compare with")
        return
    thr, var_idx = ef_ops.batch_kernel_params([params])
    offset = 0
    for bid in sorted(store.bricks):
        host = gather_store(store, [bid])
        dev = {k: jnp.asarray(v) for k, v in host.items()}
        kmask, _ = ef_ops.event_filter_batch(
            dev["scalars"], dev["tracks"], dev["n_tracks"], thr,
            var_idx=var_idx, calib_iters=calib_iters)
        kmask = np.asarray(kmask)[:, 0]
        omask = oracle_mask[offset:offset + kmask.shape[0]]
        offset += kmask.shape[0]
        bad = np.nonzero(kmask != omask)[0]
        if not bad.size:
            continue
        pt = np.asarray(query_lib.calibrate(dev, calib_iters)[..., 0])
        for i in bad:
            n = int(host["n_tracks"][i])
            row = pt[i, :n]
            near = row[np.argsort(np.abs(row - params["pt_thresh"]))[:3]]
            print(f"    event {int(host['event_id'][i])}: kernel="
                  f"{kmask[i]} oracle={omask[i]} "
                  f"scalar={float(host['scalars'][i, params['var_idx']])!r} "
                  f"count(pt > {params['pt_thresh']})="
                  f"{int((row > params['pt_thresh']).sum())} "
                  f"(cut {params['min_count']}) sum(pt)="
                  f"{float(row.sum())!r} (cap {params['sum_cap']}) "
                  f"calibrated pt nearest the threshold: "
                  f"{[repr(float(x)) for x in near]}")


def serve_windows(store, compile_clock):
    """Serve :data:`WINDOWS` through ``QueryService`` on the SPMD backend
    with the kernel on; returns ``(service, [(ticket, expr, tenant)],
    kernel_events)``."""
    from repro.obs import Observability
    from repro.service import QueryService
    obs = Observability()
    svc = QueryService(store, backend="spmd", obs=obs,
                       backend_kwargs={"use_pallas": True,
                                       "chunk_events": CHUNK_EVENTS})
    calib = CONFIG.calib_iters
    tickets = []
    for w, exprs in enumerate(WINDOWS, start=1):
        first, final = {}, {}
        compiled_before = compile_clock.seconds
        t0 = time.perf_counter()
        for i, expr in enumerate(exprs):
            tenant = TENANTS[(i + w) % len(TENANTS)]
            tid = svc.submit(expr, tenant=tenant, calib_iters=calib,
                             stream=True)
            tickets.append((tid, expr, tenant))

            def on_snapshot(snap, tid=tid):
                now = time.perf_counter() - t0
                first.setdefault(tid, now)
                if snap.final:
                    final[tid] = now

            svc.stream(tid).subscribe(on_snapshot)
        served = svc.step()
        wall = time.perf_counter() - t0
        cached = [tid for tid, e, _ in tickets[-len(exprs):]
                  if svc.result(tid).from_cache]
        ttfp = min(first.values()) if first else float("nan")
        ttf = max(final.values()) if final else float("nan")
        print(f"window {w} (smoke run, not a benchmark): {len(exprs)} "
              f"submissions, {len(served)} scanned + {len(cached)} from "
              f"cache, wall {wall:.3f} s, first partial {ttfp:.3f} s, "
              f"last final {ttf:.3f} s, compile "
              f"{compile_clock.seconds - compiled_before:.3f} s")
    svc.drain()
    kernel_events = obs.metrics.counter("spmd.kernel_events").value
    return svc, tickets, kernel_events


def run_one_chip(store, compile_clock) -> None:
    svc, tickets, kernel_events = serve_windows(store, compile_clock)
    s = svc.stats
    print(f"service: served {s.served}/{s.submitted}, batches {s.batches}, "
          f"cache hits {s.cache_hits}, events scanned {s.events_scanned}, "
          f"spmd.kernel_events {int(kernel_events)}")
    failures = []
    if kernel_events <= 0:
        failures.append("spmd.kernel_events is 0: the kernel never ran")
    if s.batches < 3:
        failures.append(f"only {s.batches} dispatch windows ran")
    repeat_hits = [tid for tid, e, _ in tickets
                   if e == REPEAT and svc.result(tid).from_cache]
    if not repeat_hits:
        failures.append("the repeated query was not answered from cache")

    t0 = time.perf_counter()
    exprs = sorted({e for _, e, _ in tickets})
    want, masks = oracle(store, exprs, CONFIG.calib_iters, CHUNK_EVENTS)
    print(f"oracle: {len(exprs)} queries, {time.perf_counter() - t0:.2f} s")
    for tid, expr, tenant in tickets:
        ticket = svc.result(tid)
        snap = svc.stream(tid).latest()
        got = ticket.result
        ok = (ticket.status == "SERVED" and got is not None
              and merge_lib.results_identical(got, want[expr])
              and snap is not None and snap.final
              and merge_lib.results_identical(snap.result, want[expr]))
        source = "cache" if ticket.from_cache else "scan"
        print(f"ticket {tid} [{tenant}, {source}] {expr!r}: "
              f"{'equal to oracle' if ok else 'DIFFERS from oracle'}: "
              f"{describe(got) if got is not None else ticket.status}")
        if not ok:
            print(f"    oracle: {describe(want[expr])}")
            explain_mismatch(store, expr, masks[expr], CONFIG.calib_iters)
            failures.append(f"ticket {tid} differs from the oracle")
    svc.close()
    if failures:
        raise SmokeFailure("; ".join(failures))


def run_mesh(store, n_chips: int) -> None:
    """One window on a ``n_chips``-device scan mesh and on one device, in
    this process; partials and finals must be bit-identical."""
    from repro.service import QueryService
    runs = {}
    for mesh in (n_chips, 1):
        svc = QueryService(store, backend="spmd",
                           backend_kwargs={"use_pallas": True,
                                           "chunk_events": CHUNK_EVENTS,
                                           "mesh_devices": mesh})
        partials = {}
        tids = []
        t0 = time.perf_counter()
        for i, expr in enumerate(WINDOWS[0]):
            tid = svc.submit(expr, tenant=TENANTS[i],
                             calib_iters=CONFIG.calib_iters, stream=True)
            tids.append(tid)
            partials[tid] = []
            svc.stream(tid).subscribe(
                lambda snap, tid=tid: partials[tid].append(snap.result))
        svc.step()
        wall = time.perf_counter() - t0
        if mesh > 1 and not svc.backend._mesh_is_real():
            raise SmokeFailure(f"mesh_devices={mesh} is not a real mesh")
        runs[mesh] = ([svc.result(t).result for t in tids],
                      [partials[t] for t in tids])
        print(f"mesh_devices={mesh}: one window of {len(tids)} queries, "
              f"wall {wall:.3f} s (includes compile), real mesh "
              f"{svc.backend._mesh_is_real()}, "
              f"{sum(len(p) for p in partials.values())} partials")
        svc.close()
    (fin_m, par_m), (fin_1, par_1) = runs[n_chips], runs[1]
    same_finals = all(merge_lib.results_identical(a, b)
                      for a, b in zip(fin_m, fin_1))
    same_partials = all(
        len(a) == len(b) and all(merge_lib.results_identical(x, y)
                                 for x, y in zip(a, b))
        for a, b in zip(par_m, par_1))
    for expr, result in zip(WINDOWS[0], fin_m):
        print(f"  {expr!r}: {describe(result)}")
    print(f"{n_chips}-device vs 1-device: finals "
          f"{'bit-identical' if same_finals else 'DIFFER'}, partials "
          f"{'bit-identical' if same_partials else 'DIFFER'}")
    if not (same_finals and same_partials):
        raise SmokeFailure(f"{n_chips}-device results differ from "
                           f"1-device results")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-device mesh path and its "
                         "1-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        devices = require_tpu(args.chips)
        from repro.launch.compile_cache import enable_compile_cache
        cache = Path(enable_compile_cache())
        warm = len(list(cache.iterdir())) if cache.is_dir() else 0
        print(f"compile cache: {cache} ({warm} entries at start)")
        print(f"device: platform={devices[0].platform} "
              f"kind={devices[0].device_kind} count={len(devices)}")
        compile_clock = CompileClock()
        store = build_store(args.seed)
        if args.chips == 1:
            run_one_chip(store, compile_clock)
        else:
            run_mesh(store, args.chips)
        print(f"compile total: {compile_clock.seconds:.3f} s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
