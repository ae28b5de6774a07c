"""Serving launcher with two modes.

``--mode lm`` (default): batched prefill -> decode loop over the
brick-sharded KV cache.  ``python -m repro.launch.serve --arch <id>
--reduced``.  The serve path is the GEPS query flow applied to generation:
the prompt batch is the "job", the KV bricks hold the per-chip context
shards, each decode step computes locally and merges the per-brick softmax
partials (core/brick_attention.py).

``--mode query``: the multi-tenant GEPS query service —
``python -m repro.launch.serve --mode query --tenants 4 --queries 64``.
Stands up a brick store + QueryService, replays a multi-tenant workload
with repeats, and reports shared-scan amortization and cache hit rates.
Add ``--stream`` for progressive delivery: every ticket gets a
ResultStream fed per-packet prefix merges mid-scan, and the report adds
time-to-first-partial vs time-to-final plus a live coverage trace for one
sample ticket.

``--backend {sim,spmd}`` (query mode) picks the execution backend every
dispatch window runs on: the virtual-time grid simulation (default) or
the SPMD chunked streaming scan over the brick shards (wall-clock
latencies, same streaming/caching/planning behaviour — see
``docs/backends.md``).

``--fleet N`` (query mode) replaces the single QueryService with a
coherence-fabric :class:`~repro.fabric.fleet.Fleet` of N front-ends over
one brick store: submissions round-robin across the fleet, a shared L2
cache tier + persistent fragment registry turn repeats into zero-I/O
hits on ANY front-end, a mid-run dataset bump demonstrates the gossip
invalidation bound, and with ``--stream`` one sample ticket is read
cross-frontend through the bus fan-out.

``--policy`` (query mode) arms the failure-policy engine
(``service/policy.py``): each front-end runs the node state machine over
its health reports, routes around degraded/banned nodes, speculatively
re-executes stragglers, and re-replicates bricks off persistently sick
nodes; fleet mode additionally hardens epoch gossip with ack/repair.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

import json

from repro.configs.geps_events import WIDTHS
from repro.configs.registry import get_config, list_archs, reduced_config
from repro.launch.mesh import make_mesh_of, make_production_mesh
from repro.models import model_zoo
from repro.parallel.sharding import Sharder
from repro.train import steps as steps_lib


def prefill_into_cache(cfg, model, params, cache, tokens, shd):
    """Feed a prompt through decode steps to fill the ring cache.

    (Chunked prefill via the forward path is the production fast path; the
    token-by-token fill is used for correctness and small prompts.)"""
    dec = lambda c, t: model.decode_step(params, c, t, shd)
    for i in range(tokens.shape[1]):
        logits, cache = dec(cache, tokens[:, i:i + 1])
    return logits, cache


def generate(cfg, model, params, shd, prompt, max_new_tokens=16,
             cache_len=256, greedy=True):
    b = prompt.shape[0]
    cache = model.init_cache(shd, b, cache_len)
    logits, cache = prefill_into_cache(cfg, model, params, cache, prompt, shd)
    dec = jax.jit(lambda c, t: model.decode_step(params, c, t, shd))
    out = []
    tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1)[:, None]
    for _ in range(max_new_tokens):
        out.append(tok)
        logits, cache = dec(cache, tok.astype(jnp.int32))
        tok = jnp.argmax(logits[:, -1 if logits.ndim == 3 else slice(None),
                                :cfg.vocab_size], axis=-1)
        tok = tok.reshape(b, 1)
    return jnp.concatenate(out, axis=1)


def _dump_trace(records, path):
    """Write trace records to ``path``: JSONL for ``.jsonl``, Chrome-trace
    JSON (Perfetto-loadable) otherwise."""
    from repro.obs import trace as trace_lib
    if str(path).endswith(".jsonl"):
        trace_lib.save_jsonl(records, path)
    else:
        trace_lib.save_chrome(records, path)
    print(f"  trace: {len(records)} records -> {path}")


def _dump_metrics(snapshot, path):
    """Write a metrics snapshot: Prometheus text exposition format for
    ``.prom`` paths, JSON otherwise."""
    if str(path).endswith(".prom"):
        with open(path, "w") as f:
            f.write(snapshot.to_prom_text())
    else:
        with open(path, "w") as f:
            json.dump(snapshot.to_dict(), f, indent=2, sort_keys=True)
    print(f"  metrics: {len(snapshot.metrics)} series -> {path}")


def _backend_kwargs(args):
    """Collect the SPMD performance knobs from the CLI into the
    ``backend_kwargs`` dict QueryService/Fleet forward to the backend
    constructor.  Returns None for the simulated backend — the knobs are
    scan-path concepts and passing them there should fail loudly, not
    silently no-op."""
    if args.backend != "spmd":
        for flag, name in ((args.use_pallas, "--use-pallas"),
                           (args.chunk_events, "--chunk-events"),
                           (args.mesh_devices, "--mesh-devices")):
            if flag:
                raise SystemExit(
                    f"{name} requires --backend spmd (the simulation "
                    "has no kernel scan path)")
        return None
    kw = {}
    if args.use_pallas:
        kw["use_pallas"] = True
    if args.interpret != "auto":
        kw["interpret"] = args.interpret == "interpret"
    if args.chunk_events is not None:
        kw["chunk_events"] = args.chunk_events
    if args.mesh_devices is not None:
        kw["mesh_devices"] = args.mesh_devices
    return kw or None


def serve_fleet(args):
    """Fleet serving mode: the multi-tenant workload of ``serve_queries``
    replayed round-robin over ``--fleet N`` coherence-fabric front-ends.
    Reports fleet-aggregate hit rates (incl. the shared-L2 contribution),
    the gossip propagation bound, registry pre-warming, and — with
    ``--stream`` — a cross-frontend proxy read of one sample ticket."""
    from repro.configs.geps_events import by_width
    from repro.core.brick import store_from_config
    from repro.fabric import Fleet, FragmentRegistry, MessageBus
    from repro.obs import flight as flight_lib

    cfg = by_width(args.width)
    store = store_from_config(cfg, n_events=args.n_events,
                              n_nodes=args.n_nodes, seed=0)
    want_obs = bool(args.trace_out or args.metrics_dump or args.policy)
    bus = MessageBus(drop_rate=args.drop_rate, seed=args.bus_seed)
    recorder = None
    if args.flight_out:
        # the store_config record makes the log self-contained: replay
        # (python -m repro.obs.replay) rebuilds an equal store from it
        recorder = flight_lib.FlightRecorder()
        recorder.record("store_config", origin="serve",
                        schema_name=f"geps_{args.width}",
                        n_events=args.n_events,
                        n_nodes=args.n_nodes,
                        events_per_brick=cfg.events_per_brick,
                        replication=cfg.replication_factor, seed=0)
    fleet = Fleet(store, args.fleet, bus=bus, registry=FragmentRegistry(),
                  backend=args.backend, backend_kwargs=_backend_kwargs(args),
                  obs=want_obs,
                  policy=args.policy, gossip_repair=args.policy,
                  single_flight=args.single_flight,
                  flight=recorder if recorder is not None else False)
    hot = ["e_total > 40 && count(pt > 15) >= 2",
           "e_t_miss > 30", "pt_lead > 60 || n_tracks >= 8"]
    t0 = time.time()
    sample = None
    for i in range(args.queries):
        tenant = f"tenant{i % args.tenants}"
        if i % 3 != 2:
            # hot index advances slower than the submit round-robin, so
            # consecutive submissions of the same hot query land on
            # DIFFERENT front-ends in the same window — the same-window
            # duplicate-scan race single-flight leases exist to close
            expr = hot[(i // 3) % len(hot)]
        else:
            expr = (f"e_total > {20 + (i % 7) * 10} && "
                    f"count(pt > 15) >= {1 + i % 4}")
        gtid = fleet.submit(expr, tenant=tenant, stream=args.stream)
        if sample is None:
            sample = gtid
        if (i + 1) % args.window == 0:
            fleet.step()
        if args.kill_node is not None and i == args.queries // 3:
            # mid-run grid-node death: failover + liveness gossip (and,
            # when recording, the event the replay must reproduce)
            fleet.node_leave(args.kill_node)
        if args.queries > 2 and i == args.queries // 2:
            # mid-run dataset bump on one member: gossip invalidates the
            # whole fleet within the documented bound
            fleet.bump_dataset_version(0)
    fleet.drain()
    dt = time.time() - t0
    s = fleet.fleet_stats()
    print(f"fleet: {args.fleet} front-ends, {s['served']}/{s['submitted']} "
          f"served in {dt:.2f}s ({s['served'] / max(dt, 1e-9):.1f} q/s)")
    print(f"  hit_rate={s['hit_rate']:.3f} (cache_hits={s['cache_hits']}, "
          f"of which l2_hits={s['l2_hits']}), "
          f"events_scanned={s['events_scanned']}")
    if args.single_flight:
        print(f"  single-flight: adopted={s['adopted']} tickets rode a "
              f"remote lease owner's stream "
              f"(fallbacks={s['lease_fallbacks']})")
    print(f"  gossip: bound={fleet.rounds_bound} rounds "
          f"(fanout={fleet.gossip_fanout}), epochs="
          f"{[fe.catalog.dataset_epoch for fe in fleet.frontends]}")
    if fleet.l2 is not None:
        print(f"  shared L2: {len(fleet.l2)} entries, "
              f"{fleet.l2.stats.hits} hits, "
              f"{fleet.l2.stats.fragment_puts} fragment installs")
    if fleet.registry is not None:
        print(f"  registry: {len(fleet.registry)} fragments tracked, "
              f"hot={fleet.registry.hot(4)}")
    if args.policy:
        for fe_id, states in fleet.policy_states().items():
            bad = {n: s for n, s in states.items() if s != "ok"}
            print(f"  policy[{fe_id}]: "
                  f"{bad if bad else 'all nodes ok'} "
                  f"(gossip repair: "
                  f"{fleet.frontends[0].gossip.stats.repairs} repairs)")
            break  # one line is enough; views converge via gossip
    if args.stream and sample is not None:
        owner_idx = fleet.owner_of(sample)
        reader = (owner_idx + 1) % args.fleet
        proxy = fleet.stream(sample, frontend=reader)
        fleet.drain()
        state = proxy.state
        print(f"  cross-frontend stream: ticket {sample} (owner fe"
              f"{owner_idx}) read from fe{reader}: {proxy.published} "
              f"snapshots, state={state}")
    if args.trace_out:
        _dump_trace(fleet.trace_records(), args.trace_out)
    if args.metrics_dump:
        _dump_metrics(fleet.metrics_snapshot(), args.metrics_dump)
    if args.flight_out:
        n = fleet.save_flight(args.flight_out)
        print(f"  flight: {n} records -> {args.flight_out} "
              f"(replay: python -m repro.obs.replay {args.flight_out})")
    fleet.close()


def serve_queries(args):
    """Query-serving mode: multi-tenant traffic over the brick store.

    With ``--adaptive-window`` the service runs a virtual arrival clock at
    ``--arrival-rate`` q/s and lets the EWMA WindowController size each
    dispatch window against measured (virtual) scan latency, instead of
    stepping every fixed ``--window`` submissions.  ``--cost-budget``
    enables per-tenant cost-budgeted admission (planner cost units).
    ``--stream`` turns every submission into a streamed ticket and reports
    progressive-delivery metrics (time-to-first-partial vs final)."""
    from repro.configs.geps_events import by_width
    from repro.core.brick import store_from_config
    from repro.service import QueryScheduler, QueryService, WindowController

    store = store_from_config(by_width(args.width), n_events=args.n_events,
                              n_nodes=args.n_nodes, seed=0)
    sched = QueryScheduler(
        max_batch=args.window,
        cost_budget_per_tenant=args.cost_budget)
    wc = clock = None
    if args.adaptive_window:
        # virtual clock: arrivals spaced 1/rate apart, same units as the
        # simulator's makespans the controller sees as scan latency
        vnow = [0.0]
        clock = lambda: vnow[0]
        wc = WindowController(initial=args.window)
    obs = None
    if args.trace_out or args.metrics_dump or args.policy:
        from repro.obs import Observability
        obs = Observability(origin="fe0")
    policy = catalog = None
    if args.policy:
        # the policy and the service must judge node liveness from the
        # SAME catalogue, so build it here and hand it to both
        from repro.core.catalog import MetadataCatalog
        from repro.service.policy import FailurePolicy
        catalog = MetadataCatalog(store.n_nodes)
        policy = FailurePolicy(catalog, store, obs=obs)
    svc = QueryService(store, catalog, scheduler=sched, window_controller=wc,
                       backend=args.backend,
                       backend_kwargs=_backend_kwargs(args),
                       obs=obs, policy=policy,
                       **({"clock": clock} if clock else {}))
    # multi-tenant workload: a few hot queries repeated across tenants
    # (the interactive-analysis regime) plus per-tenant near-duplicate
    # long-tail queries sharing aggregate fragments
    hot = ["e_total > 40 && count(pt > 15) >= 2",
           "e_t_miss > 30", "pt_lead > 60 || n_tracks >= 8"]
    t0 = time.time()
    sample_tid = None
    first_partial = {}  # ticket -> t_virtual of its FIRST published snapshot
    for i in range(args.queries):
        tenant = f"tenant{i % args.tenants}"
        if i % 3 != 2:
            expr = hot[i % len(hot)]
        else:
            expr = (f"e_total > {20 + (i % 7) * 10} && "
                    f"count(pt > 15) >= {1 + i % 4}")
        tid = svc.submit(expr, tenant=tenant, stream=args.stream)
        if args.stream:
            # record at publish time: the buffer conflates under
            # backpressure, so reading it later would miss early snapshots
            svc.stream(tid).subscribe(
                lambda s, t=tid: first_partial.setdefault(t, s.t_virtual))
        if sample_tid is None:
            sample_tid = tid
        if args.adaptive_window:
            vnow[0] += 1.0 / args.arrival_rate
            if svc.scheduler.n_pending >= wc.window():
                svc.step()
        elif (i + 1) % args.window == 0:
            svc.step()
    svc.drain()
    dt = time.time() - t0
    s = svc.stats
    scanned_per_query = s.events_scanned / max(1, s.served - s.cache_hits)
    print(f"query-service: {s.served}/{s.submitted} served in {dt:.2f}s "
          f"({s.served / dt:.1f} q/s wall)")
    print(f"  batches={s.batches} jobs_run={s.jobs_run} "
          f"cache_hits={s.cache_hits} rejected={s.rejected}")
    print(f"  events_scanned={s.events_scanned} "
          f"(store={store.n_events} events; "
          f"{scanned_per_query:.0f} scanned/executed-query)")
    if s.fragment_evals:
        print(f"  planner: fragment_evals={s.fragment_evals} "
              f"vs unshared={s.fragment_evals_unshared} "
              f"({s.fragment_evals_unshared / s.fragment_evals:.2f}x "
              f"factored out), "
              f"fragment_cache_puts={svc.cache.stats.fragment_puts}")
    if svc.window_history and args.adaptive_window:
        print(f"  adaptive windows: {svc.window_history}")
    if args.stream:
        ratios = []
        for tid, stream in svc.streams.items():
            if not stream.done or stream.published < 2:
                continue  # cache hits stream a single final snapshot
            ratios.append(first_partial[tid] / stream.latest().t_virtual)
        if ratios:
            print(f"  streaming: {len(svc.streams)} streams, "
                  f"first-partial/final virtual-time ratio "
                  f"{sum(ratios) / len(ratios):.2f} "
                  f"(mean over {len(ratios)} scanned tickets)")
        sample = svc.streams.get(sample_tid)
        if sample is not None and sample.latest() is not None:
            snap = sample.latest()
            cov = snap.coverage
            print(f"  sample ticket {sample_tid}: {sample.published} "
                  f"snapshots ({sample.dropped} conflated), final coverage "
                  f"{cov.events_scanned}/{cov.events_total} events over "
                  f"{len(cov.bricks_seen)}/{cov.bricks_total} bricks")
    if policy is not None:
        states = policy.states()
        bad = {n: st for n, st in states.items() if st != "ok"}
        print(f"  policy: {bad if bad else 'all nodes ok'} "
              f"(speculation {'on' if policy.config.speculate else 'off'})")
    if obs is not None:
        if args.trace_out:
            _dump_trace(obs.tracer.records(), args.trace_out)
        if args.metrics_dump:
            _dump_metrics(obs.metrics.snapshot(), args.metrics_dump)
    svc.close()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("lm", "query"), default="lm")
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    # query mode
    ap.add_argument("--n-events", type=int, default=1024)
    ap.add_argument("--width", choices=WIDTHS, default="reduced",
                    help="query mode: event width from "
                         "configs/geps_events.py — the reduced test width "
                         "or the paper's ~1 MB event (64 scalars, "
                         "4096 x 63 tracks)")
    ap.add_argument("--n-nodes", type=int, default=4)
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--window", type=int, default=16,
                    help="submissions per dispatch window")
    ap.add_argument("--adaptive-window", action="store_true",
                    help="EWMA-controlled window width (arrival rate vs. "
                         "measured scan latency)")
    ap.add_argument("--arrival-rate", type=float, default=8.0,
                    help="virtual arrivals/sec for --adaptive-window")
    ap.add_argument("--cost-budget", type=float, default=None,
                    help="per-tenant pending cost budget (planner units)")
    ap.add_argument("--stream", action="store_true",
                    help="progressive delivery: per-ticket ResultStreams "
                         "fed per-packet prefix merges mid-scan")
    ap.add_argument("--backend", choices=("sim", "spmd"), default="sim",
                    help="execution backend for dispatch windows: the "
                         "virtual-time grid simulation or the SPMD "
                         "chunked streaming shard scan (wall-clock "
                         "latencies; --adaptive-window then observes "
                         "real scan times)")
    ap.add_argument("--use-pallas", action="store_true",
                    help="spmd backend: run in-family plan targets "
                         "through the fused event_filter Pallas kernel "
                         "(mixed windows split per target — see "
                         "docs/backends.md, Performance tuning)")
    ap.add_argument("--interpret", choices=("auto", "interpret",
                                            "compiled"), default="auto",
                    help="spmd backend: Pallas execution mode; auto "
                         "(default) compiles on TPU/GPU and falls back "
                         "to the interpreter on CPU")
    ap.add_argument("--chunk-events", type=int, default=None, metavar="N",
                    help="spmd backend: events per scan chunk "
                         "(= streamed partial granularity)")
    ap.add_argument("--mesh-devices", type=int, default=None, metavar="D",
                    help="spmd backend: shard each brick's chunk groups "
                         "over a D-device scan mesh (shard_map; needs D "
                         "jax devices)")
    ap.add_argument("--fleet", type=int, default=1,
                    help="query mode: number of coherence-fabric "
                         "front-ends (1 = single QueryService)")
    ap.add_argument("--single-flight", action="store_true",
                    help="query mode with --fleet: scan-intent leases + "
                         "in-flight stream adoption (fabric/leases.py) — "
                         "N duplicate scans become 1 scan + N-1 zero-I/O "
                         "stream subscriptions")
    ap.add_argument("--policy", action="store_true",
                    help="query mode: enable the failure-policy engine "
                         "(node state machine, routing avoidance, "
                         "speculative re-execution, proactive "
                         "re-replication; with --fleet also gossip "
                         "ack/repair) — see docs/policy.md")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="query mode: enable the observability plane and "
                         "write the span trace to PATH (.jsonl = JSONL "
                         "records, anything else = Chrome-trace JSON "
                         "loadable in Perfetto)")
    ap.add_argument("--metrics-dump", default=None, metavar="PATH",
                    help="query mode: enable the observability plane and "
                         "write the (fleet-merged) metrics snapshot to "
                         "PATH (.prom = Prometheus text exposition, "
                         "anything else = JSON)")
    ap.add_argument("--flight-out", default=None, metavar="PATH",
                    help="query mode with --fleet: arm the flight "
                         "recorder and write the causal decision log as "
                         "JSONL to PATH; replay with "
                         "'python -m repro.obs.replay PATH'")
    ap.add_argument("--drop-rate", type=float, default=0.0,
                    help="query mode with --fleet: seeded message-loss "
                         "probability on every bus link")
    ap.add_argument("--bus-seed", type=int, default=0,
                    help="query mode with --fleet: RNG seed for the bus "
                         "loss draw (determinism knob for --flight-out)")
    ap.add_argument("--kill-node", type=int, default=None, metavar="N",
                    help="query mode with --fleet: kill grid node N a "
                         "third of the way through the workload "
                         "(failover + liveness gossip)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.mode == "query":
        if args.fleet > 1:
            serve_fleet(args)
        else:
            serve_queries(args)
        return
    if args.arch is None:
        ap.error("--arch is required for --mode lm")

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = (make_production_mesh() if args.production_mesh
            else make_mesh_of((len(jax.devices()), 1), ("data", "model")))
    shd = Sharder(cfg, mesh)
    model = model_zoo.build_model(cfg)
    params = model.table.init(jax.random.key(0))
    if cfg.is_encoder_decoder:
        # fill cross-attention cache from stub frames first
        from repro.models import encdec
        frames = jax.random.normal(
            jax.random.key(1), (args.batch, cfg.encoder_seq_len, cfg.d_model),
            jnp.dtype(cfg.dtype))
        cache = model.init_cache(shd, args.batch, 256)
        cache = encdec.prefill_cross_cache(cfg, params, frames, shd, cache)

    prompt = jax.random.randint(jax.random.key(2),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size, jnp.int32)
    t0 = time.time()
    tokens = generate(cfg, model, params, shd, prompt,
                      max_new_tokens=args.new_tokens)
    dt = time.time() - t0
    print(f"arch={cfg.name} generated {tokens.shape} in {dt:.1f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s)")
    print("sample:", tokens[0, :12].tolist())


if __name__ == "__main__":
    main()
