"""Observability plane: span determinism, schema validation, mergeable
metrics (incl. associativity property), health telemetry + scheduler
gate, disabled-path equivalence, fleet metrics reconciliation."""
import numpy as np
import pytest

try:  # property tests run where hypothesis is installed (CI tier-1)
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.configs.geps_events import reduced
from repro.core import events as ev
from repro.core import merge as merge_lib
from repro.core.brick import create_store
from repro.fabric import Fleet
from repro.obs import (HEALTH_OK, HEALTH_STATES, HEALTH_SUSPECT,
                       HealthMonitor,
                       MetricsRegistry, MetricsSnapshot, Observability,
                       STATUS_ERROR, STATUS_OK, Tracer, chrome_from_records,
                       comparable_records, load_jsonl, merge2,
                       merge_snapshots, save_jsonl, validate_records)
from repro.service import QueryScheduler, QueryService, make_submission
from repro.service.frontend import REJECTED, SERVED
from repro.service.streaming import ABORTED

CFG = reduced()
SCHEMA = ev.EventSchema.from_config(CFG)


def make_store(n_events=192, n_nodes=4, replication=2, seed=7):
    return create_store(SCHEMA, n_events=n_events, n_nodes=n_nodes,
                        events_per_brick=CFG.events_per_brick,
                        replication=replication, seed=seed)


EXPRS = [
    "e_total > 40.0",
    "e_total > 40.0 && count(pt > 12.0) >= 1",
    "sum(pt) < 400.0 || n_tracks >= 2",
]


def run_service(store, *, obs=None, backend="sim", stream=False):
    svc = QueryService(store, backend=backend, obs=obs)
    tids = [svc.submit(e, tenant=f"t{i % 2}", stream=stream)
            for i, e in enumerate(EXPRS)]
    svc.drain()
    svc.close()
    return svc, tids


# ----------------------------- tracer ---------------------------------- #
def test_tracer_span_lifecycle():
    tr = Tracer(process="fe0")
    s = tr.begin("submit", t_virtual=1.0, ticket=3, tenant="a")
    assert s.status == "open" and tr.open_spans() == [s]
    tr.end(s, t_virtual=2.0, status=STATUS_ERROR, note="boom")
    assert s.status == STATUS_ERROR and s.attrs["note"] == "boom"
    # idempotent close: the first (error) verdict wins later cleanups
    tr.end(s, t_virtual=9.0, status=STATUS_OK)
    assert s.status == STATUS_ERROR and s.t1_virtual == 2.0

    e = tr.event("final", t_virtual=2.0, ticket=3, outcome="SERVED")
    assert e.kind == "event" and e.status == STATUS_OK
    assert e.t1_virtual == e.t0_virtual
    assert tr.open_spans() == []


def test_tracer_parent_stack():
    tr = Tracer()
    w = tr.begin("window", t_virtual=0.0)
    tr.push(w)
    p = tr.begin("packet", t_virtual=0.1)
    assert p.parent_id == w.span_id
    explicit = tr.begin("plan", t_virtual=0.1, parent=p)
    assert explicit.parent_id == p.span_id
    assert tr.pop() is w
    orphan = tr.begin("submit", t_virtual=0.2)
    assert orphan.parent_id is None


def test_validate_records_catches_problems():
    tr = Tracer(process="fe0")
    s = tr.begin("window", t_virtual=0.0)
    recs = tr.records()
    assert any("open" in p for p in validate_records(recs))
    tr.end(s, t_virtual=1.0)
    assert validate_records(tr.records()) == []

    bad = tr.records()
    bad[0]["parent_id"] = 999
    assert any("dangling" in p for p in validate_records(bad))
    bad = tr.records()
    bad[0]["status"] = "weird"
    assert any("bad status" in p for p in validate_records(bad))
    bad = tr.records()
    del bad[0]["ticket"]
    assert any("missing field" in p for p in validate_records(bad))


def test_jsonl_roundtrip_and_chrome_export(tmp_path):
    tr = Tracer(process="fe0")
    s = tr.begin("dispatch", t_virtual=0.5, batch=0)
    tr.push(s)
    p = tr.begin("packet", t_virtual=0.5, node=2, brick=1, size=64)
    tr.end(p, t_virtual=1.5)
    tr.pop()
    tr.end(s, t_virtual=2.0)
    tr.event("final", t_virtual=2.0, ticket=0, outcome="SERVED")

    path = tmp_path / "t.jsonl"
    save_jsonl(tr.records(), path)
    assert load_jsonl(path) == tr.records()

    chrome = chrome_from_records(tr.records())
    evs = chrome["traceEvents"]
    assert [e["ph"] for e in evs] == ["X", "X", "i"]
    pkt = evs[1]
    assert pkt["tid"] == 2 and pkt["ts"] == pytest.approx(0.5e6)
    assert pkt["dur"] == pytest.approx(1.0e6)


def test_tracer_origin_and_wall_spans():
    tr = Tracer(process="fe0")
    w = tr.begin("window", t_virtual=0.0)
    p = tr.begin("packet", t_virtual=0.0, parent=w, seq=0)
    tr.end(p)
    tr.event("final", ticket=0)
    tr.begin("stream", ticket=0)            # still open: left out
    tr.end(w)
    spans = tr.wall_spans()
    assert [n for n, _, _ in spans] == ["window", "packet"]
    assert all(0.0 <= s <= e for _, s, e in spans)
    assert tr.wall_spans(["packet"]) == spans[1:]
    # the anchor rides the Chrome export, never the v1 records
    assert tr.chrome_trace()["otherData"]["origin_ns"] == tr.origin_ns
    assert "origin_ns" not in chrome_from_records(tr.records())["otherData"]
    assert all("origin_ns" not in r for r in tr.records())


def test_tracer_spans_land_on_the_profilers_clock(tmp_path):
    """A tracer span and a profiler annotation around the same block lie
    within a millisecond of each other once the span is shifted from the
    tracer's ``origin_ns`` to the trace's ``profile_start_time``."""
    import glob
    import time

    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tr = Tracer(process="fe0")
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    time.sleep(0.02)
    span = tr.begin("stage")
    with jax.profiler.TraceAnnotation("annotated-stage"):
        f(x).block_until_ready()
        time.sleep(0.01)
    tr.end(span)
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    env, marks = {}, []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            env = {k: v for k, v in plane.stats}
        elif plane.name.startswith("/host:"):
            marks += [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                      for line in plane.lines for e in line.events
                      if e.name == "annotated-stage"]
    (s0, e0), = marks
    shift = (tr.origin_ns - int(env["profile_start_time"])) * 1e-9
    (_, s1, e1), = tr.wall_spans(["stage"])
    assert s1 + shift == pytest.approx(s0, abs=1e-3)
    assert e1 + shift == pytest.approx(e0, abs=1e-3)


# ----------------------------- metrics --------------------------------- #
def test_histogram_buckets_and_registry_errors():
    reg = MetricsRegistry(origin="fe0")
    h = reg.histogram("lat", edges=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 3.0, 100.0):
        h.observe(v)
    assert h.counts == [2, 0, 1, 1] and h.count == 4
    # fetching without edges returns the registered instance
    assert reg.histogram("lat") is h
    with pytest.raises(ValueError):
        reg.histogram("lat", edges=(1.0, 2.0))
    with pytest.raises(ValueError):
        reg.counter("lat")
    with pytest.raises(ValueError):
        reg.histogram("bad", edges=(2.0, 1.0))


def test_merge2_semantics():
    ra, rb = MetricsRegistry("a"), MetricsRegistry("b")
    ra.counter("c").inc(3)
    rb.counter("c").inc(4)
    ra.gauge("g").set(2.0)
    rb.gauge("g").set(5.0)
    ra.histogram("h", edges=(1.0, 2.0)).observe(0.5)
    rb.histogram("h", edges=(1.0, 2.0)).observe(1.5)
    ra.counter("only_a").inc()

    m = merge2(ra.snapshot(), rb.snapshot())
    assert m.value("c") == 7 and m.value("g") == 5.0
    assert m.value("only_a") == 1
    assert m.hist("h")["counts"] == [1, 1, 0]
    assert m.origins == ("a", "b")

    rc = MetricsRegistry("c")
    rc.histogram("h", edges=(1.0, 3.0)).observe(0.5)
    with pytest.raises(ValueError):
        merge2(m, rc.snapshot())
    rd = MetricsRegistry("d")
    rd.gauge("c").set(1.0)
    with pytest.raises(ValueError):
        merge2(m, rd.snapshot())


_EDGES = [1.0, 2.0, 4.0]


def _check_merge_algebra(a, b, c):
    left = merge2(merge2(a, b), c)
    right = merge2(a, merge2(b, c))
    assert left.metrics == right.metrics
    assert merge2(a, b).metrics == merge2(b, a).metrics
    # tree reduction agrees with a sequential fold
    folded = merge2(merge2(a, b), c)
    assert merge_snapshots([a, b, c]).metrics == folded.metrics


def _random_snapshot(rng):
    # fixed name -> type mapping so any two generated snapshots merge;
    # integer-valued floats keep addition exactly associative
    metrics = {}
    if rng.random() < 0.8:
        metrics["c1"] = {"type": "counter",
                         "value": float(rng.integers(0, 1000))}
    if rng.random() < 0.5:
        metrics["g1"] = {"type": "gauge",
                         "value": float(rng.integers(0, 1000))}
    if rng.random() < 0.8:
        metrics["h1"] = {"type": "histogram", "edges": list(_EDGES),
                         "counts": [int(v) for v in
                                    rng.integers(0, 50, size=4)],
                         "sum": float(rng.integers(0, 1000)),
                         "count": int(rng.integers(0, 200))}
    return MetricsSnapshot(metrics=metrics, origins=("o",))


def test_merge2_associative_commutative_seeded():
    rng = np.random.default_rng(0)
    for _ in range(50):
        _check_merge_algebra(*(_random_snapshot(rng) for _ in range(3)))


if HAVE_HYPOTHESIS:
    def _snapshot_strategy():
        num = st.integers(0, 1000).map(float)
        counter = st.fixed_dictionaries(
            {"type": st.just("counter"), "value": num})
        gauge = st.fixed_dictionaries(
            {"type": st.just("gauge"), "value": num})
        hist = st.fixed_dictionaries({
            "type": st.just("histogram"), "edges": st.just(list(_EDGES)),
            "counts": st.lists(st.integers(0, 50), min_size=4, max_size=4),
            "sum": num, "count": st.integers(0, 200)})
        by_name = {"c1": counter, "c2": counter, "g1": gauge, "h1": hist}
        names = st.sets(st.sampled_from(sorted(by_name)), max_size=4)
        return names.flatmap(
            lambda ns: st.fixed_dictionaries(
                {n: by_name[n] for n in sorted(ns)})).map(
            lambda m: MetricsSnapshot(metrics=m, origins=("o",)))

    @settings(max_examples=60, deadline=None)
    @given(a=_snapshot_strategy(), b=_snapshot_strategy(),
           c=_snapshot_strategy())
    def test_merge2_associative_commutative(a, b, c):
        _check_merge_algebra(a, b, c)


# ----------------------------- health ---------------------------------- #
def test_health_classification():
    mon = HealthMonitor(origin="fe0", min_packets=3)
    for node in (0, 1, 2):
        for _ in range(5):
            mon.observe_packet(node, size=100, wall_s=0.01)
    for _ in range(5):  # node 3 scans 10x slower than the median
        mon.observe_packet(3, size=100, wall_s=0.1)
    mon.observe_packet(4, size=100, wall_s=5.0)  # under evidence floor
    rep = mon.report()
    assert rep.states[0] == HEALTH_OK
    assert rep.states[3] == HEALTH_SUSPECT
    assert rep.states[4] == HEALTH_OK  # insufficient data != sickness
    assert 3 in rep.suspects and rep.healthy_fraction < 1.0

    mon2 = HealthMonitor(origin="fe0")
    for _ in range(4):
        mon2.observe_failure(7)
    assert mon2.report().states[7] == HEALTH_SUSPECT


def test_health_gossip_merge():
    a, b = HealthMonitor(origin="fe0"), HealthMonitor(origin="fe1")
    for _ in range(5):
        a.observe_packet(0, size=100, wall_s=0.01)
        b.observe_packet(1, size=100, wall_s=0.01)
    b.merge_digest(a.digest())
    assert set(b.report().states) == {0, 1}
    # idempotent: merging the same digest twice changes nothing
    before = b.digest()
    b.merge_digest(a.digest())
    assert b.digest() == before
    # own-origin entries are never overwritten by hearsay
    fake = {"origin": "x", "entries": [
        {"node": 1, "origin": "fe1", "packets": 999,
         "rate_ewma": 9.9, "failure_ewma": 0.9, "stamp": 10**6}]}
    b.merge_digest(fake)
    assert b.report().failures[1] < 0.5
    # higher stamp per (node, origin) wins; lower is ignored
    a.observe_packet(0, size=100, wall_s=0.5)
    newer = a.digest()
    b.merge_digest(newer)
    got = b.report().rates[0]
    b.merge_digest({"origin": "fe0", "entries": [
        {"node": 0, "origin": "fe0", "packets": 1,
         "rate_ewma": 7.0, "failure_ewma": 0.0, "stamp": 1}]})
    assert b.report().rates[0] == got

    assert HealthMonitor().report().healthy_fraction == 1.0


# ------------------------- service integration ------------------------- #
def test_spans_deterministic_and_schema_valid():
    runs = []
    for _ in range(2):
        obs = Observability(origin="fe0")
        run_service(make_store(seed=11), obs=obs, stream=True)
        recs = obs.tracer.records()
        assert validate_records(recs) == []
        assert obs.tracer.open_spans() == []
        runs.append(comparable_records(recs))
    assert runs[0] == runs[1]


def test_disabled_path_results_identical():
    base, _ = run_service(make_store(seed=13))
    assert base.obs is None and base.backend.obs is None
    obs = Observability(origin="fe0")
    traced, _ = run_service(make_store(seed=13), obs=obs)
    for t_base, t_obs in zip(base.tickets.values(),
                             traced.tickets.values()):
        assert t_base.status == t_obs.status == SERVED
        assert merge_lib.results_identical(t_base.result, t_obs.result)
    # tracing cost the virtual timeline nothing: same makespans
    assert traced._virtual_now > 0.0
    assert obs.metrics.value("tickets.served") == len(EXPRS)


def test_cache_hit_records_short_span_and_tier_metric():
    obs = Observability(origin="fe0")
    svc = QueryService(make_store(), obs=obs)
    svc.submit(EXPRS[0])
    svc.drain()
    tid = svc.submit(EXPRS[0])  # L1 hit: answered with zero brick I/O
    assert svc.result(tid).from_cache
    assert obs.metrics.value("cache.hits_l1") == 1
    sub = [s for s in obs.tracer.spans
           if s.name == "submit" and s.ticket == tid]
    assert len(sub) == 1 and sub[0].status == STATUS_OK
    assert sub[0].attrs["cache_tier"] == "l1"
    finals = [s for s in obs.tracer.spans
              if s.name == "final" and s.ticket == tid]
    assert len(finals) == 1 and finals[0].attrs["cached"] is True
    svc.close()
    assert obs.tracer.open_spans() == []


def test_rejected_and_aborted_streams_close_spans_with_error():
    obs = Observability(origin="fe0")
    svc = QueryService(make_store(), obs=obs)
    bad = svc.submit("&& e_total", stream=True)  # parse error -> rejected
    assert svc.result(bad).status == REJECTED
    assert svc.stream(bad).state == ABORTED
    assert obs.metrics.value("submit.rejected") == 1

    pending = svc.submit(EXPRS[0], stream=True)
    svc.close()  # truncated: never dispatched; close aborts the stream
    assert svc.stream(pending).state == ABORTED
    assert obs.tracer.open_spans() == []
    by_ticket = {s.ticket: s for s in obs.tracer.spans
                 if s.name == "stream"}
    assert by_ticket[bad].status == STATUS_ERROR
    assert by_ticket[pending].status == STATUS_ERROR
    assert by_ticket[pending].attrs["note"] == "service closed"
    assert validate_records(obs.tracer.records()) == []


TICKET_SPANS = ("submit", "window", "plan", "dispatch", "final")


def _ticket_view(obs):
    recs = [r for r in obs.tracer.records() if r["name"] in TICKET_SPANS]
    recs = comparable_records(recs, virtual=False)
    # packet-span interleaving shifts span ids between backends; the
    # ticket-visible structure is ids-free
    for r in recs:
        r.pop("span_id"), r.pop("parent_id")
    return recs


def test_sim_and_spmd_ticket_spans_identical():
    views = []
    for backend in ("sim", "spmd"):
        obs = Observability(origin="fe0")
        svc, _ = run_service(make_store(seed=17), obs=obs,
                             backend=backend)
        assert validate_records(obs.tracer.records()) == []
        views.append(_ticket_view(obs))
    assert views[0] == views[1]


SPMD_KW = {"use_pallas": True, "chunk_events": 16}


def _spmd_run(obs, exprs):
    store = make_store(seed=19)
    svc = QueryService(store, backend="spmd", obs=obs,
                       backend_kwargs=SPMD_KW)
    tids = [svc.submit(e, tenant=f"t{i % 2}", stream=True)
            for i, e in enumerate(exprs)]
    svc.drain()
    svc.close()
    return store, svc, tids


@pytest.mark.parametrize("mixed, resident",
                         [(False, True), (True, True), (False, False),
                          (True, False)],
                         ids=["kernel", "mixed", "kernel-streamed",
                              "mixed-streamed"])
def test_spmd_scan_phase_spans(monkeypatch, mixed, resident):
    """Each kernel chunk's host work lies in exactly one ``stage``,
    ``launch``, ``wait`` and ``merge`` span under its ``packet`` span
    (the pipeline interleaves chunks, so the parent is explicit);
    ``spmd.h2d_bytes`` counts the inputs sent to the device: a resident
    store's once, in the ``upload`` span, a streamed store's chunk by
    chunk in every window (the full tracks for a mixed window, the
    ``pt`` plane for a kernel-only one); the finals are those of the
    untraced (resident) run."""
    from repro.core import backend as backend_lib
    from repro.obs import SPAN_NAMES
    exprs = EXPRS if mixed else EXPRS[:2]
    obs = Observability(origin="fe0")
    with monkeypatch.context() as m:
        if not resident:
            m.setattr(backend_lib, "_device_room", lambda device: 0)
        store, svc, tids = _spmd_run(obs, exprs)
    recs = obs.tracer.records()
    assert validate_records(recs) == []
    assert {r["name"] for r in recs} <= set(SPAN_NAMES)
    packets = {r["span_id"]: r for r in recs if r["name"] == "packet"}
    assert len(packets) == obs.metrics.value("packet.count") > 0
    phases = {pid: {} for pid in packets}
    for r in recs:
        if r["name"] in ("stage", "launch", "wait", "merge"):
            kids = phases[r["parent_id"]]
            kids[r["name"]] = kids.get(r["name"], 0) + 1
            assert r["attrs"]["seq"] == packets[r["parent_id"]]["attrs"]["seq"]
            p = packets[r["parent_id"]]
            assert p["t0_wall"] <= r["t0_wall"] <= r["t1_wall"] <= p["t1_wall"]
    assert all(k == {"stage": 1, "launch": 1, "wait": 1, "merge": 1}
               for k in phases.values())
    inputs = sum(int(b[k].nbytes) for b in store.bricks.values()
                 for k in ("scalars", "tracks", "n_tracks"))
    per_window = inputs if mixed else sum(
        int(b["scalars"].nbytes + b["n_tracks"].nbytes
            + b["tracks"][..., 0].nbytes) for b in store.bricks.values())
    windows = obs.metrics.value("window.dispatched")
    staged = sum(r["attrs"]["bytes"] for r in recs if r["name"] == "stage")
    uploads = [r for r in recs if r["name"] == "upload"]
    if resident:
        assert staged == 0
        assert [u["attrs"]["bytes"] for u in uploads] == [inputs]
        assert obs.metrics.value("spmd.h2d_bytes") == inputs > 0
        assert obs.metrics.value("spmd.resident_chunks") == (
            len(packets) if mixed else 0)
    else:
        assert not uploads
        assert obs.metrics.value("spmd.h2d_bytes") == staged \
            == windows * per_window > 0
    merges = [r for r in recs if r["name"] == "merge"]
    assert obs.metrics.value("stream.published") == sum(
        r["attrs"]["published"] for r in merges) > 0

    _, base, base_tids = _spmd_run(None, exprs)
    for a, b in zip(tids, base_tids):
        ta, tb = svc.result(a), base.result(b)
        assert ta.status == tb.status == SERVED
        assert merge_lib.results_identical(ta.result, tb.result)
        fa, fb = svc.stream(a).latest(), base.stream(b).latest()
        assert fa.final and fb.final
        assert merge_lib.results_identical(fa.result, fb.result)


def test_merge_span_on_the_simulated_backend():
    """The simulated grid streams through the same publisher: one
    ``merge`` span per packet, parented to it, and no kernel phases."""
    obs = Observability(origin="fe0")
    run_service(make_store(seed=23), obs=obs, stream=True)
    recs = obs.tracer.records()
    assert validate_records(recs) == []
    packets = {r["span_id"] for r in recs if r["name"] == "packet"}
    merges = [r for r in recs if r["name"] == "merge"]
    assert len(merges) == len(packets) > 0
    assert {r["parent_id"] for r in merges} == packets
    assert not any(r["name"] in ("stage", "launch", "wait") for r in recs)
    assert obs.metrics.value("spmd.h2d_bytes") == 0


def test_real_mesh_scan_phase_spans():
    """On a real two-device mesh each group stages and launches once,
    under its first slot's packet span; every slot waits and merges
    under its own."""
    from tests.test_multidevice import run_with_devices
    run_with_devices("""
        from tests.test_obs import EXPRS, make_store
        from repro.obs import Observability, validate_records
        from repro.service import QueryService
        obs = Observability(origin="fe0")
        store = make_store(seed=19)
        svc = QueryService(store, backend="spmd", obs=obs,
                           backend_kwargs={"use_pallas": True,
                                           "chunk_events": 8,
                                           "mesh_devices": 2})
        for e in EXPRS:
            svc.submit(e, stream=True)
        svc.drain()
        svc.close()
        assert svc.backend._mesh_is_real()
        recs = obs.tracer.records()
        assert validate_records(recs) == []
        packets = {r["span_id"]: r for r in recs if r["name"] == "packet"}
        count = {}
        for r in recs:
            if r["parent_id"] in packets:
                key = (r["parent_id"], r["name"])
                count[key] = count.get(key, 0) + 1
        for pid in packets:
            assert count[(pid, "wait")] == count[(pid, "merge")] == 1
        stages = [r for r in recs if r["name"] == "stage"]
        launches = [r for r in recs if r["name"] == "launch"]
        assert [r["parent_id"] for r in stages] == \
            [r["parent_id"] for r in launches]
        assert all(r["parent_id"] in packets for r in stages)
        assert sum(r["attrs"]["chunks"] for r in stages) == len(packets)
        assert any(r["attrs"]["chunks"] == 2 for r in stages)
        assert obs.metrics.value("spmd.h2d_bytes") == sum(
            r["attrs"]["bytes"] for r in stages) > 0
        print("OK")
    """, n=2)


def test_scheduler_health_gate_narrows_windows():
    obs = Observability(origin="fe0")
    for node in (0, 1):
        for _ in range(5):
            obs.health.observe_packet(node, size=100, wall_s=0.01)
    for _ in range(5):
        obs.health.observe_failure(1)  # node 1 -> suspect

    def fill(sched):
        for i in range(8):
            sched.enqueue(make_submission(i, f"t{i}", EXPRS[0], 0, SCHEMA,
                                          n_events=256))

    gated = QueryScheduler(max_batch=8, obs=obs, health_gate=True)
    fill(gated)
    window = gated.next_batch()
    assert len(window) == 4  # healthy_fraction 0.5 halves the window
    assert gated.last_health_hint["healthy_fraction"] == 0.5
    assert gated.last_health_hint["suspect"] == [1]
    assert obs.metrics.value("sched.health_hints") == 1

    ungated = QueryScheduler(max_batch=8, obs=obs)
    fill(ungated)
    assert len(ungated.next_batch()) == 8
    assert ungated.last_health_hint is None


def test_fleet_metrics_reconcile_with_fleet_stats(tmp_path):
    store = make_store(n_events=256)
    fleet = Fleet(store, 2, obs=True)
    fleet.submit(EXPRS[0], frontend=0)
    fleet.drain()
    fleet.submit(EXPRS[0], frontend=0)  # L1 hit at fe0
    fleet.submit(EXPRS[0], frontend=1)  # L2 hit via the shared tier
    fleet.submit(EXPRS[1], frontend=1)
    fleet.drain()

    snap = fleet.metrics_snapshot()
    stats = fleet.fleet_stats()
    assert stats["cache_hits"] == 2 and stats["l2_hits"] == 1
    # the invariant CI's acceptance run pins: merged obs counters
    # reconcile exactly with the service-stats aggregation
    assert (snap.value("cache.hits_l1") + snap.value("cache.hits_l2")
            == stats["cache_hits"])
    assert snap.value("cache.hits_l2") == stats["l2_hits"]
    assert snap.value("tickets.served") == stats["served"]
    assert set(snap.origins) == {"fe0", "fe1", "fleet"}
    assert snap.value("gossip.digests_sent") > 0

    recs = fleet.trace_records()
    assert validate_records(recs) == []
    n = fleet.save_chrome_trace(tmp_path / "fleet.json")
    assert n == len(recs) > 0
    rep = fleet.health_report()
    # states are wall-rate-derived (can jitter on a tiny run); pin the
    # shape: every grid node observed, every state legal
    assert rep is not None
    assert set(rep.states.values()) <= set(HEALTH_STATES)
    assert set(rep.states) == set(range(store.n_nodes))
    fleet.close()
