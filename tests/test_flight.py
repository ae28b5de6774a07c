"""Flight recorder + deterministic replay: the record -> replay
bit-identity contract under drops, partitions, epoch bumps and node
death; recording's exact-zero virtual-clock overhead; log validation;
and the Prometheus/trace satellite surfaces."""
import pytest

from repro.configs.geps_events import reduced
from repro.core import events as ev
from repro.core import merge as merge_lib
from repro.core.brick import create_store
from repro.fabric.bus import MessageBus
from repro.fabric.fleet import Fleet
from repro.obs import flight as flight_lib
from repro.obs import replay as replay_lib
from repro.obs import trace as trace_lib

N_EVENTS, N_NODES, EPB = 400, 4, 40


def mkstore(seed=7):
    schema = ev.EventSchema.from_config(reduced())
    return create_store(schema, n_events=N_EVENTS, n_nodes=N_NODES,
                        events_per_brick=EPB, replication=2, seed=seed)


def faulty_run(*, drop_rate=0.2, bus_seed=3, partition=True, bump=True,
               kill=True, store=None, flight=True):
    """A fleet-of-4 run exercising every nondeterminism-relevant path:
    seeded drops, a partition + heal, a mid-run epoch bump, a grid-node
    death, streams and single-flight adoption.  Returns (fleet-closed
    flight records, final results by gtid, comparable trace records)."""
    store = store if store is not None else mkstore()
    bus = MessageBus(drop_rate=drop_rate, seed=bus_seed)
    fleet = Fleet(store, 4, bus=bus, obs=True, single_flight=True,
                  flight=flight)
    gtids = [fleet.submit("e_total > 40", tenant="a", stream=True),
             fleet.submit("e_total > 40", tenant="b", stream=True),
             fleet.submit("e_t_miss > 30", tenant="c")]
    fleet.step(0)
    if partition:
        # bus-level fault injected OUTSIDE the driver-op log: replay
        # covers it wholesale through the scripted send outcomes
        fleet.bus.partition({"fe0", "fe1"}, {"fe2", "fe3"})
        fleet.pump(2)
        fleet.bus.heal()
    if bump:
        fleet.bump_dataset_version(0)
    if kill:
        fleet.node_leave(1, observed_by=0)
    gtids.append(fleet.submit("e_total > 40", tenant="a"))
    fleet.drain()
    results = {g: fleet.result(g).result for g in gtids}
    trace = trace_lib.comparable_records(fleet.trace_records())
    records = list(fleet.flight.records) if flight else None
    fleet.close()
    return records, results, trace


def test_record_replay_bit_identical_under_faults():
    records, _, trace = faulty_run()
    assert not flight_lib.validate_flight(records)
    # the original store was mutated (node death -> failover,
    # migration): replay MUST drive an equal FRESH store
    report = replay_lib.replay_run(records, store=mkstore())
    assert report.identical, (report.mismatches, report.bus_divergences)
    assert report.overruns == 0
    # stronger than the contract: the replay's own log is byte-equal
    assert report.records == records
    # and the span timeline (wall stamps stripped) matches exactly
    assert trace_lib.comparable_records(report.trace) == trace


def test_recording_is_deterministic():
    a, _, _ = faulty_run()
    b, _, _ = faulty_run()
    assert a == b


def test_flight_leaves_virtual_timeline_exactly_unchanged():
    store_on, store_off = mkstore(), mkstore()
    _, res_on, trace_on = faulty_run(store=store_on, flight=True)
    _, res_off, trace_off = faulty_run(store=store_off, flight=False)
    assert set(res_on) == set(res_off)
    for g in res_on:
        assert merge_lib.results_identical(res_on[g], res_off[g])
    # every span — window makespans included — identical, so the
    # recorder's virtual-clock overhead is exactly zero
    assert trace_on == trace_off


def test_replay_flags_tampered_final():
    records, _, _ = faulty_run()
    tampered = [dict(r) for r in records]
    for rec in tampered:
        if rec["kind"] == "final" and rec.get("digest"):
            rec["digest"] = "0" * 16
            break
    report = replay_lib.replay_run(tampered, store=mkstore())
    assert not report.identical
    assert any("final" in m for m in report.mismatches)


def test_replay_flags_script_divergence():
    records, _, _ = faulty_run()
    tampered = [dict(r) for r in records]
    sends = [r for r in tampered if r["kind"] == "bus_send"]
    sends[len(sends) // 2]["src"] = "fe999"
    report = replay_lib.replay_run(tampered, store=mkstore())
    assert report.bus_divergences
    assert not report.identical


def test_replay_refuses_bad_logs(tmp_path):
    records, _, _ = faulty_run(partition=False, bump=False, kill=False)
    with pytest.raises(replay_lib.ReplayError):
        replay_lib.replay_run(records[2:])  # non-contiguous eids
    with pytest.raises(replay_lib.ReplayError):
        replay_lib.replay_run(
            [r for r in records if r["kind"] != "run_header"],
            store=mkstore())
    with pytest.raises(replay_lib.ReplayError):
        replay_lib.replay_run(records)  # no store_config, no store=


def test_save_load_roundtrip_and_validation(tmp_path):
    records, _, _ = faulty_run(partition=False, bump=False, kill=False)
    path = tmp_path / "flight.jsonl"
    flight_lib.save_flight(records, path)
    assert flight_lib.load_flight(path) == records
    bad = [dict(r) for r in records]
    bad[5]["kind"] = "warp_core_breach"
    bad[6]["cause"] = 10 ** 9
    bad[7]["schema"] = 99
    problems = flight_lib.validate_flight(bad)
    assert len(problems) == 3


def test_cause_chain_reaches_driver_op():
    records, _, _ = faulty_run()
    grants = [r for r in records if r["kind"] == "lease_grant"
              and r["cause"] is not None]
    assert grants
    rec = grants[-1]
    seen = []
    while rec["cause"] is not None:
        seen.append(rec["kind"])
        rec = records[rec["cause"]]
    assert rec["kind"] == "op"


def test_prom_text_exposition():
    from repro.obs import MetricsRegistry
    reg = MetricsRegistry(origin="fe0")
    reg.counter("bus.sent").inc(5)
    reg.gauge("queue.depth").set(3)
    h = reg.histogram("window.makespan_s", edges=(1.0, 2.0))
    for v in (0.5, 1.5, 9.0):
        h.observe(v)
    text = reg.snapshot().to_prom_text()
    assert "# TYPE bus_sent counter\nbus_sent 5.0" in text
    assert "# TYPE queue_depth gauge\nqueue_depth 3.0" in text
    assert 'window_makespan_s_bucket{le="1.0"} 1' in text
    assert 'window_makespan_s_bucket{le="2.0"} 2' in text
    assert 'window_makespan_s_bucket{le="+Inf"} 3' in text
    assert "window_makespan_s_count 3" in text


def test_trace_schema_accepts_lease_key_ticket():
    tr = trace_lib.Tracer(process="fe0")
    tr.event("lease_adopt", ticket="lease:(e_total > 40.0)|c0|",
             owner="fe1")
    tr.event("final", ticket=7, outcome="SERVED")
    records = tr.records()
    assert not trace_lib.validate_records(records)
    chrome = trace_lib.chrome_from_records(records)
    lanes = [e["tid"] for e in chrome["traceEvents"]]
    assert lanes == [-1, 7]  # string tickets share the -1 lane
    assert chrome["traceEvents"][0]["args"]["ticket"].startswith("lease:")


def test_hypothesis_record_replay_identity():
    hypothesis = pytest.importorskip("hypothesis")
    st = hypothesis.strategies

    @hypothesis.settings(max_examples=8, deadline=None)
    @hypothesis.given(drop=st.sampled_from([0.0, 0.15, 0.35]),
                      seed=st.integers(0, 99),
                      partition=st.booleans(), bump=st.booleans(),
                      kill=st.booleans())
    def prop(drop, seed, partition, bump, kill):
        records, results, trace = faulty_run(
            drop_rate=drop, bus_seed=seed, partition=partition,
            bump=bump, kill=kill)
        report = replay_lib.replay_run(records, store=mkstore())
        assert report.identical, (report.mismatches,
                                  report.bus_divergences)
        assert report.records == records
        assert trace_lib.comparable_records(report.trace) == trace
        # replayed finals are bit-identical, not just digest-equal
        finals = {r["gtid"]: r for r in report.records
                  if r["kind"] == "final"}
        for g, res in results.items():
            if res is not None:
                assert finals[g]["digest"] == \
                    flight_lib.result_digest(res)

    prop()
