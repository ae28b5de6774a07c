"""Unified execution backends: contract equivalence (simulated vs SPMD
chunked streaming scan), prefix-merge bit-identity, Pallas epilogue
fusion of fragment-plan targets, SPMD telemetry feeding cost-model
calibration, window-cost-bounded dispatch, L2 persistence, and adaptive
gossip fanout."""
import numpy as np
import pytest

from repro.configs.geps_events import reduced
from repro.core import events as ev
from repro.core import backend as backend_lib
from repro.core import merge as merge_lib
from repro.core.backend import (ChunkController, SimulatedBackend,
                                SpmdBackend, make_backend)
from repro.core.brick import create_store
from repro.core.catalog import DONE, MetadataCatalog
from repro.fabric import SharedCacheTier, adaptive_fanout, rounds_bound
from repro.service import (QueryScheduler, QueryService, fit_cost_weights,
                           plan_window)

CFG = reduced()
SCHEMA = ev.EventSchema.from_config(CFG)

POOL = ["e_total > 40 && count(pt > 15) >= 2",
        "e_total > 30 && count(pt > 15) >= 2",
        "e_t_miss > 25 && count(pt > 15) >= 2",
        "pt_lead > 60 || n_tracks >= 8",
        "e_total > 55 && sum(pt) < 400",
        "e_total + 2 * e_t_miss > 120"]


def make_store(n_events=192, n_nodes=4, seed=7):
    return create_store(SCHEMA, n_events=n_events, n_nodes=n_nodes,
                        events_per_brick=CFG.events_per_brick,
                        replication=2, seed=seed)


def run_window(backend, store, exprs, *, calib=0, ramp=None):
    plan = plan_window(exprs)
    jids = [backend.catalog.submit(e, calib, tuple(sorted(store.bricks)))
            for e in exprs]
    partials = []
    merged, stats = backend.run_batch(jids, plan=plan,
                                      on_partial=partials.append,
                                      packet_ramp=ramp)
    return merged, stats, partials


def matched_backends(store, chunk=16):
    """A (sim, spmd) pair with IDENTICAL packetization: fixed sim packets
    of ``chunk`` events, spmd chunks of ``chunk`` events."""
    sim = SimulatedBackend(MetadataCatalog(store.n_nodes), store,
                           adaptive_packets=False)
    sim.engine.adaptive_packets = False
    spmd = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                       chunk_events=chunk)
    # the sim's fixed packet size is the scheduler base (64); pin it to
    # the spmd chunk so decompositions line up exactly
    return sim, spmd


def assert_window_equivalent(sim_out, spmd_out):
    (m1, s1, p1), (m2, s2, p2) = sim_out, spmd_out
    assert s1.packets == s2.packets == len(p1) == len(p2)
    for a, b in zip(m1, m2):
        assert merge_lib.results_identical(a, b)
    for pa, pb in zip(p1, p2):
        assert (pa.seq, pa.brick_id, pa.start, pa.size) == \
               (pb.seq, pb.brick_id, pb.start, pb.size)
        assert all(merge_lib.results_identical(a, b)
                   for a, b in zip(pa.partials, pb.partials))
    assert set(s1.fragment_results) == set(s2.fragment_results)
    for key, res in s1.fragment_results.items():
        assert merge_lib.results_identical(res, s2.fragment_results[key])


# ----------------------- contract equivalence --------------------------- #
def test_backends_bit_identical_on_matched_packetization():
    store = make_store()
    sim, spmd = matched_backends(store, chunk=64)
    out1 = run_window(sim, store, POOL, calib=2)
    out2 = run_window(spmd, store, POOL, calib=2)
    assert_window_equivalent(out1, out2)
    # both catalogues converged to DONE with the same result summaries
    for cat in (sim.catalog, spmd.catalog):
        assert all(r.status == DONE for r in cat.jobs.values())


def test_backend_equivalence_property():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    store = make_store(n_events=96)

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 99),
           calib=st.sampled_from([0, 2]),
           k=st.integers(1, 4))
    def check(seed, calib, k):
        rng = np.random.default_rng(seed)
        exprs = [POOL[i] for i in rng.choice(len(POOL), size=k,
                                             replace=False)]
        sim, spmd = matched_backends(store, chunk=64)
        assert_window_equivalent(
            run_window(sim, store, exprs, calib=calib),
            run_window(spmd, store, exprs, calib=calib))

    check()


def test_spmd_prefix_snapshots_bit_identical_to_tree_merge():
    store = make_store()
    spmd = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                       chunk_events=16)
    merged, stats, partials = run_window(spmd, store, POOL[:3])
    assert stats.packets == len(partials) > 1
    for col in range(len(POOL[:3])):
        acc = merge_lib.MergeAccumulator()
        for k, pp in enumerate(partials, 1):
            acc.add(pp.partials[col], brick_id=pp.brick_id)
            want = merge_lib.tree_merge(
                [p.partials[col] for p in partials[:k]])
            assert merge_lib.results_identical(acc.snapshot(), want)
        assert merge_lib.results_identical(acc.snapshot(), merged[col])
    # merge order is deterministic: brick id ascending, offset ascending
    order = [(p.brick_id, p.start) for p in partials]
    assert order == sorted(order)
    # wall-clock availability stamps are non-decreasing
    times = [p.t_virtual for p in partials]
    assert times == sorted(times)


def test_spmd_packet_ramp_caps_early_chunks():
    store = make_store()
    spmd = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                       chunk_events=16)
    _, _, partials = run_window(spmd, store, ["e_total > 40"], ramp=4)
    assert partials[0].size == 4
    assert partials[1].size == 8
    assert max(p.size for p in partials) <= 16


def test_spmd_rejects_failure_script():
    store = make_store()
    spmd = SpmdBackend(MetadataCatalog(store.n_nodes), store)
    assert not spmd.supports_failure_injection
    jid = spmd.catalog.submit("e_total > 40", 0,
                              tuple(sorted(store.bricks)))
    with pytest.raises(ValueError, match="simulated-grid"):
        spmd.run_batch([jid], failure_script={0.5: 1})


def test_service_rejects_failure_script_before_dequeue():
    store = make_store()
    svc = QueryService(store, backend="spmd")
    tid = svc.submit("e_total > 40", stream=True)
    with pytest.raises(ValueError, match="failure"):
        svc.step(failure_script={1.0: 2})
    # nothing was mutated: the window is still queued, the ticket
    # pending, the stream open — the query runs fine afterwards
    assert svc.scheduler.n_pending == 1
    assert svc.result(tid).status == "QUEUED"
    assert not svc.stream(tid).closed
    svc.step()
    assert svc.result(tid).status == "SERVED"
    assert svc.stream(tid).done
    svc.close()


def test_service_rejects_simulation_knobs_on_spmd_backend():
    from repro.core.jse import TimeModel
    store = make_store()
    with pytest.raises(ValueError, match="simulation knobs"):
        QueryService(store, backend="spmd", time_model=TimeModel())
    spmd = SpmdBackend(MetadataCatalog(store.n_nodes), store)
    with pytest.raises(ValueError, match="pre-built instance"):
        QueryService(store, backend=spmd, node_speed={0: 0.5})


def test_make_backend_factory():
    store = make_store()
    cat = MetadataCatalog(store.n_nodes)
    assert isinstance(make_backend("sim", cat, store), SimulatedBackend)
    assert isinstance(make_backend("spmd", cat, store), SpmdBackend)
    with pytest.raises(ValueError, match="unknown backend"):
        make_backend("tpu", cat, store)


# ----------------------- Pallas epilogue fusion ------------------------- #
def test_match_epilogue_relaxed_family():
    from repro.kernels.event_filter import ops as ef_ops
    full = ef_ops.match_epilogue(
        "e_total > 40 && count(pt > 15) >= 2 && sum(pt) < 400", SCHEMA)
    assert full["scalar_thresh"] == 40 and full["min_count"] == 2 \
        and full["sum_cap"] == 400
    bare_count = ef_ops.match_epilogue("count(pt > 15) >= 2", SCHEMA)
    assert bare_count is not None
    assert bare_count["scalar_thresh"] == float("-inf")
    assert bare_count["min_count"] == 2
    lone_scalar = ef_ops.match_epilogue("e_t_miss > 25", SCHEMA)
    assert lone_scalar is not None and lone_scalar["min_count"] == 0
    # outside the conjunctive family
    assert ef_ops.match_epilogue("pt_lead > 60 || n_tracks >= 8",
                                 SCHEMA) is None
    assert ef_ops.match_epilogue("e_total + 2 * e_t_miss > 120",
                                 SCHEMA) is None
    assert ef_ops.match_epilogue("sum(pt) < 0", SCHEMA) is None  # aliases
    assert ef_ops.match_epilogue("nope > 3", SCHEMA) is None


def test_spmd_pallas_fusion_matches_jnp_plan():
    store = make_store(n_events=96)
    exprs = POOL[:3]  # shared count fragment -> materialized target
    plan = plan_window(exprs)
    assert plan.materialize, "expected a materialized shared fragment"
    ref = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                      chunk_events=32)
    fused = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                        chunk_events=32, use_pallas=True)
    # the fusion hook actually engages for this window (every target —
    # roots AND the materialized boolean fragment — is in-family)
    assert fused._fuse_plan(plan) is not None
    out_ref = run_window(ref, store, exprs, calib=2)
    out_fused = run_window(fused, store, exprs, calib=2)
    assert_window_equivalent(out_ref, out_fused)


def test_spmd_pallas_falls_back_on_out_of_family_target():
    store = make_store(n_events=64)
    fused = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                        use_pallas=True)
    plan = plan_window(["pt_lead > 60 || n_tracks >= 8"])
    assert fused._fuse_plan(plan) is None
    merged, _, _ = run_window(fused, store,
                              ["pt_lead > 60 || n_tracks >= 8"])
    sim = SimulatedBackend(MetadataCatalog(store.n_nodes), store,
                           adaptive_packets=False)
    want, _, _ = run_window(sim, store, ["pt_lead > 60 || n_tracks >= 8"])
    assert merge_lib.results_identical(merged[0], want[0])


# ----------------------- mixed-window splitting ------------------------- #
MIXED = [POOL[0], POOL[3], POOL[4], POOL[5]]  # 2 in-family, 2 out


def test_spmd_mixed_window_splits_kernel_and_jnp():
    """A window with BOTH in-family and out-of-family targets no longer
    falls back wholesale to jnp: the in-family targets run as a kernel
    sub-batch (kernel_events > 0) and everything stays bit-identical to
    the pure-jnp scan — finals AND per-packet partials."""
    store = make_store(n_events=96)
    plain = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                        chunk_events=32)
    fused = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                        chunk_events=32, use_pallas=True)
    plan = plan_window(MIXED)
    split = fused._split_plan(plan)
    assert split.any_kernel and not split.full_kernel
    assert fused._fuse_plan(plan) is None  # not FULLY fused...
    out_plain = run_window(plain, store, MIXED, calib=2)
    out_fused = run_window(fused, store, MIXED, calib=2)
    assert_window_equivalent(out_plain, out_fused)
    # ...yet the kernel sub-batch actually ran (the acceptance signal)
    assert out_fused[1].kernel_events == store.n_events
    assert out_plain[1].kernel_events == 0


def test_mixed_split_bit_identity_property():
    """Property: for ANY subset of the pool (some targets epilogue-
    eligible, some not) and any chunking, the kernel/jnp split returns
    bit-identical finals and prefix snapshots vs the pure-jnp path."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    store = make_store(n_events=96)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 999), k=st.integers(1, 5),
           chunk=st.sampled_from([16, 48, 96]), calib=st.sampled_from([0, 2]))
    def check(seed, k, chunk, calib):
        rng = np.random.default_rng(seed)
        exprs = [POOL[i] for i in rng.choice(len(POOL), size=k,
                                             replace=False)]
        plain = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                            chunk_events=chunk)
        fused = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                            chunk_events=chunk, use_pallas=True)
        out_plain = run_window(plain, store, exprs, calib=calib)
        out_fused = run_window(fused, store, exprs, calib=calib)
        assert_window_equivalent(out_plain, out_fused)
        split = fused._split_plan(plan_window(exprs))
        assert out_fused[1].kernel_events == (
            store.n_events if split.any_kernel else 0)
        # prefix snapshots: accumulate both partial streams in lockstep
        for col in range(k):
            acc_p, acc_f = (merge_lib.MergeAccumulator(),
                            merge_lib.MergeAccumulator())
            for pp, pf in zip(out_plain[2], out_fused[2]):
                acc_p.add(pp.partials[col], brick_id=pp.brick_id)
                acc_f.add(pf.partials[col], brick_id=pf.brick_id)
                assert merge_lib.results_identical(acc_p.snapshot(),
                                                   acc_f.snapshot())

    check()


# ----------------------- adaptive chunk sizing -------------------------- #
def test_chunk_controller_converges_to_target():
    ctl = ChunkController(initial=64, min_chunk=8, max_chunk=4096,
                          target_s=0.01, alpha=0.5, hysteresis=0.0)
    assert ctl.chunk() == 64
    for _ in range(32):
        ctl.observe(events=64, wall_s=0.001)  # steady 64k events/s
    # rate EWMA converged; proposal = rate * target_s = 640
    assert abs(ctl.scan_rate - 64_000) / 64_000 < 1e-6
    assert ctl.chunk() == 640
    # clamping: a crawling scan floors at min_chunk
    for _ in range(64):
        ctl.observe(events=8, wall_s=10.0)
    assert ctl.chunk() == 8


def test_chunk_controller_hysteresis_dead_band():
    ctl = ChunkController(initial=100, target_s=1.0, alpha=1.0,
                          hysteresis=0.25)
    ctl.observe(events=100, wall_s=1.0)   # rate 100 -> proposal 100
    assert ctl.chunk() == 100
    ctl.observe(events=110, wall_s=1.0)   # +10% < 25% dead-band: held
    assert ctl.chunk() == 100
    ctl.observe(events=200, wall_s=1.0)   # +100%: moves
    assert ctl.chunk() == 200
    # ignores degenerate observations
    ctl.observe(events=0, wall_s=1.0)
    ctl.observe(events=10, wall_s=0.0)
    assert ctl.chunk() == 200


def test_chunk_controller_validation():
    with pytest.raises(ValueError):
        ChunkController(alpha=0.0)
    with pytest.raises(ValueError):
        ChunkController(min_chunk=0)
    with pytest.raises(ValueError):
        ChunkController(min_chunk=64, max_chunk=8)
    with pytest.raises(ValueError):
        ChunkController(target_s=0.0)
    with pytest.raises(ValueError):
        ChunkController(hysteresis=-0.1)


class TickClock:
    """Deterministic injectable clock: advances a fixed dt per call, so
    measured 'walls' — and everything derived from them, like adaptive
    chunk boundaries — replay identically run over run."""

    def __init__(self, dt=0.003):
        self.t, self.dt = 0.0, dt

    def __call__(self):
        self.t += self.dt
        return self.t


def test_spmd_adaptive_chunks_resize_and_stay_correct():
    """Adaptive chunks change packetization, not answers: with the same
    injected clock, the jnp and kernel-split scans pick the SAME chunk
    boundaries and stay bit-identical; against a sim reference the exact
    counts match and sum_var agrees to float regrouping."""
    store = make_store()

    def adaptive(**kw):
        return SpmdBackend(MetadataCatalog(store.n_nodes), store,
                           chunk_events=8, adaptive_chunks=True,
                           chunk_target_s=0.05, clock=TickClock(), **kw)

    out_jnp = run_window(adaptive(), store, POOL[:3], calib=2)
    out_ker = run_window(adaptive(use_pallas=True), store, POOL[:3],
                         calib=2)
    assert_window_equivalent(out_jnp, out_ker)
    partials = out_jnp[2]
    # the controller actually moved chunk sizes off the initial value
    assert len({p.size for p in partials}) > 1
    # exact-count agreement with the simulated reference (sum_var may
    # regroup: adaptive chunk boundaries differ from sim packets)
    sim = SimulatedBackend(MetadataCatalog(store.n_nodes), store,
                           adaptive_packets=False)
    want, _, _ = run_window(sim, store, POOL[:3], calib=2)
    for a, b in zip(want, out_jnp[0]):
        assert a.n_selected == b.n_selected
        assert a.n_processed == b.n_processed
        assert np.allclose(a.sum_var, b.sum_var)
        assert np.array_equal(a.hist, b.hist)


def test_spmd_adaptive_chunks_deterministic_flight_log(tmp_path):
    """Adaptive chunk sizing must not break the flight recorder's
    byte-identical replayability: with the backend clock injected, two
    identical runs produce identical chunk boundaries, identical stream
    snapshots, and byte-identical flight logs."""
    from repro.fabric.fleet import Fleet

    def one_run(path):
        store = make_store()
        fleet = Fleet(store, 2, backend="spmd", obs=True, flight=True,
                      backend_kwargs=dict(chunk_events=8,
                                          adaptive_chunks=True,
                                          chunk_target_s=0.05,
                                          clock=TickClock()))
        for i, e in enumerate(POOL[:3]):
            fleet.submit(e, tenant=f"t{i}", stream=True)
        fleet.drain()
        fleet.save_flight(path)
        fleet.close()
        return path.read_bytes()

    a = one_run(tmp_path / "a.jsonl")
    b = one_run(tmp_path / "b.jsonl")
    assert a == b


# ----------------------- mesh-sharded chunks ---------------------------- #
def test_spmd_mesh_lockstep_emulation_bit_identical():
    """mesh_devices > jax devices: the mesh is emulated with lockstep
    critical-path accounting — results and partials stay bit-identical
    to the single-device scan, stamps ride the lockstep clock."""
    store = make_store(n_events=96)
    base = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                       chunk_events=16, use_pallas=True)
    mesh = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                       chunk_events=16, use_pallas=True, mesh_devices=4)
    assert not mesh._mesh_is_real()
    out_base = run_window(base, store, POOL, calib=2)
    out_mesh = run_window(mesh, store, POOL, calib=2)
    assert_window_equivalent(out_base, out_mesh)
    stats, partials = out_mesh[1], out_mesh[2]
    # lockstep makespan is the sum of per-group maxima: no larger than
    # the serial sum of walls, no smaller than the largest single wall
    serial = sum(t.wall_s for t in stats.packet_telemetry)
    assert max(t.wall_s for t in stats.packet_telemetry) \
        <= stats.makespan_s <= serial + 1e-9
    times = [p.t_virtual for p in partials]
    assert times == sorted(times)


def test_spmd_mesh_wider_than_accelerator_devices_raises(monkeypatch):
    """Lockstep emulation is a CPU-only stand-in: on an accelerator a
    mesh wider than the devices fails instead of reporting made-up
    times."""
    import jax
    store = make_store(n_events=32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                       chunk_events=16, use_pallas=True,
                       mesh_devices=len(jax.devices()) + 1)
    with pytest.raises(ValueError, match="mesh_devices"):
        mesh._mesh_is_real()


def test_spmd_real_mesh_shard_map_bit_identical():
    """With enough physical devices, mesh groups execute as ONE
    shard_map call over stacked padded sub-chunks — and partials stay
    bit-identical to the sequential scan (subprocess: jax pins its
    device count at first init)."""
    from tests.test_multidevice import run_with_devices
    run_with_devices("""
        from tests.test_backend import (make_store, run_window, POOL,
                                        assert_window_equivalent)
        from repro.core.backend import SpmdBackend
        from repro.core.catalog import MetadataCatalog
        assert len(jax.devices()) == 2
        store = make_store(n_events=96)
        base = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                           chunk_events=16, use_pallas=True)
        mesh = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                           chunk_events=16, use_pallas=True,
                           mesh_devices=2)
        out_base = run_window(base, store, POOL, calib=2)
        out_mesh = run_window(mesh, store, POOL, calib=2)
        assert mesh._mesh_is_real()
        assert_window_equivalent(out_base, out_mesh)
        assert out_mesh[1].kernel_events == store.n_events
        print("OK")
    """, n=2)


def test_spmd_double_buffer_preserves_order_and_results():
    store = make_store(n_events=96)
    on = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                     chunk_events=16, use_pallas=True, double_buffer=True)
    off = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                      chunk_events=16, use_pallas=True,
                      double_buffer=False)
    assert_window_equivalent(run_window(on, store, MIXED, calib=2),
                             run_window(off, store, MIXED, calib=2))


def test_spmd_autotune_uses_cached_winner():
    from repro.kernels.event_filter import tune as ef_tune
    ef_tune.clear_cache()
    store = make_store(n_events=96)
    tuned = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                        chunk_events=32, use_pallas=True, autotune=True)
    plain = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                        chunk_events=32, use_pallas=True)
    out_tuned = run_window(tuned, store, POOL[:3], calib=2)
    out_plain = run_window(plain, store, POOL[:3], calib=2)
    assert_window_equivalent(out_plain, out_tuned)
    assert tuned.last_autotune is not None
    assert tuned.last_autotune.speedup_vs_default >= 1.0
    assert len(ef_tune.cached_shapes()) == 1
    # a second window of the same shape class pays no new sweep
    run_window(tuned, store, POOL[:3], calib=2)
    assert len(ef_tune.cached_shapes()) == 1


def test_service_backend_kwargs_thread_through():
    store = make_store()
    svc = QueryService(store, backend="spmd",
                       backend_kwargs=dict(use_pallas=True,
                                           chunk_events=24))
    assert svc.backend.use_pallas and svc.backend.chunk_events == 24
    tid = svc.submit(POOL[0])
    svc.step()
    assert svc.result(tid).status == "SERVED"
    svc.close()
    spmd = SpmdBackend(MetadataCatalog(store.n_nodes), store)
    with pytest.raises(ValueError, match="pre-built instance"):
        QueryService(store, backend=spmd,
                     backend_kwargs=dict(chunk_events=8))


# ----------------------- resident store --------------------------------- #
def force_streaming(monkeypatch):
    """Make every device report less free memory than any store needs."""
    monkeypatch.setattr(backend_lib, "_device_room", lambda device: 0)


@pytest.mark.parametrize("exprs", [POOL[:3], MIXED],
                         ids=["kernel", "mixed"])
@pytest.mark.parametrize("double_buffer", [True, False])
def test_spmd_resident_matches_streamed(monkeypatch, exprs, double_buffer):
    """Chunks sliced from the resident image give the per-chunk partials,
    prefix snapshots, fragment results and finals of chunks streamed
    from the host, bit for bit, in kernel and mixed windows."""
    store = make_store(n_events=96)
    # the six bricks upload in three landed groups of two
    brick = sum(int(store.bricks[0][k].nbytes)
                for k in ("scalars", "tracks", "n_tracks"))
    monkeypatch.setattr(backend_lib, "UPLOAD_GROUP_BYTES", 2 * brick)

    def backend():
        # chunks of 6 over bricks of 16: starts inside a brick and a
        # shorter tail chunk
        return SpmdBackend(MetadataCatalog(store.n_nodes), store,
                           chunk_events=6, use_pallas=True,
                           double_buffer=double_buffer)

    # streamed first: a backend built later over the same store would
    # share the image the resident one holds
    with monkeypatch.context() as m:
        force_streaming(m)
        streamed = backend()
        out_streamed = run_window(streamed, store, exprs, calib=2)
    assert streamed._image is None
    resident = backend()
    out_resident = run_window(resident, store, exprs, calib=2)
    assert resident._image is not None
    assert_window_equivalent(out_streamed, out_resident)
    for col in range(len(exprs)):
        acc_s, acc_r = (merge_lib.MergeAccumulator(),
                        merge_lib.MergeAccumulator())
        for ps, pr in zip(out_streamed[2], out_resident[2]):
            acc_s.add(ps.partials[col], brick_id=ps.brick_id)
            acc_r.add(pr.partials[col], brick_id=pr.brick_id)
            assert merge_lib.results_identical(acc_s.snapshot(),
                                               acc_r.snapshot())


@pytest.mark.parametrize("resident", [True, False],
                         ids=["resident", "streamed"])
def test_spmd_h2d_bytes_over_three_windows(monkeypatch, resident):
    """A resident store crosses to the device once: ``spmd.h2d_bytes`` is
    the store's kernel inputs after three windows, and every chunk is
    served from the image.  A store that does not fit streams them
    every window, as before residency."""
    from repro.obs import Observability
    if not resident:
        force_streaming(monkeypatch)
    store = make_store(n_events=96)
    obs = Observability()
    svc = QueryService(store, backend="spmd", obs=obs,
                       backend_kwargs=dict(use_pallas=True, chunk_events=16))
    for expr in POOL[:3]:
        tid = svc.submit(expr, calib_iters=2, stream=True)
        svc.step()
        assert svc.result(tid).status == "SERVED"
    svc.close()
    inputs = sum(int(b[k].nbytes) for b in store.bricks.values()
                 for k in ("scalars", "tracks", "n_tracks"))
    chunks = obs.metrics.value("packet.count")
    assert obs.metrics.value("window.dispatched") == 3
    uploads = [r for r in obs.tracer.records() if r["name"] == "upload"]
    if resident:
        assert obs.metrics.value("spmd.h2d_bytes") == inputs
        assert obs.metrics.value("spmd.resident_chunks") == chunks > 0
        assert obs.metrics.value("spmd.resident_bytes") >= inputs
        assert [u["attrs"]["bytes"] for u in uploads] == [inputs]
    else:
        assert obs.metrics.value("spmd.h2d_bytes") == 3 * inputs
        assert obs.metrics.value("spmd.resident_chunks") == 0
        assert not uploads


def test_fleet_frontends_share_one_image():
    """Front-ends of a ``Fleet`` over one store scan one resident image,
    and it is freed once both are gone."""
    import gc
    import weakref
    from repro.fabric import Fleet
    store = make_store(n_events=96)
    fleet = Fleet(store, 2, backend="spmd",
                  backend_kwargs=dict(use_pallas=True, chunk_events=16))
    for i in range(2):
        fleet.submit(POOL[i], frontend=i)
    fleet.drain()
    backends = [fe.service.backend for fe in fleet.frontends]
    image = backends[0]._image
    assert image is not None and backends[1]._image is image
    assert list(backend_lib._IMAGES.values()).count(image) == 1
    gone = weakref.ref(image)
    fleet.close()
    del fleet, backends, image
    gc.collect()
    assert gone() is None
    assert all(img.store is not store
               for img in backend_lib._IMAGES.values())


# ----------------------- service integration ---------------------------- #
def test_service_backend_agnostic_end_to_end():
    store = make_store()
    results = {}
    for kind in ("sim", "spmd"):
        svc = QueryService(store, backend=kind, use_cache=True)
        tid = svc.submit(POOL[0], stream=True)
        tid2 = svc.submit(POOL[3])
        svc.drain()
        t = svc.result(tid)
        assert t.status == "SERVED"
        stream = svc.stream(tid)
        assert stream.done and stream.latest().final
        assert merge_lib.results_identical(stream.latest().result,
                                           t.result)
        assert stream.latest().coverage.complete
        # repeat submission is a zero-I/O cache hit on either backend
        tid3 = svc.submit(POOL[0])
        assert svc.result(tid3).from_cache
        results[kind] = (t.result, svc.result(tid2).result)
        svc.close()
    for a, b in zip(results["sim"], results["spmd"]):
        assert a.n_selected == b.n_selected
        assert a.n_processed == b.n_processed
        assert np.array_equal(a.hist, b.hist)
        assert np.array_equal(a.selected_ids, b.selected_ids)
        # different default packetizations regroup the float additions;
        # every decomposition-invariant field above is exact
        assert np.isclose(a.sum_var, b.sum_var, rtol=1e-6)


def test_service_adopts_instance_backend_catalog():
    store = make_store()
    spmd = SpmdBackend(MetadataCatalog(store.n_nodes), store)
    svc = QueryService(store, backend=spmd)
    assert svc.catalog is spmd.catalog and svc.backend is spmd
    assert svc.jse is None  # no simulation engine behind this service
    with pytest.raises(ValueError, match="share one catalogue"):
        QueryService(store, MetadataCatalog(store.n_nodes), backend=spmd)
    other = make_store(seed=9)
    with pytest.raises(ValueError, match="different brick store"):
        QueryService(other, backend=spmd)


def test_spmd_telemetry_calibrates_cost_model():
    store = make_store()
    spmd = SpmdBackend(MetadataCatalog(store.n_nodes), store,
                       chunk_events=16)
    rows = []
    for calib in (0, 4):
        _, stats, _ = run_window(spmd, store, POOL[:2], calib=calib)
        rows.extend(stats.packet_telemetry)
    assert all(t.wall_s > 0 and t.n_targets == 3 for t in rows)
    weights = fit_cost_weights(rows)
    assert weights.fitted and weights.scale > 0
    # service wiring: a refit lands on the backend for the scheduler
    svc = QueryService(store, backend="spmd", refit_cost_every=1)
    svc.submit(POOL[0]), svc.submit(POOL[1])
    svc.drain()
    assert svc.cost_weights is not None
    assert svc.backend.cost_weights is svc.cost_weights
    assert svc.scheduler.backend is svc.backend
    svc.close()


# ----------------------- window-cost bounding --------------------------- #
def test_window_filled_by_cost_not_count():
    store = make_store(n_events=512)
    sched = QueryScheduler(max_batch=64, window_cost_budget=1100.0)
    svc = QueryService(store, scheduler=sched, use_cache=False)
    for i in range(6):
        svc.submit(f"e_total > {40 + i}")  # cost 512 each (no aggs)
    assert len(sched.next_batch()) == 2    # 512 + 512 <= 1100 < 1536
    assert len(sched.next_batch()) == 2
    svc.close()


def test_window_cost_budget_never_starves():
    store = make_store(n_events=512)
    sched = QueryScheduler(window_cost_budget=10.0)
    svc = QueryService(store, scheduler=sched, use_cache=False)
    svc.submit("e_total > 1"), svc.submit("e_total > 2")
    assert len(sched.next_batch()) == 1    # over-budget query runs alone
    assert len(sched.next_batch()) == 1
    svc.close()


def test_window_cost_recosted_with_fitted_weights():
    from repro.service.planner import CostWeights
    sched = QueryScheduler(max_batch=8, window_cost_budget=1600.0)
    svc = QueryService(make_store(n_events=512), scheduler=sched,
                       use_cache=False)
    for i in range(4):
        svc.submit(f"e_total > {30 + i} && count(pt > {10 + i}) >= 2")
    # static prior: cost = 512 * (1 + 4*1) = 2560 > budget -> one alone
    assert len(sched.next_batch()) == 1
    # a refit that learned aggregates are cheap: 512 * 1.5 = 768 each,
    # so two now fit under the same budget
    svc.backend.cost_weights = CostWeights(agg_weight=0.5, fitted=True)
    assert len(sched.next_batch()) == 2
    svc.close()


def test_window_cost_duplicates_ride_free():
    # the front-end dedups identical canonical queries onto ONE
    # execution, so only the first occurrence charges the window budget
    store = make_store(n_events=512)
    sched = QueryScheduler(max_batch=64, window_cost_budget=600.0)
    svc = QueryService(store, scheduler=sched, use_cache=False)
    for i in range(5):
        svc.submit("e_total > 40", tenant=f"t{i}")   # cost 512, same scan
    svc.submit("e_total > 99", tenant="t5")          # second distinct scan
    window = sched.next_batch()
    assert len(window) == 5                          # dupes free; 512+512
    assert {s.canonical for s in window} == \
        {"(e_total > 40.0)"}                         # > 600 stops the 2nd
    svc.close()


def test_count_cap_still_bounds_cheap_windows():
    sched = QueryScheduler(max_batch=3, window_cost_budget=1e12)
    svc = QueryService(make_store(), scheduler=sched, use_cache=False)
    for i in range(5):
        svc.submit(f"e_total > {i}")
    assert len(sched.next_batch()) == 3    # count cap is the fallback
    svc.close()


# ----------------------- L2 persistence --------------------------------- #
def test_shared_tier_persists_and_survives_restart(tmp_path):
    tier = SharedCacheTier(capacity=8)
    res = merge_lib.from_mask(np.array([1, 0, 1]),
                              np.array([10.0, 20.0, 30.5], np.float32),
                              np.array([7, 8, 9]))
    tier.put("(e_total > 40.0)", 2, 0, res, vv={"fe0": 1})
    path = tmp_path / "l2.json"
    tier.save(path)
    loaded = SharedCacheTier.load(path)
    hit = loaded.get("(e_total > 40.0)", 2, 0, vv={"fe0": 1})
    assert hit is not None and merge_lib.results_identical(hit, res)
    # the persisted join still guards hygiene after the restart: a newer
    # vector advances the join and purges the reloaded entry...
    assert loaded.get("(e_total > 40.0)", 2, 0, vv={"fe0": 2}) is None
    assert loaded.stats.invalidated == 1
    # ...after which the OLD vector is refused as stale
    assert loaded.get("(e_total > 40.0)", 2, 0, vv={"fe0": 1}) is None
    assert loaded.stats.stale_refused == 1


def test_shared_tier_roundtrip_preserves_lru_order_and_join():
    tier = SharedCacheTier(capacity=2)
    r1 = merge_lib.QueryResult(n_selected=1, n_processed=2, sum_var=0.5)
    r2 = merge_lib.QueryResult(n_selected=3, n_processed=4, sum_var=1.5)
    tier.put("a", 0, 1, r1, vv={"fe0": 1})
    tier.put("b", 0, 1, r2, vv={"fe0": 1})
    loaded = SharedCacheTier.from_json(tier.to_json())
    assert len(loaded) == 2
    assert loaded._fp(loaded._join) == tier._fp(tier._join)
    # LRU order survived: inserting one more evicts "a", not "b"
    loaded.put("c", 0, 1, r1, vv={"fe0": 1})
    assert loaded.get("a", 0, 1, vv={"fe0": 1}) is None
    assert loaded.get("b", 0, 1, vv={"fe0": 1}) is not None


def test_query_result_dict_roundtrip_bit_identical():
    rng = np.random.default_rng(3)
    res = merge_lib.from_mask(rng.integers(0, 2, 50),
                              rng.uniform(0, 500, 50).astype(np.float32),
                              rng.integers(0, 10**6, 50))
    back = merge_lib.QueryResult.from_dict(res.to_dict())
    assert merge_lib.results_identical(res, back)
    import json
    via_json = merge_lib.QueryResult.from_dict(
        json.loads(json.dumps(res.to_dict())))
    assert merge_lib.results_identical(res, via_json)


# ----------------------- adaptive gossip fanout ------------------------- #
def test_adaptive_fanout_scales_with_fleet_size():
    assert adaptive_fanout(1) == 1
    assert adaptive_fanout(2) == 1
    assert adaptive_fanout(4) == 2
    assert adaptive_fanout(8) == 3
    assert adaptive_fanout(16) == 4
    assert rounds_bound(16) == 4          # ceil(15/4) with adaptive fanout
    assert rounds_bound(16, 1) == 15      # explicit fanout still honoured
    assert rounds_bound(1) == 0


def test_fleet_defaults_to_adaptive_fanout():
    from repro.fabric import Fleet
    store = make_store()
    fleet = Fleet(store, 4)
    try:
        assert fleet.gossip_fanout == adaptive_fanout(4) == 2
        assert fleet.rounds_bound == rounds_bound(4)
        assert all(len(fe.gossip.targets()) == 2
                   for fe in fleet.frontends)
        # a bump still reaches every peer within the documented bound
        fleet.bump_dataset_version(0)
        fleet.pump(fleet.rounds_bound)
        assert all(fe.catalog.dataset_epoch == 1
                   for fe in fleet.frontends)
    finally:
        fleet.close()
