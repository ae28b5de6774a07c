"""GEPS core behaviour: query compiler, bricks, JSE, merge, packets,
replication, failover, elasticity."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.geps_events import reduced
from repro.core import events as ev
from repro.core import merge as merge_lib
from repro.core import query as query_lib
from repro.core.brick import create_store, gather_store
from repro.core.catalog import DONE, FAILED, MetadataCatalog
from repro.core.elastic import ElasticManager, elastic_mesh_shape
from repro.core.jse import JobSubmissionEngine, TimeModel, spmd_query_step
from repro.core.packets import AdaptivePacketScheduler
from repro.core.replication import failover_owner, place_replicas

CFG = reduced()
SCHEMA = ev.EventSchema.from_config(CFG)


def make_store(n_events=128, n_nodes=4, replication=2):
    return create_store(SCHEMA, n_events=n_events, n_nodes=n_nodes,
                        events_per_brick=CFG.events_per_brick,
                        replication=replication, seed=7)


# ---------------------------- query compiler ----------------------------- #
def test_query_simple_threshold():
    store = make_store()
    batch = gather_store(store)
    fn = query_lib.compile_query("e_total > 40", SCHEMA)
    mask = np.asarray(fn({k: jnp.asarray(v) for k, v in batch.items()}))
    np.testing.assert_array_equal(mask != 0, batch["scalars"][:, 0] > 40)


def test_query_aggregations_and_logic():
    store = make_store()
    batch = gather_store(store)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    fn = query_lib.compile_query(
        "count(pt > 15) >= 2 && sum(pt) < 800 || n_tracks == 1", SCHEMA)
    mask = np.asarray(fn(jb)) != 0
    t = np.arange(SCHEMA.max_tracks)[None, :] < batch["n_tracks"][:, None]
    pt = batch["tracks"][:, :, 0]
    cnt = ((pt > 15) & t).sum(-1)
    ssum = np.where(t, pt, 0).sum(-1)
    expect = ((cnt >= 2) & (ssum < 800)) | (batch["n_tracks"] == 1)
    np.testing.assert_array_equal(mask, expect)


def test_query_arithmetic_precedence():
    store = make_store(n_events=32)
    batch = gather_store(store)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    fn = query_lib.compile_query("e_total + 2 * e_t_miss > 100", SCHEMA)
    mask = np.asarray(fn(jb)) != 0
    s = batch["scalars"]
    np.testing.assert_array_equal(mask, s[:, 0] + 2 * s[:, 1] > 100)


def test_query_errors():
    with pytest.raises(query_lib.QueryError):
        query_lib.compile_query("nonsense_var > 1", SCHEMA)({})
    with pytest.raises(query_lib.QueryError):
        query_lib.parse("e_total >")


# ---------------------------- bricks / replication ----------------------- #
def test_brick_partition_covers_all_events():
    store = make_store(n_events=100)
    assert store.n_events == 100
    ids = np.sort(gather_store(store)["event_id"])
    np.testing.assert_array_equal(ids, np.arange(100))


def test_store_from_config_takes_the_named_width():
    from repro.configs.geps_events import CONFIG, by_width
    from repro.core.brick import store_from_config
    assert by_width("paper") is CONFIG
    with pytest.raises(ValueError, match="width"):
        by_width("huge")
    store = store_from_config(by_width("reduced"), n_events=40, n_nodes=4,
                              seed=7)
    same = make_store(n_events=40)
    assert store.schema == SCHEMA
    assert [s.replicas for s in store.specs.values()] == \
        [s.replicas for s in same.specs.values()]
    for bid, batch in same.bricks.items():
        for key, col in batch.items():
            np.testing.assert_array_equal(store.bricks[bid][key], col)


def test_replica_placement_disjoint():
    for bid in range(16):
        node = bid % 5
        reps = place_replicas(bid, node, 5, 3)
        assert node not in reps and len(set(reps)) == len(reps) == 2


def test_failover_owner():
    assert failover_owner([1, 3, 4], {1}) == 3
    assert failover_owner([1, 3], {1, 3}) == -1


# ---------------------------- JSE end to end ----------------------------- #
def test_jse_job_matches_oracle():
    store = make_store()
    cat = MetadataCatalog(store.n_nodes)
    jse = JobSubmissionEngine(cat, store)
    jid = jse.submit("e_total > 40")
    merged, stats = jse.run_job_simulated(jid)
    batch = gather_store(store)
    expect = int((batch["scalars"][:, 0] > 40).sum())
    assert merged.n_selected == expect
    assert merged.n_processed == store.n_events
    assert cat.jobs[jid].status == DONE
    assert stats.makespan_s > 0


def test_jse_survives_node_failure_with_replicas():
    store = make_store(n_events=256, n_nodes=4, replication=2)
    cat = MetadataCatalog(store.n_nodes)
    jse = JobSubmissionEngine(cat, store)
    jid = jse.submit("e_total > 40")
    # node 1 dies mid-job (virtual time 0.5 s)
    merged, stats = jse.run_job_simulated(jid, failure_script={0.5: 1})
    batch = gather_store(store)
    expect = int((batch["scalars"][:, 0] > 40).sum())
    assert merged.n_selected == expect  # no events lost
    assert cat.jobs[jid].status == DONE


def test_jse_fails_without_replicas_when_node_dies_before_job():
    store = make_store(n_events=256, n_nodes=4, replication=1)
    cat = MetadataCatalog(store.n_nodes)
    cat.mark_dead(1)
    jse = JobSubmissionEngine(cat, store)
    jid = jse.submit("e_total > 40")
    merged, _ = jse.run_job_simulated(jid)
    assert cat.jobs[jid].status == FAILED  # the paper's known weakness


def test_spmd_query_step_matches_host_path():
    store = make_store()
    batch = gather_store(store)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    step = spmd_query_step("e_total > 40", SCHEMA)
    out = step(jb)
    cat = MetadataCatalog(store.n_nodes)
    jse = JobSubmissionEngine(cat, store)
    jid = jse.submit("e_total > 40")
    merged, _ = jse.run_job_simulated(jid)
    assert int(out["n_selected"]) == merged.n_selected
    assert np.isclose(float(out["sum_var"]), merged.sum_var, rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(out["hist"], np.int64), merged.hist)


# ---------------------------- merge ----------------------------- #
def test_tree_merge_associative():
    rng = np.random.default_rng(0)
    parts = []
    for i in range(7):
        mask = rng.integers(0, 2, 50)
        var = rng.uniform(0, 500, 50).astype(np.float32)
        ids = np.arange(i * 50, (i + 1) * 50)
        parts.append(merge_lib.from_mask(mask, var, ids))
    t = merge_lib.tree_merge(parts)
    lin = parts[0]
    for p in parts[1:]:
        lin = merge_lib.merge2(lin, p)
    assert t.n_selected == lin.n_selected
    assert np.isclose(t.sum_var, lin.sum_var)
    np.testing.assert_array_equal(t.hist, lin.hist)


# ---------------------------- packets ----------------------------- #
def test_adaptive_packets_scale_with_speed():
    cat = MetadataCatalog(3)
    cat.node(0).throughput_ema = 4.0
    cat.node(1).throughput_ema = 1.0
    cat.node(2).throughput_ema = 1.0
    sched = AdaptivePacketScheduler(cat, base_packet=60)
    sched.add_work(0, 10_000)
    fast = sched.next_packet(0)
    slow = sched.next_packet(1)
    assert fast.size > slow.size


def test_packet_failure_requeue_preserves_work():
    cat = MetadataCatalog(2)
    sched = AdaptivePacketScheduler(cat, base_packet=16)
    sched.add_work(0, 64)
    done = 0
    pkt = sched.next_packet(0)
    sched.fail(pkt.packet_id, node_dead=True)  # node 0 dies
    while not sched.exhausted:
        pkt = sched.next_packet(1)
        assert pkt is not None
        sched.complete(pkt.packet_id, pkt.size, 0.1)
        done += pkt.size
    assert done == 64  # every event processed exactly once


# ---------------------------- elastic ----------------------------- #
def test_elastic_node_leave_and_rejoin():
    store = make_store(n_events=256, n_nodes=4, replication=2)
    cat = MetadataCatalog(store.n_nodes)
    em = ElasticManager(cat, store)
    plan = em.node_leave(2)
    assert not plan.lost_bricks
    assert all(old == 2 for _, old, _ in plan.reassign_primary)
    em.apply_copies(plan)
    # after re-replication every brick has an alive owner set
    dead = cat.dead_nodes()
    for bid in store.specs:
        assert failover_owner(store.owners(bid), dead) >= 0
    plan2 = em.node_join(2)
    assert isinstance(plan2.reassign_primary, list)


def test_elastic_mesh_shape():
    assert elastic_mesh_shape(256) == (16, 16)
    assert elastic_mesh_shape(255) == (8, 16)
    assert elastic_mesh_shape(512, pods=2) == (2, 16, 16)
    assert elastic_mesh_shape(8) is None


def test_catalog_persistence_roundtrip():
    cat = MetadataCatalog(3)
    jid = cat.submit("e_total > 1", 2, (0, 1))
    cat.update(jid, status=DONE, events_processed=10)
    cat.node(1).observe(100, 2.0)
    cat2 = MetadataCatalog.from_json(cat.to_json())
    assert cat2.jobs[jid].status == DONE
    assert cat2.jobs[jid].bricks == (0, 1)
    assert cat2.nodes[1].throughput_ema == cat.nodes[1].throughput_ema
