"""Node-failure recovery (the paper's acknowledged weakness + future work):
kill a node mid-job and compare outcomes with replication factor 1 vs 2.

r=1: the job FAILS when the dead node's bricks have no replica (the
paper's "biggest disadvantage").  r=2: the packets re-queue onto replica
owners and the result is exactly the no-failure result, at a measured
makespan penalty.

A second pass measures the failure-policy engine (``service/policy.py``)
acting BEFORE the death: seeded failure evidence drives the sick node to
``banned``, the trace proves zero packets were routed to it from that
window on, and sustained degradation proactively re-replicates its
bricks — all while results stay identical to a policy-less service."""
from __future__ import annotations

from repro.configs.geps_events import reduced
from repro.core import events as ev
from repro.core.backend import SimulatedBackend
from repro.core.brick import create_store, gather_store
from repro.core.catalog import FAILED, MetadataCatalog
from repro.core.jse import JobSubmissionEngine, TimeModel
from repro.core import merge as merge_lib
from repro.obs import Observability
from repro.service import QueryService
from repro.service.policy import (POLICY_BANNED, FailurePolicy,
                                  PolicyConfig)

EXPR = "e_total > 40"


def run(replication: int, kill_at=0.5, n_events=2048, n_nodes=4):
    schema = ev.EventSchema.from_config(reduced())
    store = create_store(schema, n_events=n_events, n_nodes=n_nodes,
                         events_per_brick=128, replication=replication,
                         seed=4)
    cat = MetadataCatalog(n_nodes)
    jse = JobSubmissionEngine(cat, store, TimeModel())
    jid = jse.submit(EXPR)
    merged, stats = jse.run_job_simulated(jid, failure_script={kill_at: 1})
    # post-failure the catalogue may report FAILED for r=1 jobs re-run
    import numpy as np
    batch = gather_store(store)
    expect = int((batch["scalars"][:, 0] > 40).sum())
    return {
        "replication": replication,
        "status": cat.jobs[jid].status,
        "selected": merged.n_selected,
        "expected": expect,
        "makespan_s": stats.makespan_s,
        "reassigned": stats.reassigned,
    }


def run_policy(n_events=2048, n_windows=6):
    """Drive a policy-armed service while node 1 keeps failing for two
    windows; report the ban window, packets routed to the banned node
    after it (must be 0), and proactive re-replication volume."""
    schema = ev.EventSchema.from_config(reduced())

    def service(policy_on):
        store = create_store(schema, n_events=n_events, n_nodes=4,
                             events_per_brick=256, replication=2, seed=4)
        cat = MetadataCatalog(4)
        obs = Observability(origin="bench")
        pol = None
        if policy_on:
            pol = FailurePolicy(cat, store, obs=obs, config=PolicyConfig(
                degrade_after=1, ban_after=1, probe_after=99,
                rereplicate_after=2, rate_evidence=False))
        svc = QueryService(store, backend=SimulatedBackend(
            cat, store, adaptive_packets=False), obs=obs, policy=pol)
        return svc, obs, pol

    svc, obs, pol = service(True)
    plain, _, _ = service(False)
    results, want = [], []
    ban_window, banned_node_packets = None, 0
    for w in range(n_windows):
        if w < 2:
            for _ in range(6):
                obs.health.observe_failure(1)
        expr = f"e_total > {30 + w}"
        results.append(svc.submit(expr))
        want.append(plain.submit(expr))
        before = len(obs.tracer.records())
        svc.step()
        plain.step()
        if pol.states()[1] == POLICY_BANNED and ban_window is None:
            ban_window = w
        if ban_window is not None and w > ban_window:
            banned_node_packets += sum(
                1 for r in obs.tracer.records()[before:]
                if r.get("name") == "packet" and r["attrs"].get("node") == 1)
    identical = all(
        merge_lib.results_identical(svc.result(a).result,
                                    plain.result(b).result)
        for a, b in zip(results, want))
    return {"ban_window": ban_window,
            "banned_node_packets": banned_node_packets,
            "rereplications": pol.rereplications,
            "copies": int(obs.metrics.value("policy.rereplications") or 0),
            "identical": identical}


def main():
    import os
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    n_ev = 512 if smoke else 2048
    baseline = run(replication=2, kill_at=1e9, n_events=n_ev)  # no failure
    r2 = run(replication=2, n_events=n_ev)
    print("scenario,status,selected,expected,makespan_s")
    print(f"no_failure_r2,{baseline['status']},{baseline['selected']},"
          f"{baseline['expected']},{baseline['makespan_s']:.3f}")
    print(f"kill_node1_r2,{r2['status']},{r2['selected']},"
          f"{r2['expected']},{r2['makespan_s']:.3f}")
    assert r2["selected"] == r2["expected"], "r=2 must lose no events"
    # r=1 with a dead node that exclusively owns bricks: job fails
    schema = ev.EventSchema.from_config(reduced())
    store = create_store(schema, n_events=n_ev, n_nodes=4,
                         events_per_brick=128, replication=1, seed=4)
    cat = MetadataCatalog(4)
    cat.mark_dead(1)
    jse = JobSubmissionEngine(cat, store, TimeModel())
    jid = jse.submit(EXPR)
    jse.run_job_simulated(jid)
    print(f"dead_node1_r1,{cat.jobs[jid].status},0,{r2['expected']},inf")
    assert cat.jobs[jid].status == FAILED
    print(f"# failover penalty: {r2['makespan_s'] / baseline['makespan_s']:.2f}x"
          f" makespan, 0 lost events (paper's weakness closed by replication)")

    pol = run_policy(n_events=512 if smoke else 2048)
    print("policy: ban_window,banned_node_packets,rereplicated_copies,"
          "identical")
    print(f"policy,{pol['ban_window']},{pol['banned_node_packets']},"
          f"{pol['copies']},{pol['identical']}")
    assert pol["ban_window"] is not None, "policy must ban the sick node"
    assert pol["banned_node_packets"] == 0, \
        "no packet may route to a banned node"
    assert pol["rereplications"] >= 1 and pol["copies"] >= 1
    assert pol["identical"], "policy must not change results"
    print("# policy: sick node banned pre-death, bricks re-replicated, "
          "0 packets routed post-ban")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
