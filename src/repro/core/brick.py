"""Grid bricks: the paper's core storage organization.

"The data storage is split among all grid nodes having each one a piece of
the whole information" (abstract).  A *brick* is a fixed-size slice of the
event store pinned to one node's local disk; jobs ship to bricks, results
ship back — bricks never move at job time.

Two realizations:
- host level (``BrickStore``): numpy arrays per brick with an explicit
  node placement + replica map — used by the JSE simulation, the failure /
  straggler benchmarks, and the data pipeline;
- SPMD level (``shard_to_mesh``): the same batch laid out over the
  ``("pod","data")`` mesh axes with a NamedSharding, so one lockstep jit is
  the "dispatch to all bricks" of the paper.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import events as ev
from repro.core.replication import place_replicas


@dataclasses.dataclass
class BrickSpec:
    """Catalogue entry for one brick: primary owner, replica owners, and
    the global event-id range the brick covers."""
    brick_id: int
    node: int                       # primary owner
    replicas: Tuple[int, ...]       # replica owners (paper section 7)
    n_events: int
    id_range: Tuple[int, int]       # [start, end) global event ids


@dataclasses.dataclass
class BrickStore:
    """Host-level realization of the brick-sharded event store: per-brick
    numpy EventBatches plus the placement/replication map the JSE
    simulation schedules against."""
    schema: ev.EventSchema
    bricks: Dict[int, dict]                 # brick_id -> EventBatch (numpy)
    specs: Dict[int, BrickSpec]
    n_nodes: int

    @property
    def n_events(self) -> int:
        """Total events across every brick in the store."""
        return sum(s.n_events for s in self.specs.values())

    def bricks_on_node(self, node: int, include_replicas=False) -> List[int]:
        """Brick ids whose primary (optionally: any replica) is ``node``."""
        out = []
        for bid, spec in self.specs.items():
            if spec.node == node or (include_replicas and node in spec.replicas):
                out.append(bid)
        return sorted(out)

    def owners(self, brick_id: int) -> List[int]:
        """Every node holding the brick, primary first (failover order)."""
        spec = self.specs[brick_id]
        return [spec.node, *spec.replicas]


def create_store(schema: ev.EventSchema, *, n_events: int, n_nodes: int,
                 events_per_brick: int, replication: int = 2,
                 seed: int = 0) -> BrickStore:
    """Distribute a synthetic event dataset over n_nodes as bricks."""
    rng = np.random.default_rng(seed)
    bricks, specs = {}, {}
    brick_id, offset = 0, 0
    while offset < n_events:
        n = min(events_per_brick, n_events - offset)
        batch = ev.host_events(rng, schema, n, id_offset=offset)
        node = brick_id % n_nodes
        replicas = place_replicas(brick_id, node, n_nodes, replication)
        specs[brick_id] = BrickSpec(brick_id, node, replicas, n,
                                    (offset, offset + n))
        bricks[brick_id] = batch
        offset += n
        brick_id += 1
    return BrickStore(schema, bricks, specs, n_nodes)


def store_from_config(cfg, *, n_events: int, n_nodes: int,
                      seed: int = 0) -> BrickStore:
    """The store a ``geps_events`` workload config describes — its event
    width, brick size and replication — holding ``n_events`` synthetic
    events generated from ``seed``."""
    return create_store(ev.EventSchema.from_config(cfg), n_events=n_events,
                        n_nodes=n_nodes,
                        events_per_brick=cfg.events_per_brick,
                        replication=cfg.replication_factor, seed=seed)


# --------------------------------------------------------------------------- #
# SPMD realization
# --------------------------------------------------------------------------- #
def batch_sharding(mesh) -> NamedSharding:
    """Sharding that splits the event axis over the mesh's brick axes
    (``pod``/``data``) — the SPMD twin of brick placement."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return NamedSharding(mesh, P(axes))


def shard_to_mesh(batch: dict, mesh) -> dict:
    """Place an EventBatch onto the mesh brick axes (event dim sharded)."""
    sh = batch_sharding(mesh)

    def put(x):
        spec = P(sh.spec[0], *([None] * (x.ndim - 1)))
        return jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))

    return jax.tree.map(put, batch)


def gather_store(store: BrickStore, brick_ids: Optional[List[int]] = None):
    """Concatenate bricks (host memory) in id order — for oracles/tests."""
    ids = sorted(brick_ids if brick_ids is not None else store.bricks)
    parts = [store.bricks[i] for i in ids]
    return {k: np.concatenate([p[k] for p in parts], axis=0)
            for k in parts[0]}
