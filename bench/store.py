"""The cell's brick store, generated on the device from the seed.

Each brick is drawn on the device in one jitted call from a key derived
from ``--seed`` and the brick's index, with the distributions the
configuration file states under ``data``, then copied to host numpy: the
program keeps its bricks on the host and copies each chunk to the device
as it scans.  The result is the program's own ``BrickStore`` type, so the
program gets its input exactly as ``store_from_config`` would give it.

Distributions (``dist``), each filling a column range ``cols = [a, b)``:
``normal`` (loc, scale), ``abs_normal`` (scale) and ``exponential``
(scale).  The object count per event is ``uniform_int`` (low, high, both
inclusive).
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np


def root_key(seed: int):
    """A JAX key from any whole-number seed: NumPy's ``SeedSequence``
    folds the whole integer, however large, into two 32-bit words."""
    import jax
    w0, w1 = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(w0)), int(w1))


def _draw(key, spec: dict, shape):
    import jax
    import jax.numpy as jnp
    kind = spec["dist"]
    if kind == "normal":
        return spec.get("loc", 0.0) + spec["scale"] * jax.random.normal(
            key, shape)
    if kind == "abs_normal":
        return jnp.abs(jax.random.normal(key, shape)) * spec["scale"]
    if kind == "exponential":
        return jax.random.exponential(key, shape) * spec["scale"]
    raise ValueError(f"unknown distribution {kind!r}")


def _count(key, spec: dict, n: int, max_objects: int):
    import jax
    import jax.numpy as jnp
    if spec["dist"] != "uniform_int":
        raise ValueError(f"unknown count distribution {spec['dist']!r}")
    c = jax.random.randint(key, (n,), spec["low"], spec["high"] + 1)
    return jnp.clip(c, 0, max_objects).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _brick_fn(data_json: str, n: int, n_scalars: int, max_objects: int,
              object_vars: int):
    """The jitted generator of one brick of ``n`` events."""
    import json

    import jax
    import jax.numpy as jnp
    data = json.loads(data_json)

    def gen(key):
        keys = jax.random.split(key, 2 + len(data["scalars"])
                                + len(data["objects"]))
        count = _count(keys[0], data["count"], n, max_objects)
        scalars = jnp.concatenate(
            [_draw(keys[1 + i], s, (n, s["cols"][1] - s["cols"][0]))
             for i, s in enumerate(data["scalars"])], axis=1)
        off = 1 + len(data["scalars"])
        objects = jnp.concatenate(
            [_draw(keys[off + i], s, (n, max_objects,
                                      s["cols"][1] - s["cols"][0]))
             for i, s in enumerate(data["objects"])], axis=2)
        assert scalars.shape == (n, n_scalars)
        assert objects.shape == (n, max_objects, object_vars)
        # flat rows copy to the host without the device's lane padding of
        # the last axis (63 columns would move as 128)
        return (scalars.astype(jnp.float32),
                objects.astype(jnp.float32).reshape(n, -1), count)

    return jax.jit(gen)


@dataclasses.dataclass
class Layout:
    """Where each brick starts and how many events it holds."""
    sizes: list

    @classmethod
    def of(cls, cfg: dict) -> "Layout":
        n, per = cfg["n_events"], cfg["events_per_brick"]
        return cls([min(per, n - s) for s in range(0, n, per)])

    @property
    def offsets(self) -> list:
        return [sum(self.sizes[:i]) for i in range(len(self.sizes))]


def build_store(cfg: dict, seed: int):
    """The configuration's ``BrickStore``, generated from ``seed``: each
    brick drawn on the device, the next one dispatched before the last
    one is copied to the host."""
    import json

    import jax
    from repro.core.brick import BrickSpec, BrickStore
    from repro.core.events import EventSchema, make_batch
    from repro.core.replication import place_replicas

    layout = Layout.of(cfg)
    key = root_key(seed)
    data_json = json.dumps(cfg["data"], sort_keys=True)
    nodes = cfg["grid_nodes_on_chip"]

    def dispatch(i):
        fn = _brick_fn(data_json, layout.sizes[i], cfg["n_scalars"],
                       cfg["max_tracks"], cfg["track_vars"])
        return fn(jax.random.fold_in(key, i))

    bricks, specs = {}, {}
    pending = dispatch(0)
    for i, (size, start) in enumerate(zip(layout.sizes, layout.offsets)):
        nxt = dispatch(i + 1) if i + 1 < len(layout.sizes) else None
        scalars, objects, count = jax.device_get(pending)
        objects = np.asarray(objects).reshape(size, cfg["max_tracks"],
                                              cfg["track_vars"])
        bricks[i] = make_batch(np.asarray(scalars), objects,
                               np.asarray(count),
                               np.arange(start, start + size, dtype=np.int32))
        node = i % nodes
        specs[i] = BrickSpec(i, node, place_replicas(
            i, node, nodes, cfg["replication_factor"]), size,
            (start, start + size))
        pending = nxt
    schema = EventSchema(cfg["n_scalars"], cfg["max_tracks"],
                         cfg["track_vars"])
    return BrickStore(schema, bricks, specs, nodes)


def sub_store(store, brick_ids):
    """A store of some of ``store``'s bricks, sharing their arrays: the
    warm-up scans it to compile every chunk shape the full store has."""
    from repro.core.brick import BrickStore
    return BrickStore(store.schema,
                      {b: store.bricks[b] for b in brick_ids},
                      {b: store.specs[b] for b in brick_ids}, store.n_nodes)


def shape_bricks(store) -> list:
    """One brick of each distinct size, the first of each."""
    seen, out = set(), []
    for b in sorted(store.bricks):
        n = store.specs[b].n_events
        if n not in seen:
            seen.add(n)
            out.append(b)
    return out
