"""Execution backends: ONE contract for "execute a dispatch window".

The paper's JSE is a single contract — distribute a query over
brick-resident data, merge partials at the submit server — but the repo
grew two divergent realizations of it: the virtual-time simulation
(fragment plans, streaming ``on_partial``, failure scripts, per-packet
telemetry) and the SPMD lockstep step (none of these, merge only at step
end).  This module collapses the divergence behind one interface so every
service/fabric feature (streaming, cache write-through, cost-model
calibration, window planning) works identically on both paths:

- :class:`ExecutionBackend` — the protocol:
  ``run_batch(job_ids, *, plan, on_partial, failure_script, packet_ramp)
  -> (results, JobStats)``.  Exactly the surface
  ``JobSubmissionEngine.run_job_batch_simulated`` already exposes, now
  named and substitutable.
- :class:`SimulatedBackend` — thin wrapper over the event-driven
  virtual-time grid simulation (``core/jse.py``).  Time is virtual, the
  per-packet compute is real.
- :class:`SpmdBackend` — the mesh-shard realization as a **chunked
  streaming scan**: each brick (= shard that never moves) is swept in
  chunks, every chunk evaluated through the same
  :func:`~repro.core.jse.eval_plan_slice` primitive as the simulation,
  and a :class:`~repro.core.jse.PacketPartial` emitted per chunk in
  deterministic merge order (brick id ascending, offset ascending) — so
  prefix snapshots fed to a :class:`~repro.core.merge.MergeAccumulator`
  are bit-identical to ``tree_merge`` of the same prefix, and a window
  executed with the same chunk boundaries on either backend produces
  bit-identical partial streams and final results.
- :class:`PlanSplit` — the mixed-window kernel/jnp split: plan targets
  inside the fused ``event_filter`` kernel's conjunctive family run as
  one kernel sub-batch per chunk, the rest through the jnp fragment
  walk, reassembled in slot order so prefixes stay bit-identical.
- :func:`make_backend` — string-keyed factory (``"sim"`` / ``"spmd"``)
  the service layer and ``launch/serve.py --backend`` use.

See ``docs/backends.md`` for the full contract (merge-order determinism,
clock semantics, failure semantics, Pallas fragment fusion, and the
SPMD scan's knobs: chunk size and ramp, mesh sharding, interpret
auto-detect, the pipelined finalize, the resident store).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
import weakref
from typing import Callable, Dict, List, Optional, Protocol, Tuple, \
    runtime_checkable

import jax
import numpy as np

from repro.core import merge as merge_lib
from repro.core import query as query_lib
from repro.core.brick import BrickStore
from repro.core.catalog import DONE, MetadataCatalog
from repro.core.jse import (JobStats, JobSubmissionEngine, PacketPartial,
                            PacketTelemetry, TimeModel, eval_plan_slice,
                            prepare_window)
from repro.core.packets import ramp_cap


@runtime_checkable
class ExecutionBackend(Protocol):
    """The one contract the service layer executes dispatch windows
    against.  Implementations own a catalogue + brick store pair and
    TWO mutable attributes the service relies on: ``cost_weights`` (the
    service installs fitted :class:`~repro.service.planner.CostWeights`
    there so the scheduler can bound windows by calibrated cost) and
    ``supports_failure_injection`` (checked BEFORE a window is dequeued;
    a backend that omits it is treated as not supporting failure
    scripts — the safe direction, since an error raised mid-dispatch
    would strand the window's tickets and streams)."""

    catalog: MetadataCatalog
    store: BrickStore
    cost_weights: Optional[object]
    supports_failure_injection: bool

    def run_batch(self, job_ids: List[int], *,
                  plan: Optional[query_lib.FragmentPlan] = None,
                  on_partial: Optional[
                      Callable[[PacketPartial], None]] = None,
                  failure_script: Optional[Dict[float, int]] = None,
                  packet_ramp: Optional[int] = None
                  ) -> Tuple[List[merge_lib.QueryResult], JobStats]:
        """Execute one shared-scan window of catalogued jobs.

        Contract (both backends): jobs must share bricks/calib_iters;
        ``plan`` (a fragment plan whose roots align with ``job_ids``) is
        built when absent; ``on_partial`` is invoked once per evaluated
        packet/chunk, in the exact merge order, with partials whose
        prefix merges are bit-identical to ``tree_merge`` of that
        prefix; ``packet_ramp`` caps early packet sizes for streaming;
        job statuses move RUNNING -> DONE (or FAILED) in the catalogue;
        returns ``(merged, stats)`` with materialized-fragment results
        in ``stats.fragment_results`` and per-packet compute telemetry
        in ``stats.packet_telemetry``."""
        ...


class SimulatedBackend:
    """The event-driven virtual-time grid simulation behind the
    :class:`ExecutionBackend` contract.

    A thin wrapper over :class:`~repro.core.jse.JobSubmissionEngine`
    (exposed as :attr:`engine` for callers tuning simulation knobs such
    as ``adaptive_packets`` or node speeds): scheduling, straggler
    mitigation, failure injection and virtual makespans are all the
    engine's — this class only pins the contract surface."""

    def __init__(self, catalog: MetadataCatalog, store: BrickStore, *,
                 time_model: Optional[TimeModel] = None,
                 node_speed: Optional[Dict[int, float]] = None,
                 adaptive_packets: bool = True,
                 packet_ramp: Optional[int] = None,
                 ramp_factor: float = 2.0):
        self.engine = JobSubmissionEngine(
            catalog, store, time_model=time_model, node_speed=node_speed,
            adaptive_packets=adaptive_packets, packet_ramp=packet_ramp,
            ramp_factor=ramp_factor)
        self.catalog = catalog
        self.store = store
        # fitted cost weights the service installs after telemetry refits
        # (consumed by QueryScheduler window-cost bounding)
        self.cost_weights = None
        #: the virtual grid can kill nodes mid-scan; the service checks
        #: this BEFORE dequeuing a window so an unsupported failure
        #: script fails fast with no state mutated
        self.supports_failure_injection = True
        #: the virtual grid routes packets per node, so the failure
        #: policy's avoid/probe/speculate decision applies here; the
        #: service checks this before passing routing kwargs
        self.supports_routing_policy = True

    @property
    def obs(self):
        """Observability plane handle — stored on the wrapped engine (the
        simulation loop is where packets are scanned), surfaced here so
        the service can install/inspect it backend-agnostically."""
        return self.engine.obs

    @obs.setter
    def obs(self, value):
        """Install the plane on the wrapped engine."""
        self.engine.obs = value

    def submit(self, expr: str, calib_iters: int = 0) -> int:
        """Register a job over every brick in the store (engine passthrough)."""
        return self.engine.submit(expr, calib_iters)

    def run_batch(self, job_ids: List[int], *,
                  plan: Optional[query_lib.FragmentPlan] = None,
                  on_partial: Optional[
                      Callable[[PacketPartial], None]] = None,
                  failure_script: Optional[Dict[float, int]] = None,
                  packet_ramp: Optional[int] = None,
                  route_avoid: Optional[set] = None,
                  probe_quota: Optional[Dict[int, int]] = None,
                  speculate: bool = False,
                  spec_lead_factor: float = 1.5,
                  rereplicated: Optional[List[Tuple[int, int, int]]] = None
                  ) -> Tuple[List[merge_lib.QueryResult], JobStats]:
        """Execute the window on the simulated grid (see
        :meth:`ExecutionBackend.run_batch` for the contract; the routing
        kwargs carry a :class:`~repro.service.policy.PolicyDecision` —
        see ``run_job_batch_simulated`` for their semantics, including
        the ``rereplicated`` brick-copy transfer charge)."""
        return self.engine.run_job_batch_simulated(
            job_ids, plan=plan, on_partial=on_partial,
            failure_script=failure_script, packet_ramp=packet_ramp,
            route_avoid=route_avoid, probe_quota=probe_quota,
            speculate=speculate, spec_lead_factor=spec_lead_factor,
            rereplicated=rereplicated)


@dataclasses.dataclass(frozen=True)
class PlanSplit:
    """The mixed-window kernel/jnp split of one fragment plan's targets.

    ``kernel_cols`` are the target slots (roots-then-materialized order,
    exactly :meth:`~repro.core.query.FragmentPlan.targets` order) whose
    expressions matched the fused ``event_filter`` kernel's conjunctive
    family (``match_epilogue``); they run as ONE kernel sub-batch per
    chunk with ``thresholds`` (the ``(4, K_kernel)`` layout of
    ``batch_kernel_params``) and ``var_idx``.  ``jnp_cols`` hold the
    out-of-family targets (``jnp_targets`` the matching AST nodes),
    evaluated through the same shared-memo jnp walk the plan itself
    uses.  Per chunk the two sub-batches are reassembled in the original
    slot order, so partial streams and prefixes stay bit-identical to
    the pure-jnp path regardless of how the split falls."""

    kernel_cols: Tuple[int, ...]
    jnp_cols: Tuple[int, ...]
    thresholds: Optional[object]        # jnp (4, len(kernel_cols)) or None
    var_idx: Tuple[int, ...]
    jnp_targets: Tuple[object, ...]     # AST nodes, aligned with jnp_cols

    @property
    def any_kernel(self) -> bool:
        """True when at least one target runs through the kernel."""
        return bool(self.kernel_cols)

    @property
    def full_kernel(self) -> bool:
        """True when EVERY target runs through the kernel."""
        return bool(self.kernel_cols) and not self.jnp_cols


@dataclasses.dataclass
class _Inflight:
    """One dispatched-but-unfinalized chunk: lazy device values plus the
    slot bookkeeping needed to emit its partial in order."""
    seq: int
    brick_id: int
    start: int
    size: int
    owner: int
    span: object = None
    # "plan" chunks are fully evaluated at dispatch (the eval_plan_slice
    # primitive materializes internally); "split" chunks hold lazy
    # kernel/jnp device arrays finalized later.
    res: Optional[List[merge_lib.QueryResult]] = None
    mask_dev: object = None             # (size, K_kernel) device array
    var_dev: object = None              # (size,) device array
    jnp_masks: Optional[list] = None    # lazy (size,) arrays, jnp_cols order
    ids: Optional[np.ndarray] = None


#: The brick arrays a resident image uploads; ``event_id`` stays on the
#: host.
KERNEL_INPUTS = ("scalars", "tracks", "n_tracks")


def _input_bytes(store: BrickStore) -> int:
    """Host bytes of every brick's kernel inputs: what a resident image
    uploads once, and what a streamed mixed window sends."""
    return sum(int(b[k].nbytes) for b in store.bricks.values()
               for k in KERNEL_INPUTS)


def _plane_bytes(store: BrickStore) -> int:
    """Device bytes of the store's ``pt`` planes: float32 ``(n, T)`` per
    brick (:func:`_pt_plane`)."""
    return sum(4 * b["tracks"].shape[0] * b["tracks"].shape[1]
               for b in store.bricks.values())


def _track_rows(schema) -> Tuple[int, int]:
    """``(rows per event, lanes)`` of the resident ``tracks``.  An event's
    ``T x V`` floats are kept as whole rows: in a ``(n, T, V)`` array the
    device pads V to 128 lanes (63 -> 128 at the paper's width, twice the
    bytes).  Where they fill 128-lane rows exactly, rows of 128 are
    already in the device's tile order, so the upload copies them as
    they lie; otherwise one row per event."""
    width = schema.max_tracks * schema.track_vars
    lanes = 128 if width % 128 == 0 else width
    return width // lanes, lanes


def _chunk_reserve(schema, size: int) -> int:
    """Device bytes the chunks in flight take beside a resident image:
    for each of three full-track chunks (a mixed window's two pipelined
    chunks and its calibrated jnp copy), its sliced rows
    plus the ``(size, T, V)`` copy with V padded to whole 128-lane tiles.
    A kernel-only window's plane chunks are far smaller."""
    padded = -(-schema.track_vars // 128) * 128
    return 3 * size * 4 * schema.max_tracks * (schema.track_vars + padded)


def _device_room(device) -> Optional[int]:
    """Bytes ``device`` has free by its ``memory_stats()``, or None where
    it reports no limit (the CPU)."""
    stats = device.memory_stats() or {}
    if "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


@functools.partial(jax.jit, static_argnames=("size", "event_shape"))
def _take_chunk(tracks, start, *, size, event_shape):
    """The tracks of events ``[start, start + size)`` of one resident
    brick's rows (:func:`_track_rows`), back in the ``(size, T, V)`` shape
    a jnp sub-batch reads.  ``start`` is traced, so one program serves
    every chunk of a (brick, chunk) shape."""
    t, v = event_shape
    rows = t * v // tracks.shape[1]
    return jax.lax.dynamic_slice_in_dim(
        tracks, start * rows, size * rows).reshape(size, t, v)


@functools.partial(jax.jit, static_argnames=("size",))
def _take_plane(scalars, pt, n_tracks, start, *, size):
    """Events ``[start, start + size)`` of one resident brick's
    ``scalars``, ``pt`` plane and ``n_tracks``: what the kernel reads.
    Sublane slices of lane-dense arrays, no relayout; ``start`` is
    traced, as in :func:`_take_chunk`."""
    return tuple(jax.lax.dynamic_slice_in_dim(a, start, size)
                 for a in (scalars, pt, n_tracks))


@functools.partial(jax.jit, static_argnames=("event_shape",))
def _pt_plane(tracks, *, event_shape):
    """The ``(n, T)`` ``pt`` plane of one brick's resident track rows
    (:func:`_track_rows`): every track's column 0, with the track axis on
    the lanes.  Taken in pieces of up to 8 events (one sublane tile), so
    the device holds the relayout of a piece at a time, not of the
    brick."""
    t, v = event_shape
    n = tracks.size // (t * v)
    piece = math.gcd(n, 8)
    pieces = tracks.reshape(n // piece, -1, tracks.shape[1])
    plane = jax.lax.map(lambda p: p.reshape(piece, t, v)[:, :, 0], pieces)
    return plane.reshape(n, t)


#: Bytes of brick copies in flight while an image uploads.  On a TPU v5e
#: the paper-width store (48 bricks, 12.69 GB) landed at 8.3 GB/s with
#: every copy dispatched at once, and slowed the scan overlapping it; in
#: groups of about 1 GiB, each landed before the next was sent, at
#: 11.6-11.8 GB/s.
UPLOAD_GROUP_BYTES = 1 << 30


class _ResidentImage:
    """One store's kernel inputs held in one device's memory, per brick:
    ``scalars``, ``n_tracks``, ``tracks`` as rows (:func:`_track_rows`)
    and the ``pt`` plane the kernel reads (:func:`_pt_plane`, derived on
    the device from the rows once they are there).  The bricks are
    copied in scan order, in groups of at most
    :data:`UPLOAD_GROUP_BYTES` (or one brick), each group landed before
    the next is sent; the image is whole once constructed."""

    def __init__(self, store: BrickStore, device):
        self.store = store
        self.event_shape = (store.schema.max_tracks, store.schema.track_vars)
        rows, lanes = _track_rows(store.schema)
        self.bricks = {}
        self.planes = {}
        group, in_flight = [], 0
        for bid in sorted(store.bricks):
            b = store.bricks[bid]
            n = b["scalars"].shape[0]
            host = (b["scalars"], b["tracks"].reshape(n * rows, lanes),
                    b["n_tracks"])
            nbytes = sum(a.nbytes for a in host)
            if group and in_flight + nbytes > UPLOAD_GROUP_BYTES:
                jax.block_until_ready(group)
                group, in_flight = [], 0
            self.bricks[bid] = jax.device_put(host, device)
            self.planes[bid] = _pt_plane(self.bricks[bid][1],
                                         event_shape=self.event_shape)
            group.append((self.bricks[bid], self.planes[bid]))
            in_flight += nbytes
        jax.block_until_ready(group)
        self.host_bytes = _input_bytes(store)
        self.device_bytes = sum(
            a.on_device_size_in_bytes() for bid, arrays in self.bricks.items()
            for a in arrays + (self.planes[bid],))

    def tracks(self, brick_id: int, start: int, size: int):
        """The chunk's ``(size, T, V)`` tracks, as a device array."""
        return _take_chunk(self.bricks[brick_id][1], np.int32(start),
                           size=size, event_shape=self.event_shape)

    def plane_chunk(self, brick_id: int, start: int, size: int):
        """The chunk's ``(scalars, pt, n_tracks)`` device arrays."""
        scalars, _, n_tracks = self.bricks[brick_id]
        return _take_plane(scalars, self.planes[brick_id], n_tracks,
                           np.int32(start), size=size)


#: Resident images by ``(id(store), device)``.  Backends hold their image
#: and this map only refers to it, so backends over one store on one
#: device share one copy, and it is freed with the last of them.
_IMAGES: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()


class SpmdBackend:
    """The SPMD realization of the contract: a chunked streaming scan
    over the brick shards.

    Bricks play the role of mesh shards (data that never moves); the
    scan visits them in brick-id order and sweeps each in chunks of
    ``chunk_events``, the first ones capped by the stream ramp
    (``packet_ramp``, ``ramp_factor``).  Every chunk runs the SAME
    fragment-factored evaluation as the simulation
    (:func:`~repro.core.jse.eval_plan_slice`), so a chunk's partials are
    bit-identical to the simulated backend's for the same slice.
    Per-chunk :class:`~repro.core.jse.PacketPartial`\\ s stream out
    through ``on_partial`` in merge order (brick id, then offset), so
    prefix snapshots (:class:`~repro.core.merge.MergeAccumulator`) are
    bit-identical to ``tree_merge`` of the same prefix.

    - **Clock**: ``t_virtual`` on emitted partials and
      ``JobStats.makespan_s`` are seconds on the injectable ``clock``
      (wall by default) since the window started.
    - **Failures**: shards are resident compute state, not remote disks;
      a non-empty ``failure_script`` raises ``ValueError``.
    - **Pallas fusion** (``use_pallas=True``): plan targets inside the
      fused ``event_filter`` kernel's conjunctive family run as one
      kernel sub-batch per chunk, at the kernel's default block shape;
      the rest run through the jnp fragment walk on the same slice, and
      the two are reassembled in slot order (:class:`PlanSplit`).
      ``interpret=None`` picks by platform (compiled on TPU/GPU,
      interpreter on CPU).  Per-chunk ``PacketTelemetry`` is recorded
      either way, so ``planner.fit_cost_weights`` calibrates from SPMD
      runs too.
    - **Pipeline**: a window with kernel targets finalizes chunk ``i``
      (device->host reads, slot reassembly, partial emission) after
      chunk ``i+1`` is dispatched, and each chunk's reads start at its
      launch, so they land while the next chunk is dispatched.  A
      pure-jnp chunk is already evaluated at dispatch and is finalized
      there.  Merge order is dispatch order either way.
    - **Mesh** (``mesh_devices=D > 1``): the kernel sub-batches of each
      group of ``D`` consecutive chunks of a brick run as one
      ``shard_map`` call, one chunk per device, streamed from the host
      (:meth:`_dispatch_group`); partials are still emitted per chunk.
      ``D`` wider than jax's devices raises ``ValueError``.
    - **Residency**: single-device kernel chunks read one image of the
      store in device memory, so the store crosses from the host once,
      not every window.  Per brick it holds ``scalars``, ``n_tracks``,
      ``tracks`` as lane-dense rows (:func:`_track_rows`) and the
      ``(n, T)`` ``pt`` plane the kernel reads, derived on the device;
      ``event_id`` stays on the host.  A kernel chunk is one jitted
      on-device slice of ``scalars``, the plane and ``n_tracks``; a
      window with jnp targets also takes the chunk's full tracks.  The
      image is built at the first window with kernel targets, which
      waits for it (copies in scan order, in landed groups of
      :data:`UPLOAD_GROUP_BYTES`), and only where it fits in what the
      device's ``memory_stats()`` reports free (no limit, as on the CPU,
      counts as room).  Backends over one store object on one device
      share it; it is freed with the last of them.  A store that does
      not fit streams each chunk from the host bricks.
    """

    def __init__(self, catalog: MetadataCatalog, store: BrickStore, *,
                 chunk_events: int = 64, packet_ramp: Optional[int] = None,
                 ramp_factor: float = 2.0, use_pallas: bool = False,
                 interpret: Optional[bool] = None,
                 mesh_devices: int = 1,
                 clock: Callable[[], float] = time.perf_counter):
        if chunk_events <= 0:
            raise ValueError("chunk_events must be positive")
        if packet_ramp is not None and packet_ramp <= 0:
            raise ValueError("packet_ramp must be positive")
        if ramp_factor <= 1.0:
            raise ValueError("ramp_factor must be > 1")
        if mesh_devices < 1:
            raise ValueError("mesh_devices must be >= 1")
        self.catalog = catalog
        self.store = store
        self.chunk_events = chunk_events
        self.packet_ramp = packet_ramp
        self.ramp_factor = ramp_factor
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.mesh_devices = mesh_devices
        self.clock = clock
        self.cost_weights = None  # installed by the service after refits
        #: shards are resident compute state, not killable virtual nodes
        self.supports_failure_injection = False
        #: no per-node routing either — chunks visit shards in place, so
        #: policy decisions (avoid/probe/speculate) don't apply here
        self.supports_routing_policy = False
        # observability plane (repro.obs.Observability); None = disabled
        self.obs = None
        # resolved lazily on first run (jax pins its devices at first use)
        self._mesh_real: Optional[bool] = None
        self._mesh = None
        # the resident image, or None while streaming; decided at the
        # first window with kernel targets (see "Residency")
        self._image: Optional[_ResidentImage] = None
        self._resident_decided = False

    # ------------------------------------------------------------------ #
    def _chunk_size(self, seq: int, remaining: int,
                    ramp: Optional[int]) -> int:
        """Size of chunk ``seq``: ``chunk_events``, capped early by the
        shared geometric stream ramp (``core/packets.py``), clipped to
        the shard remainder."""
        size = self.chunk_events
        if ramp is not None:
            cap = ramp_cap(seq, ramp, self.ramp_factor)
            if cap < size:
                size = max(1, int(cap))
        return min(size, remaining)

    def _split_plan(self, plan: query_lib.FragmentPlan) -> PlanSplit:
        """Partition the plan's targets into the kernel sub-batch
        (targets inside the fused kernel's conjunctive family) and the
        jnp sub-batch (everything else) — see :class:`PlanSplit`.  With
        ``use_pallas=False`` every target lands in the jnp sub-batch."""
        targets = plan.targets()
        if not self.use_pallas:
            return PlanSplit(kernel_cols=(), jnp_cols=tuple(
                range(len(targets))), thresholds=None, var_idx=(),
                jnp_targets=tuple(targets))
        from repro.kernels.event_filter import ops as ef_ops
        params = [ef_ops.match_epilogue(t, self.store.schema)
                  for t in targets]
        kcols = tuple(i for i, p in enumerate(params) if p is not None)
        jcols = tuple(i for i, p in enumerate(params) if p is None)
        thresholds, var_idx = (None, ())
        if kcols:
            thresholds, var_idx = ef_ops.batch_kernel_params(
                [params[i] for i in kcols])
        return PlanSplit(kernel_cols=kcols, jnp_cols=jcols,
                         thresholds=thresholds, var_idx=var_idx,
                         jnp_targets=tuple(targets[i] for i in jcols))

    # ------------------------------------------------------------------ #
    def _mesh_is_real(self) -> bool:
        """True when the scan runs on a ``mesh_devices``-wide mesh (the
        ``shard_map`` path).  A mesh wider than jax's devices raises
        ``ValueError``.  Resolved once — jax pins its device count at
        first init."""
        if self._mesh_real is None:
            n_dev = len(jax.devices())
            if self.mesh_devices > n_dev:
                raise ValueError(
                    f"mesh_devices={self.mesh_devices} but the "
                    f"{jax.default_backend()} backend has {n_dev} "
                    f"device(s)")
            self._mesh_real = self.mesh_devices > 1
        return self._mesh_real

    def _scan_mesh(self):
        """The 1-D ``"scan"`` mesh over the first ``mesh_devices``
        devices (real-mesh path only; built once)."""
        if self._mesh is None:
            from jax.sharding import Mesh
            self._mesh = Mesh(np.asarray(jax.devices()[:self.mesh_devices]),
                              ("scan",))
        return self._mesh

    def _adopt_image(self) -> None:
        """Find or build the store's resident image on the scan device,
        once per backend (see "Residency" in the class docstring).  The
        backend that builds it records the ``upload`` span (the copies and
        the planes' derivation) and counts the image in
        ``spmd.h2d_bytes``."""
        if self._resident_decided:
            return
        self._resident_decided = True
        device = jax.devices()[0]
        key = (id(self.store), device)
        image = _IMAGES.get(key)
        if image is None or image.store is not self.store:
            largest = max(s.n_events for s in self.store.specs.values())
            size = min(self.chunk_events, largest)
            room = _device_room(device)
            need = (_input_bytes(self.store) + _plane_bytes(self.store)
                    + _chunk_reserve(self.store.schema, size))
            if room is not None and need > room:
                return
            obs = self.obs
            span = None if obs is None else obs.tracer.begin(
                "upload", t_virtual=obs.tracer.virtual_base,
                bricks=len(self.store.bricks))
            image = _IMAGES[key] = _ResidentImage(self.store, device)
            if span is not None:
                span.attrs["bytes"] = image.host_bytes
                obs.metrics.counter("spmd.h2d_bytes").inc(image.host_bytes)
                obs.tracer.end(span, t_virtual=obs.tracer.virtual_base)
        self._image = image
        if self.obs is not None:
            self.obs.metrics.gauge("spmd.resident_bytes").set(
                image.device_bytes)

    # ------------------------------------------------------------------ #
    def _phase(self, name: str, packet, **attrs):
        """Open host phase ``name`` of the chunk whose ``packet`` span is
        given.  The parent is passed, not taken from the tracer's stack:
        the pipeline finalizes chunk i after chunk i+1 is launched.
        Phases are timed on the wall clock; on the virtual axis they sit
        at their packet's start."""
        return self.obs.tracer.begin(name, t_virtual=packet.t0_virtual,
                                     parent=packet, seq=packet.attrs["seq"],
                                     **attrs)

    def _dispatch_chunk(self, plan: query_lib.FragmentPlan,
                        split: PlanSplit, seq: int, brick_id: int,
                        start: int, size: int, owner: int,
                        calib_iters: int, span=None) -> _Inflight:
        """Dispatch one chunk: kernel sub-batch + jnp sub-batch launched
        asynchronously (device values stay lazy), or — for windows with
        no kernel targets — the shared ``eval_plan_slice`` primitive
        evaluated in place.  ``span`` is the chunk's ``packet`` span
        (None with tracing off); kernel chunks record their ``stage``
        and ``launch`` phases under it."""
        infl = _Inflight(seq=seq, brick_id=brick_id, start=start,
                         size=size, owner=owner, span=span)
        if not split.any_kernel:
            infl.res = eval_plan_slice(self.store, plan, brick_id, start,
                                       size, calib_iters)
            return infl
        import jax.numpy as jnp
        from repro.kernels.event_filter import ops as ef_ops
        stage = None if span is None else self._phase("stage", span)
        batch = self.store.bricks[brick_id]
        end = start + size
        infl.ids = np.asarray(batch["event_id"][start:end])
        # the kernel reads the chunk's pt plane; only a jnp sub-batch
        # needs its full tracks
        tracks = None
        if self._image is not None:
            scalars, pt, n_tracks = self._image.plane_chunk(brick_id, start,
                                                            size)
            if split.jnp_cols:
                tracks = self._image.tracks(brick_id, start, size)
            nbytes = 0
        elif split.jnp_cols:
            sl = [batch[k][start:end] for k in KERNEL_INPUTS]
            scalars, tracks, n_tracks = (jnp.asarray(a) for a in sl)
            pt = tracks[..., 0]
            nbytes = int(sum(a.nbytes for a in sl))
        else:
            sl = [batch["scalars"][start:end],
                  np.ascontiguousarray(batch["tracks"][start:end, :, 0]),
                  batch["n_tracks"][start:end]]
            scalars, pt, n_tracks = (jnp.asarray(a) for a in sl)
            nbytes = int(sum(a.nbytes for a in sl))
        if stage is not None:
            stage.attrs["bytes"] = nbytes
            metrics = self.obs.metrics
            metrics.counter("spmd.h2d_bytes").inc(nbytes)
            if self._image is not None:
                metrics.counter("spmd.plane_chunks").inc()
                if tracks is not None:
                    metrics.counter("spmd.resident_chunks").inc()
            self.obs.tracer.end(stage)
        launch = None if span is None else self._phase("launch", span)
        infl.mask_dev, infl.var_dev = ef_ops.event_filter_batch(
            scalars, pt, n_tracks, split.thresholds,
            var_idx=split.var_idx, calib_iters=calib_iters,
            interpret=self.interpret)
        if split.jnp_cols:
            # out-of-family targets: the same shared-memo jnp walk the
            # plan runs, restricted to the jnp sub-batch (values are
            # memo-independent, so restricting the memo cannot change
            # bits — only sharing), on the chunk's device arrays
            slj = {"scalars": scalars, "tracks": tracks,
                   "n_tracks": n_tracks}
            if calib_iters:
                slj = dict(slj, tracks=query_lib.calibrate(slj,
                                                           calib_iters))
            memo: Optional[dict] = {} if plan.shared else None
            infl.jnp_masks = [
                query_lib.eval_node(t, slj, self.store.schema, False, memo)
                for t in split.jnp_targets]
        # start the device->host reads now: they land while the next
        # chunk is dispatched, so `wait` finds them on the host instead
        # of paying each read's round trip
        for out in (infl.mask_dev, infl.var_dev, *(infl.jnp_masks or ())):
            if isinstance(out, jax.Array):
                out.copy_to_host_async()
        if launch is not None:
            self.obs.tracer.end(launch)
        return infl

    def _dispatch_group(self, plan: query_lib.FragmentPlan,
                        split: PlanSplit,
                        slots: List[Tuple[int, int, int]], brick_id: int,
                        owner: int, calib_iters: int,
                        spans: Optional[list] = None) -> List[_Inflight]:
        """Dispatch one mesh group — up to ``mesh_devices`` chunk slots
        of one brick — as a single ``shard_map`` kernel call over the
        stacked, zero-padded ``(D, n_max, ...)`` slabs of ``scalars``,
        the ``pt`` plane and ``n_tracks`` (each device owns one
        sub-chunk).  Partials are still sliced back out per slot, so
        packetization — and therefore prefix bit-identity — is unchanged
        by the group width.  jnp sub-batch targets (mixed windows) run
        per slot on the host path as usual.  ``spans`` are the slots'
        ``packet`` spans (None with tracing off); the group's ``stage``
        and ``launch`` phases go under the first."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.kernels import resolve_interpret
        from repro.kernels.event_filter import ops as ef_ops
        first = None if spans is None else spans[0]
        stage = None if first is None else self._phase(
            "stage", first, chunks=len(slots))
        batch = self.store.bricks[brick_id]
        n_max = max(size for _, _, size in slots)
        d = self.mesh_devices
        mesh = self._scan_mesh()
        per_device = NamedSharding(mesh, P("scan"))

        def slab(src, start, size):
            a = np.asarray(src[start:start + size])
            if size < n_max:
                pad = [(0, n_max - size)] + [(0, 0)] * (a.ndim - 1)
                a = np.pad(a, pad)
            return a

        def stacked(src):
            rows = [slab(src, start, size) for _, start, size in slots]
            while len(rows) < d:    # tail group: replicate a dummy slab
                rows.append(np.zeros_like(rows[0]))
            return np.stack(rows)

        host = [stacked(src) for src in (batch["scalars"],
                                         batch["tracks"][:, :, 0],
                                         batch["n_tracks"])]
        # each slab goes straight to the device that owns it
        scalars, pt, n_tracks = (jax.device_put(a, per_device)
                                 for a in host)
        if stage is not None:
            nbytes = int(sum(a.nbytes for a in host))
            stage.attrs["bytes"] = nbytes
            self.obs.metrics.counter("spmd.h2d_bytes").inc(nbytes)
            self.obs.tracer.end(stage)
        launch = None if first is None else self._phase(
            "launch", first, chunks=len(slots))
        fn = ef_ops.sharded_event_filter_batch(
            mesh, var_idx=split.var_idx, calib_iters=calib_iters,
            interpret=resolve_interpret(self.interpret))
        gmask, gvar = fn(scalars, pt, n_tracks, split.thresholds)
        out: List[_Inflight] = []
        for i, (seq, start, size) in enumerate(slots):
            infl = _Inflight(seq=seq, brick_id=brick_id, start=start,
                             size=size, owner=owner,
                             span=None if spans is None else spans[i])
            infl.ids = np.asarray(batch["event_id"][start:start + size])
            infl.mask_dev = gmask[i, :size]
            infl.var_dev = gvar[i, :size]
            if split.jnp_cols:
                sl = {k: v[start:start + size] for k, v in batch.items()}
                slj = {k: jnp.asarray(v) for k, v in sl.items()}
                if calib_iters:
                    slj = dict(slj, tracks=query_lib.calibrate(
                        slj, calib_iters))
                memo: Optional[dict] = {} if plan.shared else None
                infl.jnp_masks = [
                    query_lib.eval_node(t, slj, self.store.schema, False,
                                        memo)
                    for t in split.jnp_targets]
            out.append(infl)
        if launch is not None:
            self.obs.tracer.end(launch)
        return out

    def _finalize_chunk(self, infl: _Inflight,
                        split: PlanSplit) -> List[merge_lib.QueryResult]:
        """Force one dispatched chunk and reassemble its partials in the
        plan's slot order (kernel and jnp sub-batches interleaved back to
        their original target slots)."""
        if infl.res is not None:
            return infl.res
        # the device->host reads, where the host blocks on the device
        wait = None if infl.span is None else self._phase("wait", infl.span)
        mask = np.asarray(infl.mask_dev)   # (size, K_kernel)
        var = np.asarray(infl.var_dev)
        jnp_masks = ([np.asarray(m) for m in infl.jnp_masks]
                     if infl.jnp_masks is not None else ())
        if wait is not None:
            self.obs.tracer.end(wait)
        n_targets = len(split.kernel_cols) + len(split.jnp_cols)
        out: List[Optional[merge_lib.QueryResult]] = [None] * n_targets
        for j, col in enumerate(split.kernel_cols):
            out[col] = merge_lib.from_mask(mask[:, j], var, infl.ids)
        for j, col in enumerate(split.jnp_cols):
            out[col] = merge_lib.from_mask(jnp_masks[j], var, infl.ids)
        infl.res = out
        return out

    # ------------------------------------------------------------------ #
    def run_batch(self, job_ids: List[int], *,
                  plan: Optional[query_lib.FragmentPlan] = None,
                  on_partial: Optional[
                      Callable[[PacketPartial], None]] = None,
                  failure_script: Optional[Dict[float, int]] = None,
                  packet_ramp: Optional[int] = None
                  ) -> Tuple[List[merge_lib.QueryResult], JobStats]:
        """Execute the window as a chunked streaming scan over the brick
        shards (see the class docstring and
        :meth:`ExecutionBackend.run_batch` for the contract)."""
        if failure_script:
            raise ValueError(
                "failure_script is a simulated-grid concept; the SPMD "
                "backend has no virtual nodes to kill (use "
                "SimulatedBackend for failure experiments)")
        rec, plan = prepare_window(self.catalog, job_ids, plan)

        obs = self.obs
        clock = self.clock
        stats = JobStats(n_queries=len(job_ids))
        plan_aggs = query_lib.unique_aggregates(plan.targets())
        split = self._split_plan(plan)
        ramp = packet_ramp if packet_ramp is not None else self.packet_ramp
        # a step dispatches one chunk, or on a real mesh one group of
        # `mesh_devices` chunks as a single shard_map call; a pure-jnp
        # window scans chunk by chunk either way
        width = self.mesh_devices if (self._mesh_is_real()
                                      and split.any_kernel) else 1
        if split.any_kernel and width == 1:
            self._adopt_image()

        results: List[List[merge_lib.QueryResult]] = []
        t_start = clock()
        t_prev = t_start    # previous finalize completion (chunk walls)

        def stamp() -> float:
            return clock() - t_start

        def emit(infl: _Inflight, wall: float) -> None:
            """Record one finalized chunk: telemetry, obs, stats, and the
            in-order partial emission (inside the chunk's packet span, so
            the stream's ``merge`` span nests under it)."""
            res = infl.res
            stats.packet_telemetry.append(PacketTelemetry(
                size=infl.size, calib_iters=rec.calib_iters,
                n_aggregates=plan_aggs, wall_s=wall,
                n_targets=len(plan.targets()), node=infl.owner))
            if obs is not None:
                obs.metrics.counter("packet.count").inc()
                obs.metrics.histogram("packet.latency_s").observe(wall)
                obs.metrics.histogram("packet.events").observe(infl.size)
                if split.any_kernel:
                    obs.metrics.counter("spmd.kernel_events").inc(
                        infl.size)
                obs.health.observe_packet(infl.owner, infl.size, wall)
            results.append(res)
            stats.events_scanned += infl.size
            if split.any_kernel:
                stats.kernel_events += infl.size
            stats.fragment_evals += plan.evals_per_batch
            stats.fragment_evals_unshared += plan.unshared_evals
            stats.packets += 1
            stats.per_node_busy[infl.owner] = \
                stats.per_node_busy.get(infl.owner, 0.0) + wall
            if on_partial is not None:
                on_partial(PacketPartial(
                    seq=infl.seq, brick_id=infl.brick_id, start=infl.start,
                    size=infl.size, node=infl.owner, t_virtual=stamp(),
                    failures=0, partials=res, span=infl.span))
            if infl.span is not None:
                obs.tracer.end(infl.span,
                               t_virtual=obs.tracer.virtual_base + stamp())

        def finalize(step: List[_Inflight]) -> None:
            """Finalize one dispatched step; its chunks share the wall
            since the previous step finalized."""
            nonlocal t_prev
            for infl in step:
                self._finalize_chunk(infl, split)
            now = clock()
            wall = max(now - t_prev, 1e-9) / len(step)
            for infl in step:
                emit(infl, wall)
            t_prev = now

        pending: List[_Inflight] = []
        seq = 0
        for bid in sorted(rec.bricks):
            n = self.store.specs[bid].n_events
            owner = self.store.specs[bid].node
            start = 0
            while start < n:
                slots: List[Tuple[int, int, int]] = []
                while len(slots) < width and start < n:
                    size = self._chunk_size(seq, n - start, ramp)
                    slots.append((seq, start, size))
                    seq += 1
                    start += size
                spans = None
                if obs is not None:
                    spans = [obs.tracer.begin(
                        "packet", t_virtual=obs.tracer.virtual_base + stamp(),
                        seq=q, brick=bid, start=s0, size=sz, node=owner)
                        for q, s0, sz in slots]
                if width > 1:
                    step = self._dispatch_group(plan, split, slots, bid,
                                                owner, rec.calib_iters, spans)
                else:
                    q, s0, sz = slots[0]
                    step = [self._dispatch_chunk(
                        plan, split, q, bid, s0, sz, owner, rec.calib_iters,
                        None if spans is None else spans[0])]
                if not split.any_kernel:
                    # a pure-jnp chunk is already evaluated at dispatch
                    finalize(step)
                    continue
                # step i finalizes (host reads, merge, stream emit) while
                # step i+1's device work is in flight
                if pending:
                    finalize(pending)
                pending = step
        if pending:
            finalize(pending)

        k = len(job_ids)
        merged = (merge_lib.merge_batch(results) if results
                  else [merge_lib.QueryResult()
                        for _ in range(len(plan.targets()))])
        stats.fragment_results = dict(
            zip(plan.materialize_keys(), merged[k:]))
        merged = merged[:k]
        stats.makespan_s = clock() - t_start

        end = time.time()
        for jid, m in zip(job_ids, merged):
            self.catalog.update(
                jid, status=DONE, end_time=end,
                events_processed=m.n_processed, failures=0,
                result={
                    "n_selected": m.n_selected,
                    "n_processed": m.n_processed,
                    "sum_var": m.sum_var,
                    "makespan_s": stats.makespan_s,
                })
        return merged, stats


BACKENDS = ("sim", "spmd")


def make_backend(kind: str, catalog: MetadataCatalog, store: BrickStore,
                 **kwargs) -> ExecutionBackend:
    """Build an execution backend by name over a catalogue/store pair.

    ``kind`` is ``"sim"`` (:class:`SimulatedBackend`) or ``"spmd"``
    (:class:`SpmdBackend`); ``kwargs`` pass through to the chosen
    backend's constructor — unknown names raise ``ValueError`` so a
    mistyped ``--backend`` fails at construction, not mid-window."""
    if kind == "sim":
        return SimulatedBackend(catalog, store, **kwargs)
    if kind == "spmd":
        return SpmdBackend(catalog, store, **kwargs)
    raise ValueError(f"unknown backend {kind!r} (choose from {BACKENDS})")
