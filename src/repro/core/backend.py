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
- :class:`ChunkController` — EWMA sizing for ``chunk_events`` from
  measured per-chunk wall times (the PROOF-rule shape
  ``WindowController`` uses for window widths, applied to chunks).
- :class:`PlanSplit` — the mixed-window kernel/jnp split: plan targets
  inside the fused ``event_filter`` kernel's conjunctive family run as
  one kernel sub-batch per chunk, the rest through the jnp fragment
  walk, reassembled in slot order so prefixes stay bit-identical.
- :func:`make_backend` — string-keyed factory (``"sim"`` / ``"spmd"``)
  the service layer and ``launch/serve.py --backend`` use.

See ``docs/backends.md`` for the full contract (merge-order determinism,
clock semantics, failure semantics, Pallas fragment fusion, and the
performance-tuning knobs: block-shape autotune, adaptive chunk sizing,
mesh sharding, interpret auto-detect, double buffering, the resident
store).
"""
from __future__ import annotations

import dataclasses
import functools
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


class ChunkController:
    """EWMA controller for the SPMD scan's ``chunk_events``.

    The streaming sweet spot for chunk sizing mirrors the PROOF packet
    rule the :class:`~repro.service.frontend.WindowController` applies to
    window widths: a chunk should take about ``target_s`` seconds of
    scan, so the proposal is ``clamp(round(rate * target_s), min_chunk,
    max_chunk)`` where ``rate`` is an EWMA of measured events/second
    over completed chunks.  Chunks too small drown the scan in per-chunk
    dispatch/merge overhead; chunks too large starve the partial stream
    (time-to-first-partial grows linearly in chunk size).

    ``hysteresis`` is the same relative dead-band as the window
    controller's: the held size only moves when the proposal differs
    from it by more than ``hysteresis x current``, so a noisy rate
    estimate doesn't re-chunk every packet (chunk-size churn also churns
    kernel compilation caches, which are keyed on chunk shape).

    Determinism: the controller is a pure function of the observation
    sequence — drive it from an injectable clock
    (``SpmdBackend(clock=...)``) and a fixed seed reproduces the exact
    chunk boundaries, which is what keeps flight logs byte-identical
    under adaptive sizing (see ``tests/test_backend.py``)."""

    def __init__(self, *, initial: int = 64, min_chunk: int = 8,
                 max_chunk: int = 4096, target_s: float = 0.02,
                 alpha: float = 0.3, hysteresis: float = 0.25):
        if not (0.0 < alpha <= 1.0):
            raise ValueError("alpha must be in (0, 1]")
        if not (1 <= min_chunk <= max_chunk):
            raise ValueError("need 1 <= min_chunk <= max_chunk")
        if target_s <= 0:
            raise ValueError("target_s must be positive")
        if hysteresis < 0.0:
            raise ValueError("hysteresis must be >= 0")
        self.initial = initial
        self.min_chunk = min_chunk
        self.max_chunk = max_chunk
        self.target_s = target_s
        self.alpha = alpha
        self.hysteresis = hysteresis
        self._rate: Optional[float] = None
        self._held: Optional[int] = None

    def observe(self, events: int, wall_s: float) -> None:
        """Record one completed chunk: ``events`` swept in ``wall_s``
        seconds (host-observed, same clock as the backend's)."""
        if events <= 0 or wall_s <= 0:
            return
        rate = events / wall_s
        self._rate = rate if self._rate is None else (
            self.alpha * rate + (1 - self.alpha) * self._rate)

    @property
    def scan_rate(self) -> Optional[float]:
        """Smoothed events/second, or None before the first chunk."""
        return self._rate

    def chunk(self) -> int:
        """Chunk size for the next dispatch: the clamped ``rate *
        target_s`` proposal, filtered through the hysteresis dead-band."""
        if self._rate is None:
            target = max(self.min_chunk,
                         min(self.max_chunk, self.initial))
        else:
            target = max(self.min_chunk,
                         min(self.max_chunk,
                             int(round(self._rate * self.target_s))))
        if self._held is None or \
                abs(target - self._held) > self.hysteresis * self._held:
            self._held = target
        return self._held


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
        """True when EVERY target runs through the kernel (the
        all-in-family case the pre-split fusion hook required)."""
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


#: The brick arrays a kernel chunk reads; ``event_id`` stays on the host.
KERNEL_INPUTS = ("scalars", "tracks", "n_tracks")


def _input_bytes(store: BrickStore) -> int:
    """Host bytes of every brick's kernel inputs: what streaming sends
    per window, and what a resident image uploads once."""
    return sum(int(b[k].nbytes) for b in store.bricks.values()
               for k in KERNEL_INPUTS)


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
    for each of three chunks (two double-buffered kernel chunks and a
    mixed window's calibrated jnp copy), its sliced rows plus the
    ``(size, T, V)`` copy with V padded to whole 128-lane tiles."""
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
def _take_chunk(scalars, tracks, n_tracks, start, *, size, event_shape):
    """Events ``[start, start + size)`` of one resident brick, ``tracks``
    back in the kernel's ``(size, T, V)`` shape.  ``start`` is traced, so
    one program serves every chunk of a (brick, chunk) shape."""
    rows = tracks.shape[0] // scalars.shape[0]

    def take(a, first, n):
        return jax.lax.dynamic_slice_in_dim(a, first, n)

    return (take(scalars, start, size),
            take(tracks, start * rows, size * rows).reshape(
                size, *event_shape),
            take(n_tracks, start, size))


#: Bytes of brick copies in flight while an image uploads.  On a TPU v5e
#: the paper-width store (48 bricks, 12.69 GB) landed at 8.3 GB/s with
#: every copy dispatched at once, and slowed the scan overlapping it; in
#: groups of about 1 GiB, each landed before the next was sent, at
#: 11.6-11.8 GB/s.
UPLOAD_GROUP_BYTES = 1 << 30


class _ResidentImage:
    """One store's kernel inputs held in one device's memory, per brick:
    ``scalars``, ``n_tracks`` and ``tracks`` as rows (:func:`_track_rows`).
    The bricks are copied in scan order, in groups of at most
    :data:`UPLOAD_GROUP_BYTES` (or one brick), each group landed before
    the next is sent; the image is whole once constructed."""

    def __init__(self, store: BrickStore, device):
        self.store = store
        self.event_shape = (store.schema.max_tracks, store.schema.track_vars)
        rows, lanes = _track_rows(store.schema)
        self.bricks = {}
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
            group.append(self.bricks[bid])
            in_flight += nbytes
        jax.block_until_ready(group)
        self.host_bytes = _input_bytes(store)
        self.device_bytes = sum(a.on_device_size_in_bytes()
                                for arrays in self.bricks.values()
                                for a in arrays)

    def chunk(self, brick_id: int, start: int, size: int):
        """The chunk's ``(scalars, tracks, n_tracks)`` device arrays."""
        return _take_chunk(*self.bricks[brick_id], np.int32(start),
                           size=size, event_shape=self.event_shape)


#: Resident images by ``(id(store), device)``.  Backends hold their image
#: and this map only refers to it, so backends over one store on one
#: device share one copy, and it is freed with the last of them.
_IMAGES: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()


class SpmdBackend:
    """The SPMD realization of the contract: a chunked streaming scan
    over the brick shards.

    Bricks play the role of mesh shards (data that never moves); the
    scan visits them in brick-id order and sweeps each in chunks of
    ``chunk_events``.  Every chunk runs the SAME fragment-factored
    evaluation primitive as the simulation
    (:func:`~repro.core.jse.eval_plan_slice`), so unique fragments are
    evaluated once per chunk and a chunk's partials are bit-identical to
    the simulated backend's partials for the same slice.  Per-chunk
    :class:`~repro.core.jse.PacketPartial`\\ s stream out through
    ``on_partial`` in deterministic merge order, which is what makes
    prefix snapshots (via :class:`~repro.core.merge.MergeAccumulator`)
    bit-identical to ``tree_merge`` of the same prefix — the streaming
    guarantee the simulated path already had, now on the SPMD path.

    Differences from the simulation, by design:

    - **Clock**: ``t_virtual`` on emitted partials and
      ``JobStats.makespan_s`` are seconds on the backend's injectable
      ``clock`` (wall by default) since the window started.  With
      ``mesh_devices > 1`` on fewer physical devices, the stamps switch
      to the **lockstep mesh clock**: chunks are grouped ``mesh_devices``
      at a time, each group's cost is the *maximum* of its measured
      sub-chunk walls (all shards execute a group simultaneously on a
      real mesh), and stamps/makespan accumulate those group maxima —
      the critical-path time a D-device lockstep mesh would take for the
      measured per-shard compute.  With enough physical jax devices the
      group actually executes as one ``shard_map`` call and the clock is
      plain wall again.
    - **Failures**: shards are resident compute state, not remote disks;
      ``failure_script`` is a simulated-grid concept and a non-empty one
      raises ``ValueError`` rather than being silently ignored.
    - **Pallas fusion** (``use_pallas=True``): every plan target —
      per-query roots AND materialized boolean fragments — that matches
      the fused ``event_filter`` kernel's conjunctive family runs in the
      kernel epilogue in one track-streaming pass per chunk; the rest
      run through the jnp fragment walk on the same resident slice and
      the two sub-batches are reassembled in slot order
      (:class:`PlanSplit`), so a single out-of-family target no longer
      drops the whole window to pure jnp.  ``interpret=None``
      auto-detects (compiled on TPU/GPU, interpreter on CPU);
      ``autotune=True`` sweeps ``(block_e, block_t)`` per chunk shape
      and caches the winner in-process
      (``repro.kernels.event_filter.tune``).  Either way the per-chunk
      telemetry (``PacketTelemetry``) is recorded, so
      ``planner.fit_cost_weights`` calibrates from SPMD runs too.
    - **Double buffering** (``double_buffer=True``, the default): chunk
      ``i+1`` is dispatched before chunk ``i`` is finalized, so host-side
      ``MergeAccumulator`` prefix merging and partial emission overlap
      the device compute of the next chunk.  Merge order is unchanged
      (finalize strictly follows dispatch order).  Disabled automatically
      in emulated-mesh mode, where per-sub-chunk walls must be measured
      in isolation for the lockstep clock to be honest.
    - **Residency**: kernel chunks read one image of the store in device
      memory, so the store crosses from the host once, not every window.
      Per brick it holds ``scalars``, ``n_tracks`` and ``tracks`` as
      lane-dense rows (a ``(n, T, V)`` array would pad V to 128 lanes);
      each kernel chunk is one jitted on-device slice and reshape to the
      kernel's shape, which a mixed window's jnp sub-batch reads too,
      and ``event_id`` stays on the host.  The
      image is built at the first window with kernel targets, not at
      construction, and that window waits for it: the bricks are copied
      in scan order, in landed groups of :data:`UPLOAD_GROUP_BYTES` (a
      scan overlapping the copies measured slower on a v5e).
      Backends over the same store object on the same device share it,
      and it is freed with the last of them.  It is built only where it
      fits: its bytes plus the chunks in flight within the bytes the
      device's ``memory_stats()`` reports free (a device that reports
      no limit, such as the CPU, counts as room).  A store that does not
      fit streams each chunk from the host bricks, as do pure-jnp
      windows and the real-mesh ``shard_map`` path.
    - **Adaptive chunks** (``adaptive_chunks=True``): ``chunk_events``
      becomes the :class:`ChunkController`'s initial value and
      subsequent chunks are sized from measured per-chunk walls toward
      ``chunk_target_s`` seconds each.  Off by default — fixed chunks
      are what make matched-packetization bit-identity tests possible.
    """

    def __init__(self, catalog: MetadataCatalog, store: BrickStore, *,
                 chunk_events: int = 64, packet_ramp: Optional[int] = None,
                 ramp_factor: float = 2.0, use_pallas: bool = False,
                 interpret: Optional[bool] = None,
                 autotune: bool = False,
                 mesh_devices: int = 1,
                 adaptive_chunks: bool = False,
                 chunk_target_s: float = 0.02,
                 double_buffer: bool = True,
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
        self.autotune = autotune
        self.mesh_devices = mesh_devices
        self.adaptive_chunks = adaptive_chunks
        self.chunk_target_s = chunk_target_s
        self.double_buffer = double_buffer
        self.clock = clock
        self.cost_weights = None  # installed by the service after refits
        #: shards are resident compute state, not killable virtual nodes
        self.supports_failure_injection = False
        #: no per-node routing either — chunks visit shards in place, so
        #: policy decisions (avoid/probe/speculate) don't apply here
        self.supports_routing_policy = False
        # observability plane (repro.obs.Observability); None = disabled
        self.obs = None
        #: most recent autotune verdict (TunedShape) — bench reporting
        self.last_autotune = None
        # resolved lazily on first run (jax pins its devices at first use)
        self._mesh_real: Optional[bool] = None
        self._mesh = None
        # the resident image, or None while streaming; decided at the
        # first window with kernel targets (see "Residency")
        self._image: Optional[_ResidentImage] = None
        self._resident_decided = False

    # ------------------------------------------------------------------ #
    def _chunk_size(self, seq: int, remaining: int, ramp: Optional[int],
                    controller: Optional[ChunkController]) -> int:
        """Size of chunk ``seq``: the configured chunk (or the adaptive
        controller's proposal), capped early by the shared geometric
        stream ramp (``core/packets.py``), clipped to the shard
        remainder."""
        size = controller.chunk() if controller is not None \
            else self.chunk_events
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

    def _fuse_plan(self, plan: query_lib.FragmentPlan):
        """Back-compat fusion hook: the batched kernel params when EVERY
        plan target is in-family, else None.  Mixed windows no longer
        fall back wholesale — see :meth:`_split_plan` — but this remains
        the cheap "fully fused?" probe tests and tools use."""
        split = self._split_plan(plan)
        return (split.thresholds, split.var_idx) if split.full_kernel \
            else None

    # ------------------------------------------------------------------ #
    def _mesh_is_real(self) -> bool:
        """True when jax actually has ``mesh_devices`` devices (the
        ``shard_map`` fast path); False emulates the mesh with lockstep
        critical-path accounting, which only the CPU backend may do: on
        an accelerator a mesh wider than the devices raises instead of
        reporting made-up times.  Resolved once — jax pins its device
        count at first init."""
        if self._mesh_real is None:
            if self.mesh_devices <= 1:
                self._mesh_real = False
            else:
                n_dev = len(jax.devices())
                if n_dev < self.mesh_devices \
                        and jax.default_backend() != "cpu":
                    raise ValueError(
                        f"mesh_devices={self.mesh_devices} but the "
                        f"{jax.default_backend()} backend has {n_dev} "
                        f"device(s)")
                self._mesh_real = n_dev >= self.mesh_devices
        return self._mesh_real

    def _scan_mesh(self):
        """The 1-D ``"scan"`` mesh over the first ``mesh_devices``
        devices (real-mesh path only; built once)."""
        if self._mesh is None:
            from jax.sharding import Mesh
            self._mesh = Mesh(np.asarray(jax.devices()[:self.mesh_devices]),
                              ("scan",))
        return self._mesh

    def _maybe_autotune(self, split: PlanSplit, brick_id: int,
                        calib_iters: int) -> Tuple[int, int]:
        """Resolve the kernel block shapes for this window: the in-process
        autotune winner for the (chunk shape x K x calib) class when
        ``autotune=True``, the fixed default otherwise."""
        from repro.kernels.event_filter import tune as ef_tune
        if not (self.autotune and split.any_kernel):
            return ef_tune.DEFAULT_SHAPE
        batch = self.store.bricks[brick_id]
        n = min(self.chunk_events, batch["scalars"].shape[0])
        import jax.numpy as jnp
        tuned = ef_tune.autotune_block_shapes(
            jnp.asarray(batch["scalars"][:n]),
            jnp.asarray(batch["tracks"][:n]),
            jnp.asarray(batch["n_tracks"][:n]),
            split.thresholds, var_idx=split.var_idx,
            calib_iters=calib_iters, interpret=self.interpret)
        self.last_autotune = tuned
        if self.obs is not None:
            self.obs.metrics.gauge("spmd.autotune.block_e").set(
                tuned.block_e)
            self.obs.metrics.gauge("spmd.autotune.block_t").set(
                tuned.block_t)
        return tuned.block_e, tuned.block_t

    def _adopt_image(self) -> None:
        """Find or build the store's resident image on the scan device,
        once per backend (see "Residency" in the class docstring).  The
        backend that builds it records the ``upload`` span and counts the
        image in ``spmd.h2d_bytes``."""
        if self._resident_decided:
            return
        self._resident_decided = True
        device = jax.devices()[0]
        key = (id(self.store), device)
        image = _IMAGES.get(key)
        if image is None or image.store is not self.store:
            largest = max(s.n_events for s in self.store.specs.values())
            size = largest if self.adaptive_chunks \
                else min(self.chunk_events, largest)
            room = _device_room(device)
            need = _input_bytes(self.store) + _chunk_reserve(
                self.store.schema, size)
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
        double buffering finalizes chunk i after chunk i+1 is launched.
        Phases are timed on the wall clock; on the virtual axis they sit
        at their packet's start."""
        return self.obs.tracer.begin(name, t_virtual=packet.t0_virtual,
                                     parent=packet, seq=packet.attrs["seq"],
                                     **attrs)

    def _dispatch_chunk(self, plan: query_lib.FragmentPlan,
                        split: PlanSplit, seq: int, brick_id: int,
                        start: int, size: int, owner: int,
                        calib_iters: int,
                        block_shapes: Tuple[int, int],
                        span=None) -> _Inflight:
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
        infl.ids = np.asarray(batch["event_id"][start:start + size])
        if self._image is not None:
            scalars, tracks, n_tracks = self._image.chunk(brick_id, start,
                                                          size)
            nbytes = 0
        else:
            sl = [batch[k][start:start + size] for k in KERNEL_INPUTS]
            scalars, tracks, n_tracks = (jnp.asarray(a) for a in sl)
            nbytes = int(sum(a.nbytes for a in sl))
        if stage is not None:
            stage.attrs["bytes"] = nbytes
            metrics = self.obs.metrics
            metrics.counter("spmd.h2d_bytes").inc(nbytes)
            if self._image is not None:
                metrics.counter("spmd.resident_chunks").inc()
            self.obs.tracer.end(stage)
        launch = None if span is None else self._phase("launch", span)
        be, bt = block_shapes
        infl.mask_dev, infl.var_dev = ef_ops.event_filter_batch(
            scalars, tracks, n_tracks, split.thresholds,
            var_idx=split.var_idx, calib_iters=calib_iters,
            interpret=self.interpret, block_e=be, block_t=bt)
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
        if launch is not None:
            self.obs.tracer.end(launch)
        return infl

    def _dispatch_group(self, plan: query_lib.FragmentPlan,
                        split: PlanSplit,
                        slots: List[Tuple[int, int, int]], brick_id: int,
                        owner: int, calib_iters: int,
                        block_shapes: Tuple[int, int],
                        spans: Optional[list] = None) -> List[_Inflight]:
        """Dispatch one mesh group — up to ``mesh_devices`` chunk slots
        of one brick — as a single ``shard_map`` kernel call over the
        stacked, zero-padded ``(D, n_max, ...)`` slabs (each device owns
        one sub-chunk).  Partials are still sliced back out per slot, so
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

        def slab(key, start, size):
            a = np.asarray(batch[key][start:start + size])
            if size < n_max:
                pad = [(0, n_max - size)] + [(0, 0)] * (a.ndim - 1)
                a = np.pad(a, pad)
            return a

        def stacked(key):
            rows = [slab(key, start, size) for _, start, size in slots]
            while len(rows) < d:    # tail group: replicate a dummy slab
                rows.append(np.zeros_like(rows[0]))
            return np.stack(rows)

        host = [stacked(k) for k in KERNEL_INPUTS]
        # each slab goes straight to the device that owns it
        scalars, tracks, n_tracks = (jax.device_put(a, per_device)
                                     for a in host)
        if stage is not None:
            nbytes = int(sum(a.nbytes for a in host))
            stage.attrs["bytes"] = nbytes
            self.obs.metrics.counter("spmd.h2d_bytes").inc(nbytes)
            self.obs.tracer.end(stage)
        launch = None if first is None else self._phase(
            "launch", first, chunks=len(slots))
        be, bt = block_shapes
        fn = ef_ops.sharded_event_filter_batch(
            mesh, var_idx=split.var_idx, calib_iters=calib_iters,
            interpret=resolve_interpret(self.interpret), block_e=be,
            block_t=bt)
        gmask, gvar = fn(scalars, tracks, n_tracks, split.thresholds)
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
        controller = (ChunkController(initial=self.chunk_events,
                                      target_s=self.chunk_target_s)
                      if self.adaptive_chunks else None)
        bricks = sorted(rec.bricks)
        block_shapes = (self._maybe_autotune(split, bricks[0],
                                             rec.calib_iters)
                        if bricks and self.use_pallas else None)
        mesh = max(1, self.mesh_devices)
        lockstep = mesh > 1 and not self._mesh_is_real()
        # with enough physical devices AND kernel targets, whole groups
        # execute as one shard_map call; otherwise (pure-jnp window on a
        # real mesh) the scan degrades to the sequential stream path
        mesh_fast = mesh > 1 and not lockstep and split.any_kernel
        if split.any_kernel and not mesh_fast:
            self._adopt_image()
        # double buffering applies only where dispatch is actually lazy
        # (kernel sub-batches): a pure-jnp chunk evaluates eagerly at
        # dispatch, so holding it back would just delay its partial by a
        # whole chunk; and lockstep emulation needs isolated walls
        buffered = (self.double_buffer and split.any_kernel
                    and not lockstep and not mesh_fast)

        results: List[List[merge_lib.QueryResult]] = []
        t_start = clock()
        t_lockstep = 0.0    # critical-path seconds (emulated mesh clock)
        t_prev = t_start    # previous finalize completion (chunk walls)
        group_walls: List[float] = []

        def stamp() -> float:
            return t_lockstep if lockstep else clock() - t_start

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
            if controller is not None:
                controller.observe(infl.size, wall)
            if on_partial is not None:
                on_partial(PacketPartial(
                    seq=infl.seq, brick_id=infl.brick_id, start=infl.start,
                    size=infl.size, node=infl.owner, t_virtual=stamp(),
                    failures=0, partials=res, span=infl.span))
            if infl.span is not None:
                obs.tracer.end(infl.span,
                               t_virtual=obs.tracer.virtual_base + stamp())

        pending: Optional[_Inflight] = None

        def finalize(infl: _Inflight) -> None:
            nonlocal t_prev
            self._finalize_chunk(infl, split)
            now = clock()
            emit(infl, max(now - t_prev, 1e-9))
            t_prev = now

        seq = 0
        for bid in bricks:
            n = self.store.specs[bid].n_events
            owner = self.store.specs[bid].node
            start = 0
            while start < n:
                if lockstep:
                    # one lockstep group: up to `mesh` sub-chunks of this
                    # brick, each measured in isolation; the group costs
                    # the MAX of its walls on the mesh clock
                    group: List[_Inflight] = []
                    group_walls.clear()
                    while len(group) < mesh and start < n:
                        size = self._chunk_size(seq, n - start, ramp,
                                                controller)
                        t0 = clock()
                        infl = self._dispatch_chunk(
                            plan, split, seq, bid, start, size, owner,
                            rec.calib_iters, block_shapes)
                        self._finalize_chunk(infl, split)
                        group_walls.append(max(clock() - t0, 1e-9))
                        group.append(infl)
                        seq += 1
                        start += size
                    t_lockstep += max(group_walls)
                    for infl, wall in zip(group, group_walls):
                        emit(infl, wall)
                    continue
                if mesh_fast:
                    # one shard_map call per group of up to `mesh` slots;
                    # partials still per slot, in order
                    slots: List[Tuple[int, int, int]] = []
                    while len(slots) < mesh and start < n:
                        size = self._chunk_size(seq, n - start, ramp,
                                                controller)
                        slots.append((seq, start, size))
                        seq += 1
                        start += size
                    spans = None
                    if obs is not None:
                        spans = [obs.tracer.begin(
                            "packet",
                            t_virtual=obs.tracer.virtual_base + stamp(),
                            seq=q, brick=bid, start=s0, size=sz, node=owner)
                            for q, s0, sz in slots]
                    t0 = clock()
                    infls = self._dispatch_group(plan, split, slots, bid,
                                                 owner, rec.calib_iters,
                                                 block_shapes, spans)
                    for infl in infls:
                        self._finalize_chunk(infl, split)
                    per = max(clock() - t0, 1e-9) / len(slots)
                    for infl in infls:
                        emit(infl, per)
                    continue
                size = self._chunk_size(seq, n - start, ramp, controller)
                span = None
                if obs is not None:
                    span = obs.tracer.begin(
                        "packet",
                        t_virtual=obs.tracer.virtual_base + stamp(),
                        seq=seq, brick=bid, start=start, size=size,
                        node=owner)
                infl = self._dispatch_chunk(plan, split, seq, bid, start,
                                            size, owner, rec.calib_iters,
                                            block_shapes, span)
                if not buffered:
                    finalize(infl)
                else:
                    if pending is not None:
                        # chunk i finalizes (host merge + stream emit)
                        # while chunk i+1's device compute is in flight
                        finalize(pending)
                    pending = infl
                seq += 1
                start += size
        if pending is not None:
            finalize(pending)

        k = len(job_ids)
        merged = (merge_lib.merge_batch(results) if results
                  else [merge_lib.QueryResult()
                        for _ in range(len(plan.targets()))])
        stats.fragment_results = dict(
            zip(plan.materialize_keys(), merged[k:]))
        merged = merged[:k]
        stats.makespan_s = t_lockstep if lockstep \
            else clock() - t_start

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
