"""Block-shape autotuner for the fused ``event_filter`` kernel.

The kernel's ``(block_e, block_t)`` block shapes default to
:data:`~repro.kernels.event_filter.kernel.DEFAULT_BLOCK`, but the best
shape depends on the chunk shape the SPMD scan actually feeds
(``chunk_events`` x tracks x vars), the query width K, and whether the
kernel runs compiled or interpreted.  This
module measures instead of guessing:

- :func:`autotune_block_shapes` sweeps :data:`CANDIDATES` on a sample
  chunk (deduplicating candidates that clamp to the same effective
  shape), times each with the jitted dispatch it will actually run
  under, and returns a :class:`TunedShape` carrying the winner, the
  fixed-default baseline, every measurement, and a roofline point
  (bytes moved / useful FLOPs / achieved GB/s and GFLOP/s at the
  winner's runtime).  Candidates that cannot compile at the chunk's
  width (VMEM overflow, :func:`candidates_for`) are not swept.
- Winners are cached **in-process** by :func:`shape_key` (chunk shape x
  schema width x K x calib x interpret), so a scan pays the sweep once
  per shape class; ``BENCH_backend.json`` persists the roofline points
  via ``benchmarks/bench_backend.py --autotune``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Sequence, Tuple

from repro.kernels import resolve_interpret
from repro.kernels.event_filter.kernel import (DEFAULT_BLOCK, VMEM_BUDGET,
                                               vmem_bytes)

#: The sweep grid: few-event blocks with long track tiles for the
#: paper's 1 MB events, wider event blocks for narrow schemas (where
#: track tiles clamp short enough for (128, 512) to fit).  Candidates
#: clamp to the operand shape, so an oversized entry is timed at most
#: once (see the dedup in the sweep); a candidate whose clamped block
#: overflows the kernel's VMEM budget at the chunk's width is dropped
#: (:func:`candidates_for`).
CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (8, 256), (8, 512), (8, 1024), (16, 256), (16, 512), (32, 128),
    (32, 256), (64, 128), (128, 128), (128, 512))

#: The fixed default the tuned shape is benchmarked against.
DEFAULT_SHAPE: Tuple[int, int] = DEFAULT_BLOCK

#: In-process winner cache: ``shape_key -> TunedShape``.
_CACHE: Dict[tuple, "TunedShape"] = {}


@dataclasses.dataclass(frozen=True)
class TunedShape:
    """One autotune verdict: the winning block shape for a shape class,
    with the evidence (per-candidate timings) and the winner's roofline
    point (estimated bytes/FLOPs over measured runtime)."""
    block_e: int
    block_t: int
    best_ms: float
    default_ms: float
    #: ((block_e, block_t, ms), ...) for every effective candidate timed
    measurements: Tuple[Tuple[int, int, float], ...]
    #: bytes / flops estimates + achieved GB/s, GFLOP/s, FLOP/byte
    roofline: Dict[str, float]

    @property
    def speedup_vs_default(self) -> float:
        """default_ms / best_ms — >= 1.0 by construction (the default is
        itself a candidate, so the winner can never be slower)."""
        return self.default_ms / self.best_ms if self.best_ms > 0 else 1.0

    def as_dict(self) -> dict:
        """JSON-ready form for BENCH snapshot recording."""
        return {
            "block_e": self.block_e, "block_t": self.block_t,
            "best_ms": round(self.best_ms, 4),
            "default_ms": round(self.default_ms, 4),
            "speedup_vs_default": round(self.speedup_vs_default, 3),
            "measurements": [[be, bt, round(ms, 4)]
                             for be, bt, ms in self.measurements],
            "roofline": {k: (round(v, 4) if isinstance(v, float) else v)
                         for k, v in self.roofline.items()},
        }


def shape_key(n: int, t: int, v: int, s: int, k: int, calib_iters: int,
              interpret: Optional[bool]) -> tuple:
    """The in-process cache key: everything the winner depends on —
    chunk shape (n, t, v), scalar width s, query width k, calibration
    depth, and the *resolved* interpret mode."""
    return (n, t, v, s, k, calib_iters, resolve_interpret(interpret))


def roofline_point(n: int, t: int, v: int, s: int, k: int,
                   calib_iters: int, ms: float) -> Dict[str, float]:
    """Estimated traffic/compute for one kernel invocation, scaled by a
    measured runtime into achieved GB/s / GFLOP/s.  Traffic counts each
    operand once (the kernel's whole point is that tracks stream
    HBM->VMEM exactly once); FLOPs count the calibration polynomial
    (~10 flops/element/iter: tanh+rsqrt+mults) plus the per-query
    compare/accumulate epilogue."""
    bytes_moved = 4.0 * (n * t * v          # tracks, one streaming read
                         + n * s            # scalars
                         + n                # n_tracks
                         + n * k + n)       # mask + var outputs
    flops = (10.0 * calib_iters * n * t * v     # calibration sweep
             + n * t * (k + 2.0))               # hit test + cnt/sum accum
    sec = max(ms, 1e-9) / 1e3
    return {
        "bytes": bytes_moved, "flops": flops,
        "intensity_flop_per_byte": flops / bytes_moved,
        "gbytes_per_s": bytes_moved / sec / 1e9,
        "gflops_per_s": flops / sec / 1e9,
        "ms": ms,
    }


def candidates_for(n: int, t: int, v: int, k: int,
                   candidates: Sequence[Tuple[int, int]] = CANDIDATES
                   ) -> Tuple[Tuple[int, int], ...]:
    """The candidates that compile for an ``(n, t, v)`` chunk with ``k``
    queries: those whose clamped block fits the kernel's VMEM budget."""
    return tuple((be, bt) for be, bt in candidates
                 if vmem_bytes(min(be, n), min(bt, t), v, k) <= VMEM_BUDGET)


def _time_once(fn, repeats: int) -> float:
    """Best-of-``repeats`` wall milliseconds, after one untimed warmup
    call (compilation / trace caching)."""
    fn()  # warmup: compile + cache
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        fn()
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best


def autotune_block_shapes(scalars, tracks, n_tracks, thresholds, *,
                          var_idx: Tuple[int, ...], calib_iters: int,
                          interpret: Optional[bool] = None,
                          candidates: Sequence[Tuple[int, int]] = CANDIDATES,
                          repeats: int = 3,
                          cache: Optional[Dict[tuple, TunedShape]] = None
                          ) -> TunedShape:
    """Sweep ``candidates`` on the given sample chunk and return the
    winning :class:`TunedShape` (cached in-process by shape class).

    Candidates whose blocks clamp to the same effective ``(min(be, n),
    min(bt, t))`` are timed once — on small streaming chunks the sweep
    frequently collapses to a couple of distinct shapes, which is what
    keeps autotune affordable mid-scan.  The fixed
    :data:`DEFAULT_SHAPE` is always included, so
    ``speedup_vs_default >= 1.0``."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.event_filter import ops as ef_ops

    n, s = scalars.shape
    _, t, v = tracks.shape
    k = thresholds.shape[1]
    key = shape_key(n, t, v, s, k, calib_iters, interpret)
    store = _CACHE if cache is None else cache
    hit = store.get(key)
    if hit is not None:
        return hit

    scalars = jnp.asarray(scalars)
    tracks = jnp.asarray(tracks)
    n_tracks = jnp.asarray(n_tracks)
    thresholds = jnp.asarray(thresholds)

    effective: Dict[Tuple[int, int], Tuple[int, int]] = {}
    for be, bt in candidates_for(n, t, v, k, tuple(candidates)) \
            + (DEFAULT_SHAPE,):
        effective.setdefault((min(be, n), min(bt, t)), (be, bt))

    def run(be, bt):
        mask, var = ef_ops.event_filter_batch(
            scalars, tracks, n_tracks, thresholds, var_idx=var_idx,
            calib_iters=calib_iters, interpret=interpret,
            block_e=be, block_t=bt)
        jax.block_until_ready((mask, var))

    timed = []
    for (ebe, ebt), (be, bt) in sorted(effective.items()):
        ms = _time_once(lambda: run(be, bt), repeats)
        timed.append((be, bt, ms))
    best_be, best_bt, best_ms = min(timed, key=lambda r: r[2])
    dbe, dbt = DEFAULT_SHAPE
    default_ms = next(ms for be, bt, ms in timed
                      if (min(be, n), min(bt, t))
                      == (min(dbe, n), min(dbt, t)))
    tuned = TunedShape(
        block_e=best_be, block_t=best_bt, best_ms=best_ms,
        default_ms=default_ms, measurements=tuple(timed),
        roofline=roofline_point(n, t, v, s, k, calib_iters, best_ms))
    store[key] = tuned
    return tuned


def cached_shapes() -> Dict[tuple, TunedShape]:
    """A snapshot of the in-process winner cache (bench reporting)."""
    return dict(_CACHE)


def clear_cache() -> None:
    """Drop every cached winner (tests / fresh bench sweeps)."""
    _CACHE.clear()
