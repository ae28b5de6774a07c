"""Fused event filter+calibrate+reduce Pallas TPU kernel.

TPU adaptation of the paper's per-node event-processing loop: instead of
the CPU's "calibrate file, write it back, re-read to filter" (three HBM
passes on TPU), one VMEM pass per track tile calibrates ``pt`` and
accumulates the track aggregates per event across track tiles — tracks
stream HBM->VMEM exactly once.

Grid: (event_blocks, track_tiles); the track-tile axis is the fast
(sequential) axis, so the per-event accumulators live in the output blocks
(count/sum), which Pallas keeps resident in VMEM across the inner axis.

Layout (what Mosaic accepts): every operand but the tracks is 3-D with
the event axis LEADING, so a block of ``BE`` events is ``BE`` separate
tiles and the kernel never moves data between the event axis and the
sublane/lane axes.  The kernel body walks its ``BE`` events one at a
time; per event it loads the ``pt`` column ``(BT, 1)`` of the track tile,
compares it with the ``(1, K)`` thresholds row and sums over the sublane
axis into a ``(1, K)`` count row.  Booleans are only produced by
comparisons of equal-shaped f32 operands and consumed by a select; no
boolean is reshaped or broadcast.

BlockSpecs (VMEM):
  sc_sel   (BE, 1, K)   — each query's scalar column, selected outside
  tracks   (BE, BT, V)  — both axes blocked (the streamed operand)
  n_tracks (BE, 1, 1)
  thr      (4, 1, K)    — [scalar_thresh; pt_thresh; min_count; sum_cap]
  outputs: mask (BE, 1, K), cnt (BE, 1, K), ssum (BE, 1, 1)

A track tile pads ``V`` to 128 lanes in VMEM, so one buffer is
``BE * BT * 512`` bytes at any ``V <= 128``: :func:`vmem_bytes` is the
estimate the block-shape choices are checked against (:data:`VMEM_BUDGET`,
v5e's default scoped VMEM).

Non-divisible grids: track columns past ``t_total`` are masked out of the
accumulators explicitly; event rows past the end of a partial event block
compute on whatever the pad holds and are never written back (each event
row is independent).  The wrappers validate shapes up front — a
zero-sized operand raises a clear ``ValueError`` instead of a Pallas
trace error.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

#: Default ``(block_e, block_t)``: a 2 MiB track buffer (4 MiB
#: double-buffered) at the paper's width, well inside :data:`VMEM_BUDGET`.
DEFAULT_BLOCK = (8, 512)

#: Scoped VMEM a kernel may use without raising the compiler's limit
#: (TPU v5e default: 16 MiB).
VMEM_BUDGET = 16 * 1024 * 1024


def _tile_bytes(rows: int, lanes: int) -> int:
    """VMEM bytes of one f32/i32 ``(rows, lanes)`` slab: lanes pad to 128,
    rows to 8 (a single row keeps the (1, 128) tiling)."""
    return (rows if rows == 1 else -(-rows // 8) * 8) \
        * (-(-lanes // 128) * 128) * 4


def vmem_bytes(block_e: int, block_t: int, v: int, k: int) -> int:
    """Estimated VMEM of one kernel instance: every operand and output
    block double-buffered, plus the per-event ``(BT, K)`` temporaries of
    the body."""
    blocks = (block_e * _tile_bytes(block_t, v)        # tracks
              + 3 * block_e * _tile_bytes(1, k)        # sc_sel, mask, cnt
              + 2 * block_e * _tile_bytes(1, 1)        # n_tracks, ssum
              + 4 * _tile_bytes(1, k))                 # thresholds
    return 2 * blocks + 6 * _tile_bytes(block_t, k)


def _validate(scalars, tracks, n_tracks, thresholds, *, batch: bool,
              block_e: int, block_t: int):
    """Shape/size validation shared by both wrappers (see module doc):
    reject zero-sized operands and malformed thresholds BEFORE tracing,
    with errors that name the offending operand."""
    if scalars.ndim != 2 or tracks.ndim != 3 or n_tracks.ndim != 1:
        raise ValueError(
            f"event_filter expects scalars (N,S), tracks (N,T,V), "
            f"n_tracks (N,); got {scalars.shape}, {tracks.shape}, "
            f"{n_tracks.shape}")
    n, _ = scalars.shape
    nt, t, v = tracks.shape
    if n == 0 or t == 0 or v == 0:
        raise ValueError(
            f"event_filter got a zero-sized operand (scalars {scalars.shape}, "
            f"tracks {tracks.shape}): empty chunks must be skipped by the "
            f"caller, the kernel has no zero-width grid")
    if nt != n or n_tracks.shape[0] != n:
        raise ValueError(
            f"event axis mismatch: scalars N={n}, tracks N={nt}, "
            f"n_tracks N={n_tracks.shape[0]}")
    if block_e <= 0 or block_t <= 0:
        raise ValueError(
            f"block shapes must be positive, got block_e={block_e}, "
            f"block_t={block_t}")
    if batch:
        if thresholds.ndim != 2 or thresholds.shape[0] != 4 \
                or thresholds.shape[1] == 0:
            raise ValueError(
                f"batched thresholds must be (4, K) with K >= 1, got "
                f"{thresholds.shape}")
    elif thresholds.shape != (4,):
        raise ValueError(f"thresholds must be (4,), got {thresholds.shape}")


def _calibrate_pt(i, pt):
    """One calibration pass on the ``pt`` column: the ``pt`` column of
    ``core.query.calibrate`` (whose correction of column 0 reads only
    column 0), so the bits match the full-track calibration."""
    return pt * (1.0 + 0.01 * jnp.tanh(pt) * jax.lax.rsqrt(1.0 + pt * pt))


def _kernel(sc_ref, tracks_ref, ntr_ref, thr_ref, mask_ref, cnt_ref,
            sum_ref, *, calib_iters: int, block_e: int, block_t: int,
            t_total: int):
    """K-query shared scan: tracks stream HBM->VMEM once; the per-query
    track counts (cnt is (BE, 1, K)) and masks amortize that single read
    across the whole coalesced batch.  sum(pt) is query-independent, so
    one (BE, 1, 1) accumulator serves every query."""
    tt = pl.program_id(1)

    @pl.when(tt == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        sum_ref[...] = jnp.zeros_like(sum_ref)

    pt_thr = thr_ref[1]                                        # (1, K)
    tidx = tt * block_t + jax.lax.broadcasted_iota(
        jnp.int32, (block_t, 1), 0)
    in_tile = tidx < t_total                                   # (BT, 1)

    def one_event(e, carry):
        pt = tracks_ref[e, :, 0:1].astype(jnp.float32)         # (BT, 1)
        pt = jax.lax.fori_loop(0, calib_iters, _calibrate_pt, pt)
        valid = in_tile & (tidx < ntr_ref[e])
        # an invalid track becomes -inf, which is above no threshold
        pt_valid = jnp.where(valid, pt, -jnp.inf)
        hit = jnp.where(pt_valid > pt_thr, 1.0, 0.0)           # (BT, K)
        cnt_ref[e] += jnp.sum(hit, axis=0, keepdims=True)
        sum_ref[e] += jnp.sum(jnp.where(valid, pt, 0.0), axis=0,
                              keepdims=True)
        return carry

    jax.lax.fori_loop(0, block_e, one_event, 0)

    @pl.when(tt == pl.num_programs(1) - 1)
    def _finalize():
        shape = mask_ref.shape                                 # (BE, 1, K)
        thr = thr_ref[...]
        cap = jnp.broadcast_to(thr[3:4], shape)
        ssum = jnp.broadcast_to(sum_ref[...], shape)
        mask = ((sc_ref[...] > thr[0:1]) & (cnt_ref[...] >= thr[2:3])
                & (jnp.logical_not(cap > 0.0) | (ssum < cap)))
        mask_ref[...] = jnp.where(mask, 1.0, 0.0)


def event_filter_batch_pallas(scalars, tracks, n_tracks, thresholds, *,
                              var_idx: tuple, calib_iters: int,
                              block_e: int = DEFAULT_BLOCK[0],
                              block_t: int = DEFAULT_BLOCK[1],
                              interpret: bool | None = None):
    """Batched variant: thresholds (4, K) f32 = per-query
    [scalar_thresh; pt_thresh; min_count; sum_cap] columns, var_idx a
    static K-tuple of scalar indices.  Returns (mask (N, K), var (N,)).
    ``interpret=None`` auto-detects (compiled on TPU/GPU, interpreter on
    CPU — see ``repro.kernels.default_interpret``)."""
    _validate(scalars, tracks, n_tracks, thresholds, batch=True,
              block_e=block_e, block_t=block_t)
    n, _ = scalars.shape
    _, t, v = tracks.shape
    k = thresholds.shape[1]
    block_e = min(block_e, n)
    block_t = min(block_t, t)
    grid = (pl.cdiv(n, block_e), pl.cdiv(t, block_t))
    sc_sel = jnp.stack([scalars[:, i] for i in var_idx],
                       axis=-1).astype(jnp.float32)[:, None, :]

    kernel = functools.partial(_kernel, calib_iters=calib_iters,
                               block_e=block_e, block_t=block_t, t_total=t)
    per_event = lambda e, tt: (e, 0, 0)
    mask, _, _ = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e, 1, k), per_event),
            pl.BlockSpec((block_e, block_t, v), lambda e, tt: (e, tt, 0)),
            pl.BlockSpec((block_e, 1, 1), per_event),
            pl.BlockSpec((4, 1, k), lambda e, tt: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_e, 1, k), per_event),
            pl.BlockSpec((block_e, 1, k), per_event),
            pl.BlockSpec((block_e, 1, 1), per_event),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((n, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((n, 1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(sc_sel, tracks, n_tracks.astype(jnp.int32).reshape(n, 1, 1),
      thresholds.astype(jnp.float32).reshape(4, 1, k))
    return mask[:, 0, :], scalars[:, 0]


def event_filter_pallas(scalars, tracks, n_tracks, thresholds, *,
                        var_idx: int, calib_iters: int,
                        block_e: int = DEFAULT_BLOCK[0],
                        block_t: int = DEFAULT_BLOCK[1],
                        interpret: bool | None = None):
    """Single-query form (the K=1 case of the batched kernel):
    scalars (N,S) f32, tracks (N,T,V) f32, n_tracks (N,) i32,
    thresholds (4,) f32 = [scalar_thresh, pt_thresh, min_count, sum_cap].
    Returns (mask (N,), var (N,))."""
    _validate(scalars, tracks, n_tracks, thresholds, batch=False,
              block_e=block_e, block_t=block_t)
    mask, var = event_filter_batch_pallas(
        scalars, tracks, n_tracks, thresholds[:, None], var_idx=(var_idx,),
        calib_iters=calib_iters, block_e=block_e, block_t=block_t,
        interpret=interpret)
    return mask[:, 0], var
