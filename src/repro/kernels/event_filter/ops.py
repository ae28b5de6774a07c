"""Jitted wrapper for the event-filter kernel + query-AST pattern matcher.

``filter_and_summarize`` accepts the GEPS canonical hot-query family

    "<scalar> > A && count(pt > B) >= C [&& sum(pt) < D]"

extracts (A, B, C, D) from the parsed AST and dispatches to the fused
Pallas kernel; anything else falls back to the pure-jnp compiled query
(same results, just without the fusion win).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import query as q
from repro.kernels.event_filter.kernel import (DEFAULT_BLOCK,
                                               event_filter_batch_pallas,
                                               event_filter_pallas)
from repro.kernels.event_filter.ref import (event_filter_batch_ref,
                                            event_filter_ref)


def match_canonical(expr: str, schema) -> Optional[dict]:
    """Returns kernel params if the expression matches the FULL hot
    family — a strictness check over :func:`match_epilogue` (one matcher
    encodes the kernel's term shapes): both the scalar-threshold and the
    count terms must be present."""
    params = match_epilogue(expr, schema)
    if params is None or not {"scalar", "count"} <= params["terms"]:
        return None
    return params


def batch_kernel_params(params) -> Tuple[jnp.ndarray, Tuple[int, ...]]:
    """Assemble the batched kernel's inputs from per-target param dicts
    (``match_canonical`` / ``match_epilogue`` output): the ``(4, K)``
    float32 thresholds array — rows [scalar_thresh; pt_thresh;
    min_count; sum_cap] — and the static ``var_idx`` tuple.  The single
    place the kernel's threshold-row layout is encoded on the host
    side."""
    thresholds = jnp.array(
        [[p["scalar_thresh"] for p in params],
         [p["pt_thresh"] for p in params],
         [p["min_count"] for p in params],
         [p["sum_cap"] for p in params]], jnp.float32)   # (4, K)
    return thresholds, tuple(p["var_idx"] for p in params)


def match_epilogue(target, schema) -> Optional[dict]:
    """Relaxed matcher for kernel-EPILOGUE fusion of fragment-plan targets.

    ``match_canonical`` requires the full hot family (scalar threshold AND
    count term); a fragment plan's targets also include materialized
    boolean fragments that are *subsets* of it — a bare
    ``count(pt > 15) >= 2`` conjunct, a lone scalar cut.  This matcher
    accepts any ``&&``-conjunction of the kernel's three term shapes with
    each term OPTIONAL (at least one present):

        <scalar> > A    |    count(pt > B) >= C    |    sum(pt) < D

    and encodes missing terms as neutral thresholds the kernel epilogue
    already treats as pass-through: no scalar term -> ``scalar_thresh =
    -inf`` (any finite scalar passes), no count term -> ``min_count = 0``
    (the count accumulator is always >= 0), no sum term -> ``sum_cap =
    -1`` (the kernel's existing no-cap sentinel; a sum term with D <= 0
    is rejected rather than aliased onto it).  ``target`` is an AST node
    (what :meth:`FragmentPlan.targets` holds) or an expression string.
    Returns kernel params — with ``"terms"``, the set of term kinds that
    were present, so :func:`match_canonical` can impose its stricter
    full-family requirement — or None when the target is outside the
    family."""
    if isinstance(target, str):
        try:
            target = q.parse(target)
        except q.QueryError:
            return None

    def is_cmp(node, op):
        return isinstance(node, q.Bin) and node.op == op

    terms = []

    def flatten_and(node):
        if isinstance(node, q.Bin) and node.op == "&&":
            flatten_and(node.lhs)
            flatten_and(node.rhs)
        else:
            terms.append(node)

    flatten_and(target)
    out = {"var_idx": 0, "scalar_thresh": float("-inf"),
           "pt_thresh": 0.0, "min_count": 0.0, "sum_cap": -1.0}
    seen = set()
    for t in terms:
        if (is_cmp(t, ">") and isinstance(t.lhs, q.Var)
                and isinstance(t.rhs, q.Num) and "scalar" not in seen):
            try:
                out["var_idx"] = schema.scalar_index(t.lhs.name)
            except ValueError:
                return None
            out["scalar_thresh"] = t.rhs.value
            seen.add("scalar")
        elif (is_cmp(t, ">=") and isinstance(t.lhs, q.Agg)
              and t.lhs.fn == "count" and is_cmp(t.lhs.arg, ">")
              and isinstance(t.lhs.arg.lhs, q.Var)
              and t.lhs.arg.lhs.name == "pt"
              and isinstance(t.lhs.arg.rhs, q.Num)
              and isinstance(t.rhs, q.Num) and "count" not in seen):
            out["pt_thresh"] = t.lhs.arg.rhs.value
            out["min_count"] = t.rhs.value
            seen.add("count")
        elif (is_cmp(t, "<") and isinstance(t.lhs, q.Agg)
              and t.lhs.fn == "sum" and isinstance(t.lhs.arg, q.Var)
              and t.lhs.arg.name == "pt" and isinstance(t.rhs, q.Num)
              and t.rhs.value > 0 and "sum" not in seen):
            out["sum_cap"] = t.rhs.value
            seen.add("sum")
        else:
            return None
    if not seen:
        return None
    out["terms"] = frozenset(seen)
    return out


@functools.partial(jax.jit, static_argnames=("var_idx", "calib_iters",
                                             "interpret", "use_pallas",
                                             "block_e", "block_t"))
def event_filter(scalars, pt, n_tracks, thresholds, *, var_idx: int,
                 calib_iters: int, interpret: Optional[bool] = None,
                 use_pallas: bool = True,
                 block_e: int = DEFAULT_BLOCK[0],
                 block_t: int = DEFAULT_BLOCK[1]):
    """Jitted single-query kernel dispatch over the ``(N, T)`` ``pt``
    plane (``tracks[..., 0]``): the Pallas path (``use_pallas=True``) or
    the jnp reference.  ``interpret=None`` auto-detects (compiled on
    TPU/GPU, interpreter on CPU); ``block_e`` / ``block_t`` are the
    kernel's static block shapes (default ``DEFAULT_BLOCK``)."""
    if use_pallas:
        return event_filter_pallas(
            scalars, pt, n_tracks, thresholds, var_idx=var_idx,
            calib_iters=calib_iters, interpret=interpret,
            block_e=block_e, block_t=block_t)
    return event_filter_ref(
        scalars, pt[..., None], n_tracks, var_idx=var_idx,
        scalar_thresh=thresholds[0], pt_thresh=thresholds[1],
        min_count=thresholds[2], sum_cap=thresholds[3],
        calib_iters=calib_iters)


def filter_and_summarize(expr: str, schema, batch, *, calib_iters: int = 0,
                         interpret: Optional[bool] = None):
    """(mask, var) for an arbitrary expression; Pallas path when canonical.

    NOTE: when the kernel handles calibration the caller must pass the RAW
    batch (core.jse passes calib_iters through here instead of
    pre-calibrating)."""
    params = match_canonical(expr, schema)
    if params is None:
        pred = q.compile_query(expr, schema)
        b = batch
        if calib_iters:
            b = dict(b, tracks=q.calibrate(b, calib_iters))
        return pred(b), b["scalars"][:, 0]
    thresholds = jnp.array([params["scalar_thresh"], params["pt_thresh"],
                            params["min_count"], params["sum_cap"]],
                           jnp.float32)
    return event_filter(
        batch["scalars"], batch["tracks"][..., 0], batch["n_tracks"],
        thresholds,
        var_idx=params["var_idx"], calib_iters=calib_iters,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("var_idx", "calib_iters",
                                             "interpret", "use_pallas",
                                             "block_e", "block_t"))
def event_filter_batch(scalars, pt, n_tracks, thresholds, *,
                       var_idx: Tuple[int, ...], calib_iters: int,
                       interpret: Optional[bool] = None,
                       use_pallas: bool = True,
                       block_e: int = DEFAULT_BLOCK[0],
                       block_t: int = DEFAULT_BLOCK[1]):
    """Jitted K-query kernel dispatch over the ``pt`` plane (see
    :func:`event_filter` for the flag semantics; thresholds are the
    ``(4, K)`` layout from :func:`batch_kernel_params`)."""
    if use_pallas:
        return event_filter_batch_pallas(
            scalars, pt, n_tracks, thresholds, var_idx=var_idx,
            calib_iters=calib_iters, interpret=interpret,
            block_e=block_e, block_t=block_t)
    return event_filter_batch_ref(
        scalars, pt[..., None], n_tracks, thresholds, var_idx=var_idx,
        calib_iters=calib_iters)


@functools.lru_cache(maxsize=64)
def sharded_event_filter_batch(mesh, *, var_idx: Tuple[int, ...],
                               calib_iters: int, interpret: Optional[bool],
                               block_e: int = DEFAULT_BLOCK[0],
                               block_t: int = DEFAULT_BLOCK[1]):
    """The jitted ``shard_map`` kernel call over a 1-D ``"scan"`` mesh:
    stacked ``(D, n, ...)`` chunk slabs (``scalars``, the ``pt`` plane,
    ``n_tracks``) are sharded over the leading axis
    (each device owns one sub-chunk and runs the batched kernel on it),
    thresholds replicated, ``(D, n, K)`` masks and ``(D, n)`` vars
    sharded back.  Cached per mesh and static kernel parameters."""
    from jax.sharding import PartitionSpec as P

    def body(sc, pt, ntr, thr):
        mask, var = event_filter_batch_pallas(
            sc[0], pt[0], ntr[0], thr, var_idx=var_idx,
            calib_iters=calib_iters, interpret=interpret,
            block_e=block_e, block_t=block_t)
        return mask[None], var[None]

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("scan"), P("scan"), P("scan"), P(None, None)),
        out_specs=(P("scan"), P("scan")), check_vma=False))


def filter_and_summarize_batch(exprs, schema, batch, *, calib_iters: int = 0,
                               interpret: Optional[bool] = None):
    """K-query shared scan: (masks (K, N), var (N,)).

    The fused batched kernel runs when EVERY expression matches the
    canonical hot family; a single non-canonical straggler drops the whole
    window to the stacked-predicate jnp path (still one sweep, one shared
    calibration — just without the kernel's track-streaming fusion)."""
    params = [match_canonical(e, schema) for e in exprs]
    if any(p is None for p in params):
        bpred = q.compile_query_batch(exprs, schema)
        b = batch
        if calib_iters:
            b = dict(b, tracks=q.calibrate(b, calib_iters))
        return bpred(b), b["scalars"][:, 0]
    thresholds, var_idx = batch_kernel_params(params)
    mask, var = event_filter_batch(
        batch["scalars"], batch["tracks"][..., 0], batch["n_tracks"],
        thresholds, var_idx=var_idx, calib_iters=calib_iters,
        interpret=interpret)
    return mask.T, var
