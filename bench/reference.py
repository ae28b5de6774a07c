"""The plain reference: what every final should say, computed apart from
the program, and the comparison that decides ``correct``.

It imports nothing of the program.  Its input is the data the benchmark
generated (the host arrays of the cell's store), brick by brick; its
semantics are each query family's own ``reference`` predicate, built from
the plain helpers here: the paper's calibration of ``pt`` (section 4.1),
the k-th largest valid ``pt`` of an event (``count(pt > B) >= C`` holds
exactly when the ``ceil(C)``-th largest valid ``pt`` is above ``B``) and
the sum of the valid ``pt``.  Each final is summarized as the program's
results are: the number of events selected and processed, the sum of
scalar column 0 over the selected events, its histogram (64 bins over
[0, 512], the last bin closed) and the first 128 selected event ids.

Float32 rounding may decide an event either way where a rounded value
lies on a cut: a calibrated ``pt`` within ``PT_TOL`` of a threshold, a
sum of up to 4096 ``pt`` within ``SUM_TOL`` of a cap (relative to the
cut, at least 1).  Each conjunct says where that is (``unsure``), and a
query's selection has three parts: the reference's own decision
(``keep``), the events selected whatever the rounding (``certain``) and
those rounding decides (``unsure``).  A final is judged against the
range these allow (``gaps``):

- ``selection_off``: events counted outside ``certain`` plus some of
  ``unsure``, summed over the count, the histogram's bins and the id
  sample.  Exact: the limit is 0.
- ``sum_off``: how far the sum of column 0 lies outside that range, over
  the store's largest |column 0| (one event's most), which is rounding
  alone in a final that selects the right events.

``dtype`` is the precision the whole reference computes in: float32, the
configuration's, for the comparison; bfloat16 for the control, whose
``keep`` stands in for the served finals and has to fail it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, NamedTuple

import numpy as np

HIST_BINS = 64
HIST_RANGE = (0.0, 512.0)
MAX_IDS = 128
#: Ids kept per part, so that a sound sample of 128 lies inside what the
#: reference knows even where unsure events come first.
KNOWN_IDS = 2 * MAX_IDS
#: Query columns are padded to a multiple of this, so a run's reference
#: compiles one program whatever its number of distinct queries.
QUERY_PAD = 128
#: Relative distance from a cut within which float32 rounding may decide
#: an event: a calibrated ``pt`` against a threshold (a few ulps after
#: four passes), and a sum of up to 4096 positive ``pt`` against a cap
#: (the bound of a sequential sum, 4096 x 2**-24).
PT_TOL = 1e-5
SUM_TOL = 2.5e-4


# ------------------------------------------------------------ helpers -- #
def calibrate_pt(pt, iters: int):
    """The paper's calibration refinement of the ``pt`` column: each pass
    scales ``pt`` by ``1 + 0.01 tanh(pt) / sqrt(1 + pt^2)``."""
    import jax
    import jax.numpy as jnp
    one = jnp.asarray(1.0, pt.dtype)
    for _ in range(iters):
        pt = pt * (one + jnp.asarray(0.01, pt.dtype) * jnp.tanh(pt)
                   * jax.lax.rsqrt(one + pt * pt))
    return pt


def valid_objects(pt, count):
    """(n, T) mask of the objects an event holds."""
    import jax.numpy as jnp
    return jnp.arange(pt.shape[1])[None, :] < count[:, None]


def kth_largest(pt, count, k):
    """(n, Q): the ``k[q]``-th largest valid ``pt`` of each event, -inf
    where the event holds fewer than ``k[q]`` objects or ``k[q] < 1``."""
    import jax.numpy as jnp
    t = pt.shape[1]
    ranked = jnp.sort(jnp.where(valid_objects(pt, count), pt, -jnp.inf),
                      axis=1)[:, ::-1]
    kth = ranked[:, jnp.clip(k - 1, 0, t - 1)]
    return jnp.where((k >= 1) & (k <= t), kth, -jnp.inf)


class Conjunct(NamedTuple):
    """(n, Q) decision of one conjunct, and where rounding could flip it."""
    holds: object
    unsure: object


class Selection(NamedTuple):
    """(n, Q) masks of a query's selection: the reference's own
    decision, the events selected whatever the rounding, and those the
    rounding decides."""
    keep: object
    certain: object
    unsure: object


def _near(value, cut, rel):
    import jax.numpy as jnp
    return jnp.abs(value - cut) <= rel * jnp.maximum(jnp.abs(cut), 1.0)


def greater(value, threshold) -> Conjunct:
    """``value > threshold`` on a stored column: exact."""
    import jax.numpy as jnp
    holds = value > threshold
    return Conjunct(holds, jnp.zeros_like(holds))


def count_at_least(pt, count, threshold, k) -> Conjunct:
    """(n, Q): ``count(pt > threshold[q]) >= k[q]``, k a whole number."""
    kth = kth_largest(pt, count, k)
    thr = threshold[None, :]
    return Conjunct((k[None, :] <= 0) | (kth > thr),
                    (k[None, :] >= 1) & _near(kth, thr, PT_TOL))


def sum_below(total, cap) -> Conjunct:
    """(n, Q): ``sum < cap[q]`` where ``cap[q] > 0``, else always true."""
    cap = cap[None, :]
    return Conjunct((cap <= 0) | (total[:, None] < cap),
                    (cap > 0) & _near(total[:, None], cap, SUM_TOL))


def all_of(*conjuncts: Conjunct) -> Selection:
    """The conjunction: certain where every conjunct holds and none is
    unsure; possible where every one holds or is unsure."""
    keep = certain = possible = True
    for c in conjuncts:
        keep = keep & c.holds
        certain = certain & c.holds & ~c.unsure
        possible = possible & (c.holds | c.unsure)
    return Selection(keep, certain, possible & ~certain)


def valid_sum(pt, count):
    """(n,) sum of each event's valid ``pt``."""
    import jax.numpy as jnp
    return jnp.sum(jnp.where(valid_objects(pt, count), pt, 0.0), axis=1)


# ---------------------------------------------------------- summaries -- #
@dataclasses.dataclass
class Part:
    """One part of a query's selection over the store: its events, the
    histogram of column 0, the range of its sum of column 0 (negative
    and positive values apart) and its first event ids."""
    n: int
    hist: np.ndarray
    sum_lo: float
    sum_hi: float
    ids: np.ndarray


@dataclasses.dataclass
class Summary:
    """What one final should say."""
    n_processed: int
    keep: Part
    certain: Part
    unsure: Part


PARTS = ("keep", "certain", "unsure")


def _summarize(mask, var):
    """Per-query selected counts, histogram and sums of the negative and
    positive values of ``var``; counts are exact (0/1 products summed in
    float32 below 2**24)."""
    import jax
    import jax.numpy as jnp
    lo, hi = HIST_RANGE
    width = (hi - lo) / HIST_BINS
    v = var.astype(jnp.float32)
    b = jnp.floor((v - lo) / width).astype(jnp.int32)
    b = jnp.where(v == hi, HIST_BINS - 1, b)
    inside = (v >= lo) & (v <= hi)
    onehot = (b[:, None] == jnp.arange(HIST_BINS)[None, :]) & inside[:, None]
    hist = jnp.dot(onehot.T.astype(jnp.bfloat16), mask.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    nsel = jnp.sum(mask.astype(jnp.int32), axis=0)
    signed = jnp.stack([jnp.minimum(var, 0), jnp.maximum(var, 0)])
    if var.dtype == jnp.float32:
        sums = jnp.dot(signed, mask.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    else:
        sums = jnp.dot(signed, mask.astype(var.dtype))
    return nsel, hist, sums.astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _block_fn(family, calib_iters: int, dtype_name: str):
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype_name)

    def fn(scalars, pt, count, params):
        cols = {"scalars": scalars.astype(dtype), "pt": pt.astype(dtype),
                "count": count}
        sel = family.reference(cols, params, calib_iters, dtype)
        return {name: (mask, *_summarize(mask, cols["scalars"][:, 0]))
                for name, mask in sel._asdict().items()}

    return jax.jit(fn)


def _pad_params(family, params: List[dict]) -> dict:
    n = -(-max(1, len(params)) // QUERY_PAD) * QUERY_PAD
    padded = list(params) + [family.NEVER] * (n - len(params))
    return family.param_arrays(padded)


def evaluate(store, family, params: List[dict], calib_iters: int,
             dtype: str = "float32") -> List[Summary]:
    """Each query's summary over the whole store, one brick at a time."""
    import jax
    import jax.numpy as jnp
    q = len(params)
    fn = _block_fn(family, calib_iters, dtype)
    parr = {k: jnp.asarray(v) for k, v in _pad_params(family, params).items()}
    acc = {name: {"n": np.zeros(q, np.int64),
                  "hist": np.zeros((HIST_BINS, q), np.int64),
                  "sums": np.zeros((2, q), np.float64),
                  "ids": [[] for _ in range(q)]} for name in PARTS}
    keep_ids = {"keep": MAX_IDS, "certain": KNOWN_IDS, "unsure": KNOWN_IDS}
    processed = 0
    for bid in sorted(store.bricks):
        b = store.bricks[bid]
        pt = np.ascontiguousarray(b["tracks"][:, :, 0])
        out = fn(jnp.asarray(b["scalars"]), jnp.asarray(pt),
                 jnp.asarray(b["n_tracks"]), parr)
        processed += b["scalars"].shape[0]
        for name, (mask, ns, h, sums) in out.items():
            a = acc[name]
            ns, h, sums = jax.device_get((ns, h, sums))
            a["n"] += ns[:q]
            a["hist"] += np.rint(h[:, :q]).astype(np.int64)
            a["sums"] += sums[:, :q].astype(np.float64)
            cap = keep_ids[name]
            need = [i for i in range(q) if len(a["ids"][i]) < cap and ns[i]]
            if need:
                m = np.asarray(mask)[:, need]
                for j, i in enumerate(need):
                    sel = b["event_id"][np.flatnonzero(m[:, j])]
                    a["ids"][i].extend(int(x)
                                       for x in sel[:cap - len(a["ids"][i])])

    def part(name, i):
        a = acc[name]
        lo, hi = a["sums"][:, i]
        if name != "unsure":
            lo = hi = lo + hi
        return Part(int(a["n"][i]), a["hist"][:, i].copy(), float(lo),
                    float(hi), np.asarray(a["ids"][i], np.int64))

    return [Summary(processed, *(part(name, i) for name in PARTS))
            for i in range(q)]


def value_scale(store) -> float:
    """The largest |scalar column 0| in the store: one event's most a
    selection change can move a sum of that column."""
    return max(float(np.max(np.abs(b["scalars"][:, 0])))
               for b in store.bricks.values())


# --------------------------------------------------------- comparison -- #
def _outside(x, lo, hi):
    return np.maximum(0, np.maximum(lo - x, x - hi))


def id_mismatches(got_ids, certain_ids, unsure_ids) -> int:
    """Ids in a final's sample that no sound final could hold, plus
    certain ids that a sound sample reaching as far would hold."""
    got = [int(x) for x in got_ids]
    have = set(got)
    horizon = min(ids[-1] if len(ids) >= KNOWN_IDS else np.inf
                  for ids in (certain_ids, unsure_ids))
    known = set(int(x) for x in certain_ids) | set(int(x) for x in unsure_ids)
    extra = sum(1 for x in have if x > horizon or x not in known)
    reach = max(have) if len(have) >= MAX_IDS else np.inf
    missing = sum(1 for x in certain_ids if x <= reach and int(x) not in have)
    return extra + missing + (len(got) - len(have))


def gaps(got_n: int, got_sum: float, got_hist, got_ids, want: Summary,
         scale: float) -> tuple:
    """``(selection_off, sum_off)`` of one final against its summary."""
    c, u = want.certain, want.unsure
    hist = np.asarray(got_hist, np.int64)
    selection = (int(_outside(int(got_n), c.n, c.n + u.n))
                 + int(_outside(hist, c.hist, c.hist + u.hist).sum())
                 + id_mismatches(got_ids, c.ids, u.ids))
    off = float(_outside(float(got_sum), c.sum_lo + u.sum_lo,
                         c.sum_hi + u.sum_hi))
    return selection, off / max(scale, 1e-30)
