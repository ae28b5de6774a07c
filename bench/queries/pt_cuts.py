"""Query family ``pt_cuts``: new cuts from independent physicists.

    e_total > A && count(pt > B) >= C [&& sum(pt) < D]

The shape of the paper's hot query, which the program serves through its
fused calibrate-and-filter kernel.  Every query draws A, B, C and, for
half of them, D.  The ranges keep the selected share of a
``geps-paper`` store away from 0 and 1: ``e_total`` is |N(0,1)| x 50,
``pt`` is Exp(1) x 10 and an event holds 1..4096 tracks, so
``count(pt > B) >= C`` selects the events with more than about
``C e^(B/10)`` tracks and ``sum(pt) < D`` those with fewer than ``D / 10``.
C is drawn through that track number ``n_lo`` and D through ``n_hi >
n_lo``, so the two track cuts never exclude each other.  The scalar is
always ``e_total`` (see PERF.md, Cells: the program compiles a kernel per
tuple of scalar columns).
"""
import math

import numpy as np

#: A query that selects nothing: pads the reference's query columns.
NEVER = {"A": math.inf, "B": 0.0, "C": 1, "D": 0.0}


def draw(rng: np.random.Generator, prev=None) -> dict:
    b = float(f"{rng.uniform(5.0, 60.0):.3f}")
    n_lo = rng.uniform(200.0, 2600.0)
    p = {"A": float(f"{rng.uniform(5.0, 80.0):.3f}"), "B": b,
         "C": max(1, int(round(n_lo * math.exp(-b / 10.0)))), "D": 0.0}
    if rng.random() < 0.5:
        p["D"] = float(f"{10.0 * rng.uniform(n_lo + 600.0, 4096.0):.1f}")
    return p


def expression(p: dict) -> str:
    s = f"e_total > {p['A']:.3f} && count(pt > {p['B']:.3f}) >= {p['C']}"
    if p["D"] > 0:
        s += f" && sum(pt) < {p['D']:.1f}"
    return s


def scalar_columns(p: dict) -> set:
    """Scalar columns a query reads (column 0 is the summary variable)."""
    return {0}


def param_arrays(ps: list) -> dict:
    return {"A": np.array([p["A"] for p in ps], np.float32),
            "B": np.array([p["B"] for p in ps], np.float32),
            "C": np.array([p["C"] for p in ps], np.int32),
            "D": np.array([p["D"] for p in ps], np.float32)}


def reference(cols: dict, P: dict, calib_iters: int, dtype):
    """The selection of each query (``bench.reference.Selection`` of
    (n, Q) masks), computed in ``dtype``."""
    from bench import reference as ref
    pt = ref.calibrate_pt(cols["pt"], calib_iters)
    count = cols["count"]
    return ref.all_of(
        ref.greater(cols["scalars"][:, 0:1], P["A"].astype(dtype)[None, :]),
        ref.count_at_least(pt, count, P["B"].astype(dtype), P["C"]),
        ref.sum_below(ref.valid_sum(pt, count), P["D"].astype(dtype)))
