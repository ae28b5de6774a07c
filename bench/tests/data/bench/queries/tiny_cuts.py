"""``pt_cuts`` at the tiny configuration's 32 tracks: its expressions and
reference, with the ranges of its draws scaled to the smaller events."""
import math

from bench import loader

_base = loader.family("pt_cuts")
NEVER = _base.NEVER
expression = _base.expression
scalar_columns = _base.scalar_columns
param_arrays = _base.param_arrays
reference = _base.reference


def draw(rng, prev=None):
    b = float(f"{rng.uniform(2.0, 20.0):.3f}")
    n_lo = rng.uniform(2.0, 20.0)
    p = {"A": float(f"{rng.uniform(5.0, 80.0):.3f}"), "B": b,
         "C": max(1, int(round(n_lo * math.exp(-b / 10.0)))), "D": 0.0}
    if rng.random() < 0.5:
        p["D"] = float(f"{10.0 * rng.uniform(n_lo + 4.0, 32.0):.1f}")
    return p
