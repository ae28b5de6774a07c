"""The metric arithmetic: end-to-end metrics from the client's records, and
the work a window needs, which the per-layer readers divide by time.

Percentiles are numpy's default (linear) over every ticket of the window.
A ticket that never got a final counts, in the latencies, as having waited
until the run's last final: it missed any limit a user would set.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Callable, Dict, Optional

import numpy as np

from bench import drive

PEAKS = Path(__file__).resolve().parent / "peaks.json"

#: Floating-point operations of one calibration pass on one ``pt`` value:
#: pt*pt, +1, rsqrt, tanh, *0.01, *, +1, * (a transcendental counts once).
CALIB_FLOPS = 8


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def _latencies(w: drive.WindowRecord, first: bool) -> np.ndarray:
    out = []
    for t in w.tickets:
        got = t.t_first if first else t.t_final
        out.append((got if got is not None else w.t_end) - t.t_start)
    return np.asarray(out, np.float64)


def ttfp_p95_s(w, setup_s):
    return float(np.percentile(_latencies(w, True), 95))


def ttf_p50_s(w, setup_s):
    return float(np.percentile(_latencies(w, False), 50))


def ttf_p95_s(w, setup_s):
    return float(np.percentile(_latencies(w, False), 95))


def queries_per_s(w, setup_s):
    """Finals over the window; a closed loop's window runs until the
    final of the last query started inside ``seconds``, an open loop's is
    ``seconds`` and counts the finals inside it."""
    if w.loop == "closed":
        return len(w.finals()) / w.t_end
    return sum(1 for t in w.finals() if t.t_final <= w.seconds) / w.seconds


def setup(w, setup_s):
    return setup_s


END_TO_END: Dict[str, Callable] = {
    "setup_s": setup,
    "ttfp_p95_s": ttfp_p95_s,
    "ttf_p50_s": ttf_p50_s,
    "ttf_p95_s": ttf_p95_s,
    "queries_per_s": queries_per_s,
}


@dataclasses.dataclass
class StoreShape:
    """What the work model needs of the store."""
    n_events: int
    n_objects: int          # valid objects (tracks, jets) over all events

    @classmethod
    def of(cls, store) -> "StoreShape":
        return cls(store.n_events,
                   int(sum(int(np.sum(b["n_tracks"], dtype=np.int64))
                           for b in store.bricks.values())))


def step_work(step: drive.StepRecord, w: drive.WindowRecord, family,
              shape: StoreShape, calib_iters: int) -> tuple:
    """``(bytes, flops)`` the filter semantics need for one window, whatever
    implements them: per event its valid ``pt`` values, its object count,
    its id and the scalar columns the window's queries read (column 0
    among them); per valid ``pt`` the calibration passes, one comparison
    per query and one addition to its sum."""
    if step.events_scanned <= 0:
        return 0.0, 0.0
    per = {t.ticket: t for t in w.tickets}
    cols = {0}
    for tid in step.tickets:
        if tid in per:
            cols |= family.scalar_columns(per[tid].query.params)
    scans = step.events_scanned / shape.n_events
    objects = shape.n_objects * scans
    nbytes = 4.0 * objects + step.events_scanned * 4.0 * (2 + len(cols))
    flops = objects * (CALIB_FLOPS * calib_iters + step.jobs_run + 1)
    return nbytes, flops


@dataclasses.dataclass
class RunView:
    """What a per-layer reader may read of one run."""
    window: drive.WindowRecord
    trace: Optional[object]         # bench.trace.Reduction, or None
    family: object
    shape: StoreShape
    calib_iters: int
    peaks: Optional[dict]           # None off the chip
