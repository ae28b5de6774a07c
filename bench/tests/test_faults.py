"""The whole run, past the look for a chip, on a tiny cell on the CPU:
sound, it comes out correct; with the served path broken underneath it
comes out not correct, once for each fault this system's cells can have.

- half of each window's chunks left out of the merge;
- an answer altered where it is produced (each chunk's selection of its
  first half of events inverted);
- a final that never comes (the window's streams never finish).

A one-chip cell has no exchange between chips to leave out, and a
service keeps no step state that a window could return unchanged: its
state is the answers, which the second fault alters.
"""
import io
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import run as run_lib

DATA = Path(__file__).resolve().parent / "data" / "bench"
ROOT = Path(__file__).resolve().parents[2]


def run_tiny(cell="tiny-closed", seconds=1.0, trace=False):
    return run_lib.run_cell(cell, 4_000_000_007, seconds, trace,
                            bench_dir=DATA, need_chip=False)


def test_sound_run_is_correct():
    out = run_tiny()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["bad_finals"]["value"] == 0
    assert set(out["metrics"]) >= {"setup_s", "ttf_p50_s", "ttf_p95_s",
                                   "queries_per_s"}
    assert list(out)[-1] == "checks"


def test_open_loop_sound_run_is_correct():
    out = run_tiny("tiny-open", seconds=2.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 12


def test_half_the_chunks_left_out(monkeypatch):
    from repro.core import merge
    whole = merge.merge_batch
    monkeypatch.setattr(merge, "merge_batch", lambda parts: whole(parts[::2]))
    out = run_tiny()
    assert not out["correct"]
    assert out["checks"]["bad_finals"]["value"] > 0


def test_an_answer_altered_where_it_is_produced(monkeypatch):
    from repro.core import merge
    honest = merge.from_mask

    def altered(mask, var, event_id):
        mask = np.array(mask, copy=True)
        half = max(1, mask.shape[0] // 2)
        mask[:half] = np.where(mask[:half] != 0, 0.0, 1.0)
        return honest(mask, var, event_id)

    monkeypatch.setattr(merge, "from_mask", altered)
    out = run_tiny()
    assert not out["correct"]
    assert out["checks"]["selection_off"]["value"] > \
        out["checks"]["selection_off"]["limit"]


def test_a_final_that_never_comes(monkeypatch):
    from repro.service import streaming
    monkeypatch.setattr(streaming.WindowStreamPublisher, "finish",
                        lambda self, merged, makespan_s: None)
    out = run_tiny()
    assert not out["correct"]
    assert out["checks"]["bad_finals"]["value"] > 0


def test_no_chip_no_result_line():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "paper-pt-cuts", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_traced_run_places_its_spans_in_the_window():
    from bench import trace as trace_lib
    seen = {}
    reduce = trace_lib.reduce_trace

    def spy(*args, **kwargs):
        seen["r"] = reduce(*args, **kwargs)
        return seen["r"]

    trace_lib.reduce_trace = spy
    try:
        out = run_tiny("tiny-open", seconds=2.0, trace=True)
    finally:
        trace_lib.reduce_trace = reduce
    assert out["correct"], out["checks"]
    assert out["device"]["window_s"] > 0
    r = seen["r"]
    steps = r.span_intervals(["step", "drain"])
    assert steps and 0.0 <= steps[0][0] < steps[-1][1] <= r.window_s + 0.1
