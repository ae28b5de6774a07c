"""``bench/trace.py`` against a small trace recorded on a TPU v5e
(``record_trace.py``): its numbers against a brute-force reading of the
same file, and its spans and gaps against what the recorder did."""
from pathlib import Path

import pytest

from bench import trace as trace_lib

TRACE = Path(__file__).resolve().parent / "data" / "tpu_small.xplane.pb"
SPANS = ("submit", "step", "await_arrival", "drain")


@pytest.fixture(scope="module")
def reduction():
    return trace_lib.reduce_trace(str(TRACE), SPANS)


@pytest.fixture(scope="module")
def device_ops():
    import jax
    data = jax.profiler.ProfileData.from_file(str(TRACE))
    out = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out += [(e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
    return out


def test_merge_and_overlap():
    assert trace_lib.merge_intervals([(3, 4), (0, 1), (0.5, 2)]) == [
        (0, 2), (3, 4)]
    assert trace_lib.overlap([(0, 2), (3, 4)], [(1, 3.5)]) == 1.5
    assert trace_lib.op_label("jit_f(123)", "%copy.4 = f32[2] copy(x)") \
        == "f/copy.4"


def test_busy_is_the_union_of_device_ops(reduction, device_ops):
    assert len(reduction.busy) == 1
    # brute force: sweep the sorted endpoints, counting open operations
    events = sorted([(s, 1) for s, _ in device_ops]
                    + [(e, -1) for _, e in device_ops])
    depth, last, busy = 0, None, 0.0
    for t, d in events:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert reduction.busy_s == pytest.approx(busy, rel=1e-9)
    assert sum(reduction.op_seconds.values()) == pytest.approx(
        sum(e - s for s, e in device_ops), rel=1e-9)


def test_ops_are_named_by_program(reduction):
    names = [n for n, _ in reduction.top_ops(10)]
    assert any(n.startswith("_lambda/") for n in names)
    assert all(s > 0 for _, s in reduction.top_ops(10))


def test_spans_and_gaps(reduction):
    kinds = [n for n, _, _ in reduction.spans]
    assert kinds.count("step") == 1
    assert "await_arrival" in kinds and "submit" in kinds
    step = reduction.span_intervals(["step"])
    assert 0 < reduction.busy_within(["step"]) <= reduction.busy_s
    assert reduction.busy_within(["await_arrival"]) < 1e-3
    gaps = reduction.idle_gaps(10)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    # the recorder slept 20 ms inside await_arrival with the device idle
    slept = [g for g in gaps if g[0] == "await_arrival"]
    assert slept and slept[0][1] >= 0.015
    assert reduction.window_s >= step[-1][1] - step[0][0]


def test_given_spans_are_placed_on_the_trace_clock(reduction):
    """Spans timed from a wall-clock instant land where the trace's own
    annotations of the same blocks lie."""
    import jax
    data = jax.profiler.ProfileData.from_file(str(TRACE))
    env = [dict(p.stats) for p in data.planes
           if p.name == "Task Environment"][0]
    start = int(env["profile_start_time"])
    origin = start + 250_000_000        # 0.25 s into the trace
    own = reduction.spans
    given = [(n, s - 0.25, e - 0.25) for n, s, e in own]
    shifted = trace_lib.reduce_trace(str(TRACE), SPANS, spans=given,
                                     origin_ns=origin)
    assert [n for n, _, _ in shifted.spans] == [n for n, _, _ in own]
    for (_, s0, e0), (_, s1, e1) in zip(own, shifted.spans):
        assert s1 == pytest.approx(s0, abs=1e-9)
        assert e1 == pytest.approx(e0, abs=1e-9)
    assert shifted.busy_s == reduction.busy_s


def test_harness_spans_match_the_profilers_clock(tmp_path):
    """On the CPU, a span the harness's client records and a profiler
    annotation around the same block start within a millisecond."""
    import time

    import jax
    import jax.numpy as jnp

    from bench import drive
    f = jax.jit(lambda x: jnp.tanh(x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    client = drive.Client(None, 0, time.perf_counter())
    time.sleep(0.02)
    with client.span("step"):
        with jax.profiler.TraceAnnotation("submit"):
            f(x).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    own = trace_lib.reduce_trace(str(tmp_path), ["submit"])
    placed = trace_lib.reduce_trace(str(tmp_path), SPANS,
                                    spans=client.spans,
                                    origin_ns=client.origin_ns)
    (_, s0, e0), = own.spans
    (_, s1, e1), = placed.spans
    assert s1 == pytest.approx(s0, abs=1e-3)
    assert e1 == pytest.approx(e0, abs=1e-3)
