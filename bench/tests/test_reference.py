"""The plain reference at a tiny size on the CPU: the served path agrees
with it, its rounding bands hold what they should, and the control (the
reference in bfloat16 in the program's place) fails the comparison."""
from pathlib import Path

import numpy as np
import pytest

from bench import loader, reference, traffic
from bench import run as run_lib
from bench import store as store_lib

DATA = Path(__file__).resolve().parent / "data" / "bench"


def test_calibration_and_counts_by_hand():
    import jax.numpy as jnp
    pt = jnp.asarray([[30.0, 5.0, 20.0, 99.0]], jnp.float32)
    count = jnp.asarray([3], jnp.int32)
    once = reference.calibrate_pt(pt, 1)
    want = 30.0 * (1 + 0.01 * np.tanh(30.0) / np.sqrt(1 + 900.0))
    assert float(once[0, 0]) == pytest.approx(want, rel=1e-6)
    # the 4th track is past the count: never counted, never summed
    got = reference.count_at_least(pt, count, jnp.asarray([10.0, 10.0, 1.0]),
                                   jnp.asarray([2, 3, 3]))
    assert got.holds.tolist() == [[True, False, True]]
    assert not got.unsure.any()
    assert float(reference.valid_sum(pt, count)[0]) == 55.0


def test_a_cut_within_rounding_is_unsure():
    import jax.numpy as jnp
    pt = jnp.asarray([[20.0, 10.0], [20.0, 10.0]], jnp.float32)
    count = jnp.asarray([2, 2], jnp.int32)
    # the 2nd largest pt (10) lies one ulp below the first threshold
    thr = jnp.asarray([np.nextafter(np.float32(10.0), np.float32(11.0)),
                       9.0], jnp.float32)
    c = reference.count_at_least(pt, count, thr, jnp.asarray([2, 2]))
    assert c.holds.tolist() == [[False, True], [False, True]]
    assert c.unsure.tolist() == [[True, False], [True, False]]
    total = reference.valid_sum(pt, count)
    s = reference.sum_below(total, jnp.asarray([0.0, 30.001]))
    assert s.holds.tolist() == [[True, True]] * 2
    assert s.unsure.tolist() == [[False, True]] * 2
    sel = reference.all_of(c, s)
    assert sel.keep.tolist() == [[False, True]] * 2
    assert not sel.certain.any()
    assert sel.unsure.all()
    far = reference.sum_below(total, jnp.asarray([29.0, 31.0]))
    assert far.holds.tolist() == [[False, True]] * 2
    assert not far.unsure.any()


def test_gaps_judge_a_final_against_its_bands():
    n = reference.MAX_IDS
    ids = np.arange(0, 2 * n, 2)          # 128 certain ids
    extra = np.arange(1, 41, 2)           # 20 unsure ids among them
    hist = np.zeros(reference.HIST_BINS, np.int64)
    want = reference.Summary(
        1000,
        reference.Part(300, hist + 0, 90.0, 90.0, ids[:n]),
        reference.Part(300, hist + 0, 90.0, 90.0,
                       np.arange(0, 4 * n, 2)[:reference.KNOWN_IDS]),
        reference.Part(20, hist + 0, 0.0, 6.0, extra))
    ok = np.sort(np.concatenate([ids[:n - 20], extra]))
    assert reference.gaps(320, 96.0, hist, ok, want, 3.0) == (0, 0.0)
    assert reference.gaps(300, 90.0, hist, ids[:n], want, 3.0) == (0, 0.0)
    # one event too many, a certain id missing, a sum 3 above the range
    sel, off = reference.gaps(321, 99.0, hist, ids[1:n + 1] + 0, want, 3.0)
    assert sel >= 2 and off == pytest.approx(1.0)


def test_served_path_agrees():
    cfg = loader.config("tiny-paper", DATA)
    workload = loader.workload("tiny-open", DATA)
    family = loader.family("tiny_cuts", DATA)
    from repro.service import QueryService
    store = store_lib.build_store(cfg, 2**40 + 3)
    qs = traffic.draw_queries(workload, family, np.random.SeedSequence(5), 12)
    svc = QueryService(store, backend="spmd",
                       backend_kwargs=cfg["backend_kwargs"])
    tids = [svc.submit(q.expr, tenant=q.tenant,
                       calib_iters=cfg["calib_iters"], stream=True)
            for q in qs]
    svc.step()
    want = reference.evaluate(store, family, [q.params for q in qs],
                              cfg["calib_iters"])
    scale = reference.value_scale(store)
    assert any(w.keep.n for w in want)
    for tid, w in zip(tids, want):
        got = svc.result(tid).result
        assert got.n_processed == store.n_events
        assert got.n_selected == w.keep.n
        assert np.array_equal(got.hist, w.keep.hist)
        assert np.array_equal(got.selected_ids, w.keep.ids)
        sel, off = reference.gaps(got.n_selected, got.sum_var, got.hist,
                                  got.selected_ids, w, scale)
        assert sel == 0 and off < 1e-3


def test_lower_precision_disagrees():
    """The control at a size a test run holds: a whole run of a tiny cell
    of 2048 events, whose finals the bfloat16 reference replaces."""
    out = run_lib.run_cell("tiny-control", 4_000_000_011, 2.0, False,
                           bench_dir=DATA, need_chip=False, control=True)
    assert out["checks"]["bad_finals"]["value"] == 0
    assert not out["correct"], out["checks"]
    assert out["checks"]["selection_off"]["value"] > 0
