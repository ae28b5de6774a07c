"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles,
executed in Pallas interpret mode (the kernel bodies run in Python on CPU;
the BlockSpecs/grids are the TPU-target artifacts)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.event_filter.kernel import (event_filter_batch_pallas,
                                               event_filter_pallas)
from repro.kernels.event_filter.ref import (event_filter_batch_ref,
                                            event_filter_ref)
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.mlstm_scan.kernel import mlstm_pallas
from repro.kernels.mlstm_scan.ref import mlstm_ref
from repro.kernels.rglru_scan.kernel import rglru_scan_pallas
from repro.kernels.rglru_scan.ref import rglru_scan_ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-4, atol=2e-4)


# ------------------------------ flash attention -------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,sk,h,kh,d,bq,bk", [
    (1, 64, 64, 4, 4, 32, 16, 16),     # MHA square
    (2, 128, 128, 8, 2, 64, 32, 64),   # GQA 4:1
    (1, 96, 96, 4, 1, 32, 32, 32),     # MQA, non-pow2 seq
    (2, 32, 128, 4, 2, 16, 16, 32),    # cross Sq < Sk (decode-ish)
])
def test_flash_attention_sweep(b, sq, sk, h, kh, d, bq, bk, dtype):
    q = jnp.asarray(RNG.normal(size=(b, sq, h, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, sk, kh, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, sk, kh, d)), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [16, 40])
def test_flash_attention_window(window):
    q = jnp.asarray(RNG.normal(size=(1, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 128, 2, 32)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=32, block_k=32)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_softcap():
    q = jnp.asarray(RNG.normal(size=(2, 64, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 64, 4, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 64, 4, 32)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, logit_cap=30.0,
                          block_q=16, block_k=16)
    ref = flash_attention_ref(q, k, v, causal=True, logit_cap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------ event filter ----------------------------- #
@pytest.mark.parametrize("n,t,v,be,bt", [
    (128, 64, 7, 64, 32),
    (300, 70, 5, 128, 16),   # partial blocks both axes
    (64, 16, 3, 64, 16),     # single block
])
@pytest.mark.parametrize("calib_iters", [0, 3])
def test_event_filter_sweep(n, t, v, be, bt, calib_iters):
    scalars = jnp.asarray(np.abs(RNG.normal(size=(n, 8)) * 50), jnp.float32)
    tracks = jnp.asarray(RNG.normal(size=(n, t, v)), jnp.float32)
    tracks = tracks.at[:, :, 0].set(
        jnp.asarray(RNG.exponential(size=(n, t)) * 10))
    n_tracks = jnp.asarray(RNG.integers(1, t + 1, size=(n,)), jnp.int32)
    th = jnp.array([40.0, 15.0, 2.0, 800.0], jnp.float32)
    mask, var = event_filter_pallas(scalars, tracks[..., 0], n_tracks, th,
                                    var_idx=0, calib_iters=calib_iters,
                                    block_e=be, block_t=bt)
    mask_r, var_r = event_filter_ref(
        scalars, tracks, n_tracks, var_idx=0, scalar_thresh=40.0,
        pt_thresh=15.0, min_count=2.0, sum_cap=800.0,
        calib_iters=calib_iters)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_r))
    np.testing.assert_allclose(np.asarray(var), np.asarray(var_r))


def test_event_filter_no_sum_cap():
    n, t, v = 64, 32, 4
    scalars = jnp.asarray(np.abs(RNG.normal(size=(n, 8)) * 50), jnp.float32)
    tracks = jnp.asarray(RNG.normal(size=(n, t, v)), jnp.float32)
    n_tracks = jnp.asarray(RNG.integers(1, t + 1, size=(n,)), jnp.int32)
    th = jnp.array([40.0, 0.0, 1.0, -1.0], jnp.float32)  # cap disabled
    mask, _ = event_filter_pallas(scalars, tracks[..., 0], n_tracks, th,
                                  var_idx=0, calib_iters=0, block_e=32,
                                  block_t=16)
    mask_r, _ = event_filter_ref(scalars, tracks, n_tracks, var_idx=0,
                                 scalar_thresh=40.0, pt_thresh=0.0,
                                 min_count=1.0, sum_cap=-1.0, calib_iters=0)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_r))


def _ef_operands(n=64, t=32, v=4, s=8):
    scalars = jnp.asarray(np.abs(RNG.normal(size=(n, s)) * 50), jnp.float32)
    tracks = jnp.asarray(RNG.normal(size=(n, t, v)), jnp.float32)
    n_tracks = jnp.asarray(RNG.integers(1, t + 1, size=(n,)), jnp.int32)
    return scalars, tracks, n_tracks


@pytest.mark.parametrize("n,t,be,bt", [
    (64, 4096, 8, 4096),     # the paper's tracks, the default block
    (100, 300, 16, 128),     # partial event block, T not a lane multiple
    (13, 70, 8, 4096),       # partial event block, T below one lane tile
    (24, 256, 24, 128),      # whole blocks on both axes
])
@pytest.mark.parametrize("k", [1, 64])
@pytest.mark.parametrize("calib_iters", [0, 4])
def test_event_filter_batch_pt_plane_vs_ref(n, t, be, bt, k, calib_iters):
    """The batched kernel over the ``pt`` plane against the oracle over
    the full tracks: masks exactly equal, for K = 1 and K = 64 queries
    (scalar columns, thresholds and caps drawn per query), with rows
    whose ``n_tracks`` is every track."""
    scalars, tracks, n_tracks = _ef_operands(n=n, t=t, v=3)
    tracks = tracks.at[:, :, 0].set(
        jnp.asarray(RNG.exponential(size=(n, t)) * 10))
    n_tracks = n_tracks.at[0].set(t).at[n - 1].set(t)
    thr = jnp.asarray(np.stack([
        RNG.uniform(20, 60, k), RNG.uniform(5, 25, k),
        RNG.integers(0, 6, k),
        np.where(RNG.random(k) < 0.5, -1.0,
                 RNG.uniform(5 * t, 15 * t, k))]), jnp.float32)
    var_idx = tuple(int(i) for i in RNG.integers(0, 8, k))
    mask, var = event_filter_batch_pallas(
        scalars, tracks[..., 0], n_tracks, thr, var_idx=var_idx,
        calib_iters=calib_iters, block_e=be, block_t=bt)
    mask_r, var_r = event_filter_batch_ref(
        scalars, tracks, n_tracks, thr, var_idx=var_idx,
        calib_iters=calib_iters)
    assert mask.shape == (n, k)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_r))
    np.testing.assert_array_equal(np.asarray(var), np.asarray(var_r))


def test_event_filter_rejects_zero_sized_inputs():
    """Empty operands must fail with a clear ValueError at validation,
    not a Pallas trace error from a zero-width grid."""
    scalars, tracks, n_tracks = _ef_operands()
    pt = tracks[..., 0]
    th = jnp.array([[40.0], [15.0], [2.0], [-1.0]], jnp.float32)
    with pytest.raises(ValueError, match="zero-width grid"):
        event_filter_pallas(scalars[:0], pt[:0], n_tracks[:0],
                            jnp.array([40.0, 15.0, 2.0, -1.0]),
                            var_idx=0, calib_iters=0)
    with pytest.raises(ValueError, match="zero-width grid"):
        event_filter_batch_pallas(scalars, pt[:, :0], n_tracks, th,
                                  var_idx=(0,), calib_iters=0)
    with pytest.raises(ValueError, match="thresholds"):
        event_filter_batch_pallas(scalars, pt, n_tracks, th[:, :0],
                                  var_idx=(), calib_iters=0)


def test_event_filter_validates_shapes_and_blocks():
    scalars, tracks, n_tracks = _ef_operands()
    pt = tracks[..., 0]
    th1 = jnp.array([40.0, 15.0, 2.0, -1.0], jnp.float32)
    with pytest.raises(ValueError, match="event axis"):
        event_filter_pallas(scalars[:32], pt, n_tracks, th1,
                            var_idx=0, calib_iters=0)
    with pytest.raises(ValueError, match="block"):
        event_filter_pallas(scalars, pt, n_tracks, th1,
                            var_idx=0, calib_iters=0, block_e=0)
    with pytest.raises(ValueError, match="thresholds"):
        event_filter_batch_pallas(scalars, pt, n_tracks, th1,
                                  var_idx=(0,), calib_iters=0)
    with pytest.raises(ValueError, match=r"pt \(N,T\)"):
        event_filter_pallas(scalars, tracks, n_tracks, th1,
                            var_idx=0, calib_iters=0)


def test_event_filter_tail_masking_vs_padded_duplicate():
    """The tail tile is masked explicitly: appending garbage rows past
    the true event count must not change the valid rows' outputs."""
    n, t = 100, 70    # neither a multiple of its block
    scalars, tracks, n_tracks = _ef_operands(n=n, t=t)
    th = jnp.array([40.0, 15.0, 2.0, 800.0], jnp.float32)
    mask, var = event_filter_pallas(scalars, tracks[..., 0], n_tracks, th,
                                    var_idx=0, calib_iters=2,
                                    block_e=64, block_t=32)
    mask_r, var_r = event_filter_ref(
        scalars, tracks, n_tracks, var_idx=0, scalar_thresh=40.0,
        pt_thresh=15.0, min_count=2.0, sum_cap=800.0, calib_iters=2)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_r))
    np.testing.assert_allclose(np.asarray(var), np.asarray(var_r))


def test_default_interpret_env_override(monkeypatch):
    import repro.kernels as K
    monkeypatch.setenv(K.INTERPRET_ENV, "interpret")
    assert K.default_interpret() is True
    assert K.resolve_interpret(None) is True
    monkeypatch.setenv(K.INTERPRET_ENV, "compiled")
    assert K.default_interpret() is False
    assert K.resolve_interpret(None) is False
    # explicit flags always win over the environment
    assert K.resolve_interpret(True) is True
    monkeypatch.setenv(K.INTERPRET_ENV, "auto")
    # auto = backend probe (CPU test runners -> interpreter)
    assert K.default_interpret() == (jax.default_backend()
                                     not in K.COMPILED_BACKENDS)
    monkeypatch.setenv(K.INTERPRET_ENV, "bogus")
    with pytest.raises(ValueError, match="REPRO_INTERPRET"):
        K.default_interpret()


# ------------------------------ rglru scan ------------------------------- #
@pytest.mark.parametrize("b,s,w,bb,bs,bw", [
    (2, 64, 32, 2, 16, 32),
    (3, 100, 48, 2, 32, 16),   # partial blocks everywhere
    (1, 256, 128, 1, 256, 128),  # single chunk
])
def test_rglru_scan_sweep(b, s, w, bb, bs, bw):
    a = jnp.asarray(RNG.uniform(0.7, 0.999, size=(b, s, w)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(b, s, w)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(b, w)), jnp.float32)
    y, hl = rglru_scan_pallas(a, x, h0, block_b=bb, block_s=bs, block_w=bw)
    yr, hlr = rglru_scan_ref(a, x, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr),
                               rtol=1e-5, atol=1e-5)


def test_rglru_scan_no_h0():
    a = jnp.asarray(RNG.uniform(0.5, 0.99, size=(2, 37, 24)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(2, 37, 24)), jnp.float32)
    y, _ = rglru_scan_pallas(a, x, block_b=2, block_s=8, block_w=8)
    yr, _ = rglru_scan_ref(a, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)


def test_rglru_matches_model_block():
    """Kernel-backed op == the model's associative-scan block output."""
    from repro.configs.registry import reduced_config
    from repro.kernels.rglru_scan.ops import rglru_scan as krn
    from repro.models import rglru as m
    from repro.models.params import ParamTable

    cfg = reduced_config("recurrentgemma-9b")
    t = ParamTable(cfg)
    m.add_recurrent_params(t, cfg, "rec", None)
    p = t.init(jax.random.key(0))["rec"]
    x = jnp.asarray(RNG.normal(size=(2, 48, cfg.lru_width)), jnp.float32)
    y_k, h_k = krn(p, x)
    y_m, h_m = m.rglru_scan(p, x)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_m),
                               rtol=1e-4, atol=1e-4)


# ------------------------------ mlstm ------------------------------------ #
@pytest.mark.parametrize("b,s,h,d,bq,bk", [
    (1, 64, 2, 16, 16, 16),
    (2, 96, 4, 32, 32, 16),   # partial blocks
    (1, 128, 1, 64, 64, 64),
])
def test_mlstm_sweep(b, s, h, d, bq, bk):
    q = jnp.asarray(RNG.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, h, d)), jnp.float32)
    log_i = jnp.asarray(RNG.normal(size=(b, s, h)), jnp.float32)
    log_f = jnp.asarray(-np.abs(RNG.normal(size=(b, s, h))) * 0.5,
                        jnp.float32)
    out = mlstm_pallas(q, k, v, log_i, log_f, block_q=bq, block_k=bk)
    ref = mlstm_ref(q, k, v, log_i, log_f, chunk_size=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-4, atol=5e-4)


def test_mlstm_matches_recurrent_decode():
    """Chunkwise kernel at position t == sequential recurrent state decode
    (the two mLSTM formulations must agree)."""
    from repro.configs.registry import reduced_config
    from repro.models import xlstm as xm
    from repro.models.params import ParamTable
    from repro.parallel.sharding import Sharder
    from repro.launch.mesh import make_mesh_of

    cfg = reduced_config("xlstm-350m")
    t = ParamTable(cfg)
    xm._add_mlstm(t, cfg, "m", 1)
    p = jax.tree.map(lambda a: a[0], t.init(jax.random.key(0))["m"])
    mesh = make_mesh_of((1, 1), ("data", "model"))
    shd = Sharder(cfg, mesh)

    b, s = 2, 12
    x = jnp.asarray(RNG.normal(size=(b, s, cfg.d_model)) * 0.1, jnp.float32)
    y_par = xm.mlstm_block(cfg, p, x, shd)

    d, inner, h, hd, _ = xm._dims(cfg)
    state = {"C": jnp.zeros((b, h, hd, hd)), "n": jnp.zeros((b, h, hd)),
             "m": jnp.full((b, h), -1e30),
             "conv": jnp.zeros((b, cfg.conv1d_width - 1, inner))}
    outs = []
    for i in range(s):
        y_i, state = xm.mlstm_decode(cfg, p, x[:, i:i + 1], state, shd)
        outs.append(y_i)
    y_seq = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq),
                               rtol=2e-3, atol=2e-3)
