"""Ahead-of-time compiles of the scan kernels for a described TPU v5e.

The TPU compiler is installed with jax, and it compiles for a chip that is
described rather than attached, so what Mosaic would refuse on the chip
(layouts, block shapes, scoped VMEM) fails here at no chip time.  The
topology, and everything built from it, lives in module-scoped fixtures:
nothing touches the TPU runtime while the module is imported, and where
no topology can be described the tests skip.  Nothing here runs, so these
tests say nothing about results or times.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.geps_events import CONFIG, reduced
from repro.kernels.event_filter import ops as ef_ops
from repro.kernels.event_filter.kernel import (DEFAULT_BLOCK,
                                               event_filter_batch_pallas,
                                               event_filter_pallas)

K = 3
CALIB = CONFIG.calib_iters
#: (events per chunk, scalars, tracks, track vars) at both widths
WIDTHS = {
    "paper": (64, CONFIG.n_scalars, CONFIG.max_tracks, CONFIG.track_vars),
    "reduced": (64, reduced().n_scalars, reduced().max_tracks,
                reduced().track_vars),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "can't"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def scan_mesh(topo):
    return Mesh(np.asarray(topo.devices[:4]), ("scan",))


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _operands(width, sharding, lead=()):
    """The kernel's operands: scalars, the ``pt`` plane, n_tracks."""
    n, s, t, _ = WIDTHS[width]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(lead + shape, dt,
                                                 sharding=sharding)
    return (sds((n, s), jnp.float32), sds((n, t), jnp.float32),
            sds((n,), jnp.int32))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_event_filter_batch_compiles_for_v5e(one_chip, width):
    """The kernel over the ``pt`` plane, at a few queries and at the
    widest window (64, the service's ``max_batch``)."""
    for k in (K, 64):
        thr = jax.ShapeDtypeStruct((4, k), jnp.float32, sharding=one_chip)
        fn = lambda sc, pt, ntr, th: event_filter_batch_pallas(
            sc, pt, ntr, th, var_idx=tuple(range(k)), calib_iters=CALIB,
            interpret=False)
        _assert_kernel(jax.jit(fn).lower(*_operands(width, one_chip),
                                         thr).compile())


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_event_filter_single_compiles_for_v5e(one_chip, width):
    thr = jax.ShapeDtypeStruct((4,), jnp.float32, sharding=one_chip)
    fn = lambda sc, pt, ntr, th: event_filter_pallas(
        sc, pt, ntr, th, var_idx=0, calib_iters=CALIB, interpret=False)
    _assert_kernel(jax.jit(fn).lower(*_operands(width, one_chip),
                                     thr).compile())


def test_sharded_kernel_call_compiles_on_4_devices(scan_mesh):
    """The SPMD backend's mesh path: one shard_map kernel call over a
    4-device scan mesh, each device owning one paper-width chunk."""
    per_device = NamedSharding(scan_mesh, P("scan"))
    thr = jax.ShapeDtypeStruct((4, K), jnp.float32,
                               sharding=NamedSharding(scan_mesh, P()))
    fn = ef_ops.sharded_event_filter_batch(
        scan_mesh, var_idx=tuple(range(K)), calib_iters=CALIB,
        interpret=False)
    compiled = fn.lower(*_operands("paper", per_device, lead=(4,)),
                        thr).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("k", [1, 2, 3, 5, 8, 13, 21, 34, 55, 64],
                         ids=lambda k: f"K={k}")
def test_autotune_candidates_compile_at_paper_width(one_chip, k):
    """The kernel compiles at the paper's width at its default block
    shape for each query width K a window may bring, up to the
    service's ``max_batch`` of 64."""
    thr = jax.ShapeDtypeStruct((4, k), jnp.float32, sharding=one_chip)
    fn = lambda sc, pt, ntr, th: event_filter_batch_pallas(
        sc, pt, ntr, th, var_idx=tuple(range(k)), calib_iters=CALIB,
        block_e=DEFAULT_BLOCK[0], block_t=DEFAULT_BLOCK[1],
        interpret=False)
    _assert_kernel(jax.jit(fn).lower(*_operands("paper", one_chip),
                                     thr).compile())


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_resident_chunk_slice_compiles_for_v5e(one_chip, width):
    """A mixed window's per-chunk full-track take: one brick's image
    sliced at a traced start and reshaped to ``(size, T, V)``."""
    from repro.core.backend import _take_chunk, _track_rows
    from repro.core.events import EventSchema
    n, s, t, v = WIDTHS[width]
    brick = 4 * n
    rows, lanes = _track_rows(EventSchema(s, t, v))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    compiled = _take_chunk.lower(
        sds((brick * rows, lanes), jnp.float32), sds((), jnp.int32), size=n,
        event_shape=(t, v)).compile()
    assert "dynamic-slice" in compiled.as_text()


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_resident_plane_slice_compiles_for_v5e(one_chip, width):
    """A kernel chunk's per-chunk program: one brick's ``scalars``,
    ``pt`` plane and ``n_tracks`` sliced at a traced start, with no
    relayout of the chunk."""
    from repro.core.backend import _take_plane
    n, s, t, _ = WIDTHS[width]
    brick = 4 * n
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    compiled = _take_plane.lower(
        sds((brick, s), jnp.float32), sds((brick, t), jnp.float32),
        sds((brick,), jnp.int32), sds((), jnp.int32), size=n).compile()
    text = compiled.as_text()
    assert "dynamic-slice" in text
    relayouts = [line for line in text.splitlines()
                 if f"= f32[{n},{t}]" in line
                 and (" copy(" in line or " reshape(" in line)]
    assert relayouts == []


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_resident_plane_derivation_compiles_for_v5e(one_chip, width):
    """The upload's derivation of one brick's ``pt`` plane from its
    resident rows, in pieces that keep the brick's relayout out of
    HBM."""
    from repro.core.backend import _pt_plane, _track_rows
    from repro.core.events import EventSchema
    n, s, t, v = WIDTHS[width]
    brick = 4 * n
    rows, lanes = _track_rows(EventSchema(s, t, v))
    compiled = _pt_plane.lower(
        jax.ShapeDtypeStruct((brick * rows, lanes), jnp.float32,
                             sharding=one_chip),
        event_shape=(t, v)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 8 * t * 128 * 4
