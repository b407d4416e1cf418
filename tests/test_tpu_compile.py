"""Compile the main path's kernels for a described v5e, at real widths.

Nothing runs: XLA's TPU compiler is handed shapes for a chip that is
described, not attached, and refuses what the chip would refuse — more
scoped VMEM than a kernel may use, more HBM than the chip has.
Interpret-mode tests cannot see either. The topology is described inside
a fixture, never at import: only one process at a time may load libtpu,
and every xdist worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from geomesa_tpu.analytics import join
from geomesa_tpu.scan import zscan

ROWS = 100_000_000  # the per-chip north-star scale


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _s(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _scan_cols(sh, n):
    return ([_s(sh, (n,), jnp.float32)] * 4
            + [_s(sh, (n,), jnp.int32)] * 2)


def _query(sh, k, b, batch=()):
    return [_s(sh, batch + (k, 8), jnp.float32),
            _s(sh, batch + (k,), jnp.bool_),
            _s(sh, batch + (b, 4), jnp.int32),
            _s(sh, batch + (b,), jnp.bool_)]


def _compile(fn, *args, **static):
    return fn.lower(*args, **static).compile()


# (boxes, intervals, time_any): one box to the most a query pads to
QUERY_SHAPES = pytest.mark.parametrize(
    "k,b,time_any", [(1, 1, False), (4, 2, False), (8, 8, False),
                     (8, 1, True)])


@QUERY_SHAPES
def test_dense_scan_100m(one_chip, k, b, time_any):
    c = _compile(zscan._scan_mask, *_scan_cols(one_chip, ROWS),
                 *_query(one_chip, k, b), time_any=time_any)
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


@QUERY_SHAPES
def test_dense_codes_100m(one_chip, k, b, time_any):
    # the code byte a row (verdict + boundary flag) in the same pass, as
    # many bytes out as the bool mask
    args = (*_scan_cols(one_chip, ROWS), *_query(one_chip, k, b))
    c = _compile(zscan._scan_mask, *args, time_any=time_any,
                 flag_boundary=True)
    mask = _compile(zscan._scan_mask, *args, time_any=time_any)
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30
    assert (c.memory_analysis().output_size_in_bytes
            == mask.memory_analysis().output_size_in_bytes)


def test_batch_mask_10m_x32(one_chip):
    # the batched path's kernel: verdict and boundary flags per query
    _compile(zscan._batch_mask_cand, *_scan_cols(one_chip, 10_000_000),
             *_query(one_chip, 1, 1, (32,)), _s(one_chip, (), jnp.int32))


def test_batch_compaction_100m_x32(one_chip):
    """32 fused queries over 100M rows: vmapped, nonzero's temporaries
    asked for 39 GB of HBM; one row at a time they need under 1 GiB."""
    c = _compile(zscan._batch_nonzero,
                 _s(one_chip, (32, ROWS), jnp.bool_), size=1 << 20)
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


def test_contains_counts_100m(one_chip):
    f32, i32 = jnp.float32, jnp.int32
    kp, ne = 1024, 8
    _compile(join._contains_counts_all,
             _s(one_chip, (ROWS,), f32), _s(one_chip, (ROWS,), i32),
             _s(one_chip, (kp,), i32), _s(one_chip, (kp,), i32),
             _s(one_chip, (kp, 4), f32), _s(one_chip, (kp, ne, 4), f32),
             _s(one_chip, (kp, ne), jnp.bool_),
             _s(one_chip, (ROWS,), f32), _s(one_chip, (ROWS,), f32),
             _s(one_chip, (), i32), smax=1 << 21, band_cap=256)


def test_knn_two_stage(one_chip):
    # past 4 * 16384 rows the kernel takes its two-stage top-k; the
    # 100M-row shape compiles too, in about 25 s
    n = 1 << 17
    f32 = jnp.float32
    _compile(join._knn_kernel, _s(one_chip, (n,), f32),
             _s(one_chip, (n,), f32), _s(one_chip, (8,), f32),
             _s(one_chip, (8,), f32), k=256,
             nrows=_s(one_chip, (), jnp.int32))
