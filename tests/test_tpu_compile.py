"""Hot-path kernels compiled for a described (not attached) TPU v5e chip at
the paper's 1M-row scale and real widths.

Nothing runs: each case lowers and compiles for one chip of a ``v5e:2x2``
topology description, so what Mosaic or XLA would refuse on the chip
(block shapes, VMEM, HBM) fails here without one. The streaming cases also
check that no (B, N) score matrix is materialised.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.index.ivf import _lloyd
from repro.kernels.streaming.ops import scan_tiles, streaming_fused_scan
from repro.launch.roofline import V5E, peaks
from repro.serve.engine import _GATHER_BYTES, _gather_scores, gather_chunk

N = 1 << 20        # rows: the paper's 1M scale
B = 64             # queries per dispatch
HBM = peaks(V5E)["hbm_bytes"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    prev_cache = jax.config.jax_enable_compilation_cache
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can describe the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prev_log


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _scan(**kw):
    return jax.jit(functools.partial(streaming_fused_scan, interpret=False,
                                     **kw))


@pytest.mark.parametrize("d", [128, 2560])
def test_streaming_scan_plain(one_chip, d):
    compiled = _scan(k=100).lower(
        _spec(one_chip, (B, d)), _spec(one_chip, (N, d))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < B * N * 4


@pytest.mark.parametrize("batch", [1, 32])
@pytest.mark.parametrize("d", [128, 512])
def test_streaming_scan_chip_cell(one_chip, d, batch):
    # the benchmark cell's flat scan: a resident 1M-row column (rows
    # padded to 128) at its own tiles, with a ragged last tile; the
    # column is read in place, never copied
    n = 1_000_064
    bm, bn = scan_tiles(batch, n, d, 100)
    assert n % bn and bn >= 1024 and bm == max(8, batch)
    s = functools.partial(_spec, one_chip)
    compiled = _scan(k=100).lower(
        s((batch, d)), s((n, d)), valid_n=s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < n * d * 4


def test_streaming_scan_masked_delta_l2(one_chip):
    d, nd = 768, 8192
    s = functools.partial(_spec, one_chip)
    compiled = _scan(k=100, metric="l2").lower(
        s((B, d)), s((N, d)), valid_n=s((), jnp.int32),
        dead_mask=s((N,), jnp.bool_), delta=s((nd, d)),
        delta_valid_n=s((), jnp.int32), delta_dead_mask=s((nd,), jnp.bool_),
        keep_mask=s((N,), jnp.bool_), delta_keep_mask=s((nd,), jnp.bool_),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < B * (N + nd) * 4


def test_streaming_scan_k_past_row_tile(one_chip):
    # IVF's gathered probe union: one query (an 8-row query block), k far
    # past a 128-row tile, so the fold's trip count is capped at min(k, bn)
    d, k = 512, 1024
    s = functools.partial(_spec, one_chip)
    compiled = _scan(k=k).lower(
        s((1, d)), s((N, d)), valid_n=s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_semcache_probe_k1(one_chip):
    # the semantic cache's L2 probe: queries vs the cache's query ring
    cap, d = 256, 512
    s = functools.partial(_spec, one_chip)
    compiled = _scan(k=1, metric="l2").lower(
        s((B, d)), s((cap, d)), valid_n=s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ivf_gather_scores(one_chip):
    # one IVF probe union per query, covering most of the 1M-row table:
    # gathered in chunks, so the (B, R, d) rows never exist at once
    R, d = 786432, 512
    chunk = gather_chunk(B, R, d)
    s = functools.partial(_spec, one_chip)
    compiled = _gather_scores.lower(
        s((N, d)), s((B, R), jnp.int32), s((B, d)), chunk=chunk).compile()
    assert R % chunk == 0 and chunk < R
    # one chunk of gathered rows plus the (B, R) scores, not B·R·d rows
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 2 * _GATHER_BYTES + 2 * B * R * 4)


def test_ivf_lloyd_1m(one_chip):
    # IVF build at the paper's scale: 1M rows, 1024 lists, 8 Lloyd rounds
    s = functools.partial(_spec, one_chip)
    compiled = jax.jit(_lloyd, static_argnames=("n_iters",)).lower(
        s((N, 512)), s((1024, 512)), n_iters=8).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM
