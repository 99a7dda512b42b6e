"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip that is described rather than attached.  Each case lowers one
kernel with ``interpret=False`` at a real width and compiles it, which
catches what interpret mode cannot — Mosaic refusing an op or a layout,
a kernel that needs more VMEM than the core has.  The compiled text
must carry the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, so a
describe at collection would hand pytest-xdist workers different test
sets.  The persistent compilation cache is off around these compiles: a
compile for a described chip is written to it but cannot be read back
without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bq
from repro.kernels.binarize import binarize_pallas
from repro.kernels.bq_distance import (
    bq_distance_pallas,
    bq_distance_rows_pallas,
)
from repro.kernels.hamming import hamming_distance_pallas
from repro.kernels.list_scan import list_scan_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _words(sharding, rows, words):
    return jax.ShapeDtypeStruct((rows, words), jnp.uint32, sharding=sharding)


@pytest.mark.parametrize("dim", [384, 768, 1536])
def test_bq_distance_compiles_for_v5e(one_chip, dim):
    w = bq.n_words(dim)
    text = _compile_text(
        lambda q, b, m: bq_distance_pallas(
            q, b, m, dim=dim, block_q=8, block_n=128, interpret=False
        ),
        _words(one_chip, 8, 2 * w),
        _words(one_chip, 1024, 2 * w),
        jax.ShapeDtypeStruct((w,), jnp.uint32, sharding=one_chip),
    )
    assert "tpu_custom_call" in text



@pytest.mark.parametrize("dim", [768, 1536])
@pytest.mark.parametrize("k", [1, 32])
def test_bq_distance_rows_compiles_for_v5e(one_chip, dim, k):
    """The beam's per-hop call: 256 queries, each against its own K rows
    (K = 32 neighbours a hop, K = 1 for the start node)."""
    w = bq.n_words(dim)
    text = _compile_text(
        lambda q, r: bq_distance_rows_pallas(q, r, dim=dim, interpret=False),
        _words(one_chip, 256, 2 * w),
        jax.ShapeDtypeStruct((256, k, 2 * w), jnp.uint32, sharding=one_chip),
    )
    assert "tpu_custom_call" in text

def test_hamming_compiles_for_v5e(one_chip):
    w = bq.n_words(384)
    text = _compile_text(
        lambda q, b: hamming_distance_pallas(
            q, b, block_q=8, block_n=128, interpret=False
        ),
        _words(one_chip, 8, w),
        _words(one_chip, 1024, w),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dim", [384, 768, 1536])
def test_binarize_compiles_for_v5e(one_chip, dim):
    text = _compile_text(
        lambda x: binarize_pallas(
            x, true_dim=dim, block_n=256, interpret=False
        ),
        jax.ShapeDtypeStruct((1024, dim), jnp.float32, sharding=one_chip),
    )
    assert "tpu_custom_call" in text


def test_list_scan_compiles_for_v5e_at_1m_lists(one_chip):
    """768-d against L = 1,024 centroids: the √N lists of a 1M corpus,
    padded to the 128-wide centroid block."""
    w = bq.n_words(768)
    text = _compile_text(
        lambda q, c, m: list_scan_pallas(
            q, c, m, dim=768, block_q=8, interpret=False
        ),
        _words(one_chip, 256, 2 * w),
        _words(one_chip, 1024, 2 * w),
        jax.ShapeDtypeStruct((w,), jnp.uint32, sharding=one_chip),
    )
    assert "tpu_custom_call" in text
