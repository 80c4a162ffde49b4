"""Compile rehearsals for the serving kernels on a described TPU v5e.

The TPU compiler is installed even where no chip is attached, so these
tests lower the fused ``lss_topk`` kernel (and the ``simhash_codes``
kernel the IUL fit retrieves through) at qwen2-0.5b's head widths and
compile them for one chip of a ``v5e:2x2`` topology.  Mosaic refuses
what interpret mode accepts: unaligned DMA slices, scalar stores to
VMEM, bool transposes, rank-changing shape casts, and more VMEM than the
kernel asks for.  Nothing runs, so these say nothing about results.

The topology is described inside a fixture: only the worker that runs
these tests loads the TPU library, and every worker collects the same
tests.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lss_topk.kernel import lss_topk_pallas
from repro.kernels.lss_topk.ops import lss_topk_vmem_bytes, lss_topk_vmem_limit
from repro.kernels.simhash_codes.kernel import simhash_codes_pallas

# qwen2-0.5b's LSS head: d_aug 897 -> 1024 lanes, K=10 bits, L=1 table,
# P = 304 slots per bucket -> 384 lanes (configs/qwen2_0_5b.py)
D, K_BITS, N_TABLES, CAP, TOP_K = 1024, 10, 1, 384, 10
N_SLABS = N_TABLES * 2 ** K_BITS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("slab_dtype", ["fp32", "int8"])
@pytest.mark.parametrize("dedup", ["quadratic", "bitonic"])
@pytest.mark.parametrize("block_q", [1, 8])
def test_lss_topk_compiles_for_v5e(one_chip, no_persistent_cache, block_q,
                                   dedup, slab_dtype):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    quantized = slab_dtype == "int8"
    est = lss_topk_vmem_bytes(N_TABLES * CAP, D, CAP, block_q=block_q,
                              dedup=dedup, kl=K_BITS * N_TABLES,
                              slab_dtype=slab_dtype)
    limit = lss_topk_vmem_limit(est)
    compiled = lss_topk_pallas.lower(
        arg((block_q, D), jnp.float32),
        arg((D, K_BITS * N_TABLES), jnp.float32),
        arg((N_SLABS, 1, CAP), jnp.int32),
        arg((N_SLABS, CAP, D), jnp.int8 if quantized else jnp.float32),
        arg((N_SLABS, 1, CAP), jnp.float32) if quantized else None,
        k_bits=K_BITS, n_tables=N_TABLES, top_k=TOP_K, block_q=block_q,
        dedup=dedup, vmem_limit_bytes=limit).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert est < limit


def test_simhash_codes_compiles_for_v5e(one_chip, no_persistent_cache):
    block_b = 256
    x = jax.ShapeDtypeStruct((2 * block_b, D), jnp.float32,
                             sharding=one_chip)
    theta = jax.ShapeDtypeStruct((D, K_BITS * N_TABLES), jnp.float32,
                                 sharding=one_chip)
    compiled = simhash_codes_pallas.lower(
        x, theta, k_bits=K_BITS, n_tables=N_TABLES,
        block_b=block_b).compile()
    assert "tpu_custom_call" in compiled.as_text()
