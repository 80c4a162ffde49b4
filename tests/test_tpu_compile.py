"""Compile rehearsals for the serving kernels on a described TPU v5e.

The TPU compiler is installed even where no chip is attached, so these
tests lower the fused ``lss_topk`` kernel (and the ``simhash_codes``
kernel the IUL fit retrieves through) at qwen2-0.5b's head widths, and
``lss_topk`` again and the ``moe_gmm`` grouped product at
qwen2-moe-a2.7b's (d 2,048, experts of 1,408, 15 held), and compile them
for one chip of a ``v5e:2x2`` topology.  One more lowers
the LSS head's whole jitted step, to see that an index stored in the
kernel's aligned layout reaches the kernel without a slab-sized pad or
copy, as logical storage does not; another the pooled decode step at both
decode widths, to see that it writes its KV rows into the donated cache
without copying a layer's slab.  Mosaic refuses
what interpret mode accepts: unaligned DMA slices, scalar stores to
VMEM, bool transposes, rank-changing shape casts, and more VMEM than the
kernel asks for.  Nothing runs, so these say nothing about results.

The topology is described inside a fixture: only the worker that runs
these tests loads the TPU library, and every worker collects the same
tests.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import qwen2_0_5b
from repro.core.lss import LSSIndex
from repro.core.tables import LSSTables
from repro.kernels import registry
from repro.kernels.lss_topk.kernel import lss_topk_pallas
from repro.kernels.lss_topk.ops import lss_topk_vmem_bytes, lss_topk_vmem_limit
from repro.kernels.moe_gmm.ops import moe_gmm_op
from repro.kernels.simhash_codes.kernel import simhash_codes_pallas
from repro.models import transformer as T
from repro.serve.heads import make_lss_head

# qwen2-0.5b's LSS head: d_aug 897 -> 1024 lanes, K=10 bits, L=1 table,
# P = 304 slots per bucket -> 384 lanes (configs/qwen2_0_5b.py)
D, K_BITS, N_TABLES, CAP, TOP_K = 1024, 10, 1, 384, 10
N_SLABS = N_TABLES * 2 ** K_BITS
D_AUG, CAP_LOGICAL, D_MODEL = 897, 304, 896


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


# qwen2-moe-a2.7b: d_aug 2,049 -> 2,176 lanes, the same K, L and P
D_MOE = 2176


@pytest.mark.parametrize("block_q", [1, 8])
def test_lss_topk_compiles_for_v5e_at_qwen2_moe_width(
        one_chip, no_persistent_cache, block_q):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    est = lss_topk_vmem_bytes(N_TABLES * CAP, D_MOE, CAP, block_q=block_q,
                              dedup="quadratic", kl=K_BITS * N_TABLES)
    compiled = lss_topk_pallas.lower(
        arg((block_q, D_MOE), jnp.float32),
        arg((D_MOE, K_BITS * N_TABLES), jnp.float32),
        arg((N_SLABS, 1, CAP), jnp.int32),
        arg((N_SLABS, CAP, D_MOE), jnp.float32), None,
        k_bits=K_BITS, n_tables=N_TABLES, top_k=TOP_K, block_q=block_q,
        dedup="quadratic", vmem_limit_bytes=lss_topk_vmem_limit(est)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows,k,n", [
    (128, 2048, 1408),      # a decode step's 32 rows x top-4: gate / up
    (128, 1408, 2048),      # down
    (4096, 2048, 1408),     # a 1,024-token prefill bucket
])
def test_moe_gmm_compiles_for_v5e(one_chip, no_persistent_cache, rows, k,
                                  n):
    """The grouped product over 15 held experts of qwen2-moe-a2.7b, read
    in place from six layers' stacked weights, as the ``pallas`` impl
    calls it; its custom call keeps the name the benchmark's reader finds
    it by."""
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    impl = moe_gmm_op.impls["pallas"]
    hlo = jax.jit(impl).lower(arg((rows, k), jnp.bfloat16),
                              arg((6, 15, k, n), jnp.bfloat16),
                              arg((15,), jnp.int32),
                              arg((1,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert re.search(r"%moe_gmm_pallas(\.\d+)? = ", hlo)


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


def _lss_index(one_chip, layout: str) -> LSSIndex:
    """qwen2-0.5b's fp32 LSS index as shapes, in either slab layout."""
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tables = LSSTables(
        arg((N_TABLES, 2 ** K_BITS, CAP_LOGICAL), jnp.int32),
        arg((N_TABLES,), jnp.int32), K_BITS, N_TABLES, CAP_LOGICAL)
    theta = arg((D_AUG, K_BITS * N_TABLES), jnp.float32)
    if layout == "stored":
        return LSSIndex(theta, tables, arg((N_SLABS, CAP, D), jnp.float32),
                        None, arg((N_SLABS, 1, CAP), jnp.int32))
    return LSSIndex(theta, tables, arg(
        (N_TABLES, 2 ** K_BITS, CAP_LOGICAL, D_AUG), jnp.float32))


def _slab_sized_moves(hlo: str, n_elems: int,
                      ops: str = "pad|copy") -> list[str]:
    """The instructions of ``hlo`` named by ``ops`` (pad and copy by
    default) with at least ``n_elems`` elements (a copy's or transpose's
    result is its operand's size, and a pad's is larger)."""
    move = re.compile(rf"= \w+\[([\d,]*)\]\S* (?:{ops})\(")
    found = []
    for line in hlo.splitlines():
        m = move.search(line)
        if m and np.prod([int(x) for x in m.group(1).split(",") if x]
                         ) >= n_elems:
            found.append(line.strip())
    return found


@pytest.mark.parametrize("layout", ["stored", "padded_per_call"])
def test_lss_head_step_moves_no_slab_tensor(one_chip, no_persistent_cache,
                                            layout):
    """The decode step's LSS head at qwen2-0.5b's widths, 32 rows: over
    aligned storage the optimized program neither pads nor copies the
    slab tensor and its temporaries stay below its bytes; over logical
    storage it pads it in every call."""
    head = make_lss_head(_lss_index(one_chip, layout), None, TOP_K,
                         impl="pallas")
    q = jax.ShapeDtypeStruct((32, D_MODEL), jnp.float32, sharding=one_chip)
    compiled = jax.jit(head.with_operands).lower(q, *head.operands).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    slab_elems = N_TABLES * 2 ** K_BITS * CAP_LOGICAL * D_AUG
    moves = _slab_sized_moves(hlo, slab_elems)
    temp = compiled.memory_analysis().temp_size_in_bytes
    if layout == "stored":
        assert moves == []
        assert temp < N_SLABS * CAP * D * 4
    else:
        assert any(" pad(" in line for line in moves), moves


# the decode cells' attention: qwen2-0.5b's program config (24 layers, KV
# 2 x 64), and a dense stand-in with qwen2-moe-a2.7b's chip share (6 layers,
# MHA 16 x 128; the shared expert's width for the FFN)
_DECODE_WIDTHS = {
    "qwen2-0.5b": qwen2_0_5b.CONFIG.model_cfg,
    "qwen2-moe-a2.7b": T.TransformerConfig(
        name="qwen2-moe-a2.7b-attention", n_layers=6, d_model=2048,
        n_heads=16, n_kv_heads=16, head_dim=128, d_ff=5632, vocab=151936,
        qkv_bias=True, dtype=jnp.bfloat16),
}


def _decode_args(cfg, sharding, b=32, s=1024, cache_sharding=None):
    """Abstract params, tokens/lengths [b] and one k or v cache [L, b, s,
    KV, H] for a compile on described chips."""
    def arg(shape, dtype, sh=sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    shapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    shardings = (jax.tree.map(lambda _: sharding, shapes)
                 if cache_sharding is None else
                 jax.tree.map(lambda spec: NamedSharding(sharding.mesh, spec),
                              T.param_specs(cfg)))
    params = jax.tree.map(lambda a, sh: arg(a.shape, a.dtype, sh), shapes,
                          shardings)
    kv = arg((cfg.n_layers, b, s, cfg.n_kv_heads, cfg.head_dim),
             jnp.bfloat16, cache_sharding or sharding)
    return params, arg((b,), jnp.int32), kv


def _aliased(hlo: str) -> set[int]:
    """The parameters the compiled module aliases to its outputs."""
    header = hlo.splitlines()[0]          # HloModule ..., input_output_alias
    return {int(n) for n in re.findall(r"\((\d+), \{\}, \w+-alias\)",
                                       header)}


@pytest.mark.parametrize("step", ["pooled", "scalar"])
@pytest.mark.parametrize("width", list(_DECODE_WIDTHS))
def test_decode_step_writes_kv_in_place(one_chip, no_persistent_cache,
                                        width, step):
    """The decode step, 32 rows of 1,024 positions, k/v donated, with
    per-row lengths (the pooled step) or one length (``decode_step``):
    both slabs alias the step's outputs, no copy or transpose moves a
    layer's slab, and the temporaries stay below one layer slab's bytes
    (a step that rewrote the cache as the layer scan's output held two
    cache-sized copies and a cache's bytes of temporaries); at the MoE
    widths attention fuses the select of the step's rows."""
    cfg = _DECODE_WIDTHS[width]
    params, rows, kv = _decode_args(cfg, one_chip)
    if step == "pooled":
        fn, length = T.decode_step_pooled, rows
    else:
        def fn(p, tok, k, v, n, cfg):
            return T.decode_step(p, tok, T.KVCache(k, v, n), cfg)
        length = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    with registry.use_impl("pallas"):    # as the chip's backend selects
        compiled = jax.jit(
            lambda p, tok, k, v, n: fn(p, tok, k, v, n, cfg),
            donate_argnums=(2, 3), keep_unused=True
        ).lower(params, rows, kv, kv, length).compile()
    hlo = compiled.as_text()
    n_params = len(jax.tree.leaves(params))
    assert {n_params + 1, n_params + 2} <= _aliased(hlo), hlo[:300]
    layer_slab = 32 * 1024 * cfg.n_kv_heads * cfg.head_dim
    assert _slab_sized_moves(hlo, layer_slab, "copy|transpose") == []
    assert compiled.memory_analysis().temp_size_in_bytes < layer_slab * 2
    if cfg.head_dim % 128 == 0:
        # the rows form: attention's score and value fusions read the
        # step's rows selected into the slab before the write, as they did
        # when the step rewrote the whole cache (read from the scattered
        # cache, they compiled to other code and other low bits)
        slab = f"bf16[32,1024,{cfg.n_kv_heads},{cfg.head_dim}]"
        assert len(re.findall(rf"= {re.escape(slab)}\S* select\(",
                              hlo)) == 2


@pytest.fixture(scope="module")
def two_by_four(topo):
    """The (2, 4) ('data', 'model') mesh over a described v5e:2x4."""
    from jax.experimental import topologies
    try:
        chips = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x4")
    except Exception as e:
        pytest.skip(f"no v5e:2x4 topology can be described here: {e}")
    return jax.sharding.Mesh(np.array(chips.devices).reshape(2, 4),
                             ("data", "model"))


@pytest.mark.parametrize("width", list(_DECODE_WIDTHS))
def test_split_cache_decode_step_writes_kv_in_place(
        two_by_four, no_persistent_cache, width):
    """The pooled decode step under the (2, 4) mesh, its caches split as
    ``cache_specs`` splits 32 rows (rows over 'data', positions over
    'model'), k/v donated: the write takes the layer select, both slabs
    alias, no copy or transpose moves a chip's whole cache, and the
    temporaries stay below a chip's layer slab.  (A row scatter into the
    split cache at head_dim 64 relayouted it: four cache-sized copies and
    100 MB of temporaries a chip.)"""
    cfg = _DECODE_WIDTHS[width]
    mesh = two_by_four
    cache = NamedSharding(mesh, T.cache_specs(cfg, 32).k)
    params, rows, kv = _decode_args(cfg, NamedSharding(mesh, P()),
                                    cache_sharding=cache)
    mark = registry.dispatch_count()
    with registry.use_impl("pallas"), jax.set_mesh(mesh):
        compiled = jax.jit(
            lambda p, tok, k, v, lengths: T.decode_step_pooled(
                p, tok, k, v, lengths, cfg),
            donate_argnums=(2, 3), keep_unused=True
        ).lower(params, rows, kv, kv, rows).compile()
    assert {c for op, c in registry.dispatch_log(mark)
            if op == "decode.kv_write"} == {"layer_select"}
    hlo = compiled.as_text()
    n_params = len(jax.tree.leaves(params))
    assert {n_params + 1, n_params + 2} <= _aliased(hlo), hlo[:300]
    chip_slab = 32 * 1024 * cfg.n_kv_heads * cfg.head_dim // 8
    assert _slab_sized_moves(hlo, chip_slab * cfg.n_layers,
                             "copy|transpose") == []
    assert compiled.memory_analysis().temp_size_in_bytes < chip_slab * 2


def test_paged_decode_step_lanes_views_are_bitcasts(one_chip,
                                                    no_persistent_cache):
    """The paged decode step at qwen2-0.5b's widths (32 rows of 1,024
    positions in pages of 16), arenas donated: the write takes the lanes
    kernel over the gathered view, and neither its [L, B, KV, H, S] view
    nor the view back is a copy or a transpose; both arenas alias."""
    cfg = _DECODE_WIDTHS["qwen2-0.5b"]
    b, s, page = 32, 1024, 16
    params, rows, _ = _decode_args(cfg, one_chip)
    arena = jax.ShapeDtypeStruct(
        (cfg.n_layers, b * s // page + 1, page, cfg.n_kv_heads,
         cfg.head_dim), jnp.bfloat16, sharding=one_chip)
    table = jax.ShapeDtypeStruct((b, s // page), jnp.int32,
                                 sharding=one_chip)
    mark = registry.dispatch_count()
    with registry.use_impl("pallas"):
        compiled = jax.jit(
            lambda p, tok, k, v, tab, lengths: T.decode_step_paged(
                p, tok, k, v, tab, lengths, cfg, s),
            donate_argnums=(2, 3), keep_unused=True
        ).lower(params, rows, arena, arena, table, rows).compile()
    assert {c for op, c in registry.dispatch_log(mark)
            if op == "decode.kv_write"} == {"lanes"}
    hlo = compiled.as_text()
    n_params = len(jax.tree.leaves(params))
    assert {n_params + 1, n_params + 2} <= _aliased(hlo), hlo[:300]
    lanes_view = f"{cfg.n_layers},{b},{cfg.n_kv_heads},{cfg.head_dim},{s}"
    assert not re.search(
        rf"= bf16\[{lanes_view}\]\S* (?:copy|transpose)\(", hlo)
