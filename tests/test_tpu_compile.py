"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Interpret mode checks what the kernels compute; only the TPU compiler
checks that Mosaic accepts their block shapes, casts and VMEM use.  These
tests lower each kernel of the served path at LLaMA-7B widths (d_model
4096, 32 heads, head_dim 128, d_ff 11008) through its ``kernels.ops``
entry point with ``mode="pallas"`` and compile it for one chip of a
described ``v5e:2x2`` — no chip is needed, nothing runs.

The topology is described inside a module-scoped fixture (never at import):
only one process may load the TPU library, and under several test workers
only the worker that runs this file loads it.  Every compile stays in this
one file for the same reason.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.qtensor import QTensor
from repro.kernels import ops

D_MODEL, HEADS, HEAD_DIM, D_FF = 4096, 32, 128, 11008
BATCH, PAGES_PER_SEQ, CHUNK, GROUP = 8, 33, 128, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_tpu(one_chip):
    """Compile ``fn`` at the given shapes for one described v5e chip with
    the persistent compilation cache off (an entry written without a chip
    cannot be read back) and return the optimized HLO text."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)

    def run(fn, *shapes):
        args = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        return jax.jit(fn).lower(*args).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _custom_call_names(hlo):
    """The instruction names of an HLO text's ``tpu_custom_call``s, without
    their ``.N`` suffix: the names a device trace keys kernel time on."""
    names = set()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            lhs = line.split(" = ", 1)[0].replace("ROOT ", "").strip()
            names.add(re.sub(r"\.\d+$", "", lhs.lstrip("%")))
    return names


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _packed_weight(k, n, bits=4, group=GROUP):
    return QTensor(packed=_sds((k // 8 * bits, n), jnp.uint8),
                   scale=_sds((k // group, n), jnp.float32),
                   zp=_sds((k // group, n), jnp.float32),
                   bits=bits, group_size=group)


def _kv_pools(num_pages, page_size, kv_bits, heads=HEADS):
    if kv_bits == 4:
        codes = _sds((num_pages, page_size, heads, HEAD_DIM // 2), jnp.int8)
        scales = _sds((num_pages, page_size, heads, HEAD_DIM // 32),
                      jnp.bfloat16)
    else:
        codes = _sds((num_pages, page_size, heads, HEAD_DIM), jnp.int8)
        scales = _sds((num_pages, page_size, heads), jnp.float32)
    return (codes, codes, scales, scales)


@pytest.mark.parametrize("k,n", [(D_MODEL, D_MODEL), (D_FF, D_MODEL)])
@pytest.mark.parametrize("m", [BATCH, CHUNK])
def test_w4a8_matmul_compiles(compile_tpu, m, k, n):
    """The W4A4 g64 serving matmul, decode rows and a prefill chunk; the
    K=11008 down projection runs as one K block (11008 % 512 != 0)."""
    hlo = compile_tpu(
        lambda x, w: ops.quant_matmul(x, w, a_bits=4, mode="pallas"),
        _sds((m, k), jnp.bfloat16), _packed_weight(k, n))
    assert "tpu_custom_call" in hlo


def test_dequant_matmul_w4_compiles(compile_tpu):
    """The weight-only (--abits 16) path: codes are cast to f32 via int32,
    the uint32 -> f32 cast Mosaic refuses never appears."""
    hlo = compile_tpu(lambda x, w: ops.dequant_matmul(x, w, mode="pallas"),
                      _sds((BATCH, D_MODEL), jnp.bfloat16),
                      _packed_weight(D_MODEL, D_FF))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kv_bits,page_size,heads,batch,pages", [
    pytest.param(8, 16, HEADS, BATCH, PAGES_PER_SEQ, id="8-16-32"),
    pytest.param(8, 64, HEADS, BATCH, PAGES_PER_SEQ, id="8-64-32"),
    pytest.param(4, 16, HEADS, BATCH, PAGES_PER_SEQ, id="4-16-32"),
    # one chip's head slice of a (1, 4) mesh
    pytest.param(8, 16, HEADS // 4, BATCH, PAGES_PER_SEQ, id="8-16-8"),
    # the benchmark's pools: OPT-6.7B decode and OPT-13B long context
    pytest.param(8, 16, 32, 16, 56, id="8-16-32-b16-p56"),
    pytest.param(8, 16, 40, 4, 112, id="8-16-40-b4-p112"),
    pytest.param(8, 16, 8, 16, 56, id="8-16-8-b16-p56")])
def test_flash_decode_paged_compiles(compile_tpu, kv_bits, page_size, heads,
                                     batch, pages):
    """Paged decode at the engine's page sizes: blocks of pages, each page
    of all heads one BlockSpec slot, under the kernel's own name."""
    num_pages = batch * pages
    hlo = compile_tpu(
        lambda q, kv, pt, n: ops.flash_decode(q, kv, n, page_table=pt,
                                              mode="pallas"),
        _sds((batch, 1, heads, HEAD_DIM), jnp.bfloat16),
        _kv_pools(num_pages, page_size, kv_bits, heads),
        _sds((batch, pages), jnp.int32), _sds((batch,), jnp.int32))
    assert "flash_decode_paged" in _custom_call_names(hlo)


def test_flash_prefill_paged_compiles(compile_tpu):
    """A (max_batch, prefill_chunk) engine chunk step over the kv8 pool."""
    num_pages = BATCH * PAGES_PER_SEQ
    hlo = compile_tpu(
        lambda q, kv, pt, off, cl: ops.flash_prefill(
            q, kv, off, cl, page_table=pt, mode="pallas"),
        _sds((BATCH, CHUNK, HEADS, HEAD_DIM), jnp.bfloat16),
        _kv_pools(num_pages, 16, 8),
        _sds((BATCH, PAGES_PER_SEQ), jnp.int32), _sds((BATCH,), jnp.int32),
        _sds((BATCH,), jnp.int32))
    assert "tpu_custom_call" in hlo


def _linear_decode_hlo(compile_tpu, batch, s):
    codes = _sds((batch, s, HEADS, HEAD_DIM), jnp.int8)
    scales = _sds((batch, s, HEADS), jnp.float32)
    return compile_tpu(
        lambda q, kv, n: ops.flash_decode(q, kv, n, mode="pallas"),
        _sds((batch, 1, HEADS, HEAD_DIM), jnp.bfloat16),
        (codes, codes, scales, scales), _sds((batch,), jnp.int32))


def test_flash_decode_linear_compiles(compile_tpu):
    """Linear kv8 cache, cut into 16-token pages."""
    hlo = _linear_decode_hlo(compile_tpu, BATCH, 1024)
    assert "tpu_custom_call" in hlo


def test_flash_decode_linear_ragged_compiles(compile_tpu):
    """A linear kv8 cache of a ragged length (a 1000 + 200 token run's
    ``max_len``): 8-token pages, the largest that divide it, never one
    page of the whole cache."""
    hlo = _linear_decode_hlo(compile_tpu, 4, 1208)
    assert "flash_decode" in _custom_call_names(hlo)
    assert "s8[604,8,32,128]" in hlo       # 4 rows x 151 pages of 8 tokens


def test_quantize_pack_compiles(compile_tpu):
    """Offline weight packing (off the serve path) compiles too."""
    hlo = compile_tpu(
        lambda w: ops.quantize_pack(w, bits=4, group_size=GROUP,
                                    mode="pallas"),
        _sds((D_MODEL, D_MODEL), jnp.float32))
    assert "tpu_custom_call" in hlo
