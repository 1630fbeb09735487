"""The decode path's Pallas kernels compile for a TPU v5e at llada-8b widths.

Interpret-mode oracle tests check what the kernels compute; they cannot
check what the TPU's compiler (Mosaic) accepts: block shapes against the
(8, 128) tiling of the last two dims, scalar-prefetch index maps, VMEM
use. Here each kernel is compiled, not run, for one chip of a DESCRIBED
v5e topology (no chip needed: the compiler ships with libtpu), at the
widths the chip serves — B=8 rows, block 32, 32 x 128 MHA heads, T=1024
cache slots, d_model 4096, vocab 126464, bf16 — and the compiled program
must hold the kernel as a ``tpu_custom_call``.

The topology is described inside a module fixture (never at import):
only one process may load libtpu at a time, so describing it while
pytest collects would break multi-worker runs. Every test of this file
must stay in this one file for the same reason.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_attention import (_pages_per_block,
                                           cached_block_attention_pallas,
                                           paged_block_attention_pallas)
from repro.kernels.confidence import fused_confidence_pallas
from repro.kernels.fused_step import (fused_step_pallas,
                                      quantized_fused_step_pallas)
from repro.kernels.quantized_matmul import quantized_matmul_pallas

B, BS, H, KH, D, T, PS = 8, 32, 32, 32, 128, 1024, 16
M_, V, FF = 4096, 126464, 12288
BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, device, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=device) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _pallas_calls(hlo, name):
    return [ln for ln in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln and name in ln]


@pytest.mark.parametrize("per_row", [False, True])
def test_dense_block_attention_compiles(one_chip, per_row):
    row = (B,) if per_row else ()

    def fn(q, ck, cv, bk, bv, pos, slot, start, lim, exc):
        return cached_block_attention_pallas(
            q, ck, cv, bk, bv, pos, slot=slot, block_start=start,
            kv_limit=lim, exclude_start=exc, exclude_len=BS)

    hlo = _compile(fn, one_chip, ((B, BS, H, D), BF16), ((B, T, KH, D), BF16),
                   ((B, T, KH, D), BF16), ((B, BS, KH, D), BF16),
                   ((B, BS, KH, D), BF16), ((T,), I32), (row, I32),
                   (row, I32), (row, I32), (row, I32))
    assert _pallas_calls(hlo, "block_attention")


def test_paged_block_attention_compiles(one_chip):
    pages = B * T // PS

    def fn(q, pk, pv, bk, bv, pos, pt, slot, start, lim):
        return paged_block_attention_pallas(
            q, pk, pv, bk, bv, pos, pt, slot=slot, block_start=start,
            kv_limit=lim)

    hlo = _compile(fn, one_chip, ((B, BS, H, D), BF16),
                   ((pages, PS, KH, D), BF16), ((pages, PS, KH, D), BF16),
                   ((B, BS, KH, D), BF16), ((B, BS, KH, D), BF16),
                   ((T,), I32), ((B, T // PS), I32), ((B,), I32),
                   ((B,), I32), ((B,), I32))
    assert _pallas_calls(hlo, "paged_block_attention")


@pytest.mark.parametrize("rows,bs,heads,kv_heads,slots", [
    (16, 32, 64, 8, 768),     # deepseek-67b decode: 256 q rows a kv head
    (8, 32, 32, 32, 3264),    # llada-8b decode over the shared document
    (8, 64, 32, 32, 3264),    # llada-8b shared-prefix admission, 64 rows
    (1, 64, 32, 32, 3264),    # the same admission of one row (its bucket)
], ids=["deepseek67b", "llada8b", "llada8b-admit", "llada8b-admit1"])
def test_paged_block_attention_compiles_at_cell_shapes(
        one_chip, rows, bs, heads, kv_heads, slots):
    """The benchmark cells' paged geometries (16-token pages) compile with
    compute blocks of 8 pages: the VMEM reckoning keeps 128 keys a block
    and the compiler accepts what it asks for."""
    n_log = slots // PS
    pages = rows * n_log
    qt = min(128, heads // kv_heads * bs)
    assert _pages_per_block(PS, n_log, kv_heads, qt, D, bs, 2) == 8

    def fn(q, pk, pv, bk, bv, pos, pt, slot, start, lim, exc):
        return paged_block_attention_pallas(
            q, pk, pv, bk, bv, pos, pt, slot=slot, block_start=start,
            kv_limit=lim, exclude_start=exc, exclude_len=bs)

    row = ((rows,), I32)
    hlo = _compile(fn, one_chip, ((rows, bs, heads, D), BF16),
                   ((pages, PS, kv_heads, D), BF16),
                   ((pages, PS, kv_heads, D), BF16),
                   ((rows, bs, kv_heads, D), BF16),
                   ((rows, bs, kv_heads, D), BF16), ((slots,), I32),
                   ((rows, n_log), I32), row, row, row, row)
    assert _pallas_calls(hlo, "paged_block_attention")


@pytest.mark.parametrize("tied,quota", [(False, 0), (True, 0), (False, 4)])
def test_fused_step_compiles(one_chip, tied, quota):
    w = (V, M_) if tied else (M_, V)
    tiles = dict(row_tile=BS) if quota else {}

    def fn(x, w, tau, masked):
        return fused_step_pallas(x, w, tau, masked, tied=tied, quota=quota,
                                 **tiles)

    hlo = _compile(fn, one_chip, ((B * BS, M_), BF16), (w, BF16),
                   ((B * BS,), F32), ((B * BS,), jnp.bool_))
    assert _pallas_calls(hlo, "fused_step")


@pytest.mark.parametrize("tied", [False, True])
def test_quantized_fused_step_compiles(one_chip, tied):
    w = (V, M_) if tied else (M_, V)

    def fn(x, q, scale, tau, masked):
        return quantized_fused_step_pallas(x, q, scale, tau, masked,
                                           tied=tied)

    hlo = _compile(fn, one_chip, ((B * BS, M_), BF16), (w, I8), ((V,), F32),
                   ((B * BS,), F32), ((B * BS,), jnp.bool_))
    assert _pallas_calls(hlo, "quantized_fused_step")


@pytest.mark.parametrize("transpose", [False, True])
def test_quantized_matmul_compiles(one_chip, transpose):
    w = (FF, M_) if transpose else (M_, FF)
    scale = (FF, 1) if transpose else (1, FF)

    def fn(x, q, s):
        return quantized_matmul_pallas(x, q, s, transpose=transpose)

    hlo = _compile(fn, one_chip, ((B * BS, M_), BF16), (w, I8),
                   (scale, F32))
    assert _pallas_calls(hlo, "quantized_matmul")


def test_fused_confidence_compiles(one_chip):
    hlo = _compile(fused_confidence_pallas, one_chip, ((B * BS, V), F32))
    assert _pallas_calls(hlo, "fused_confidence")
