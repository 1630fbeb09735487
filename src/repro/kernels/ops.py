"""jit'd wrappers + platform dispatch for the Pallas kernels.

On TPU the Pallas kernels run natively; elsewhere (this CPU container, or
any backend without Mosaic) the mathematically identical jnp forms run
instead. Tests sweep shapes/dtypes through ``interpret=True`` to validate
the kernel bodies themselves on CPU.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.block_attention import (cached_block_attention_pallas,
                                           kv_limit_from_pos,
                                           paged_block_attention_pallas)
from repro.kernels.confidence import fused_confidence_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_step import (fused_step_pallas,
                                      quantized_fused_step_pallas)
from repro.kernels.quantized_matmul import quantized_matmul_pallas
from repro.models.quantize import QuantizedTensor

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# int8 dequant-in-register matmul (the weight-streaming decode path)
# ---------------------------------------------------------------------------

def _chunks(n: int) -> int:
    """N-chunk count for the XLA dequant-matmul: the largest power of two
    that divides N and keeps chunks >= 128 wide. Chunking bounds the f32
    dequant scratch to one chunk (the whole point — the weight stays int8
    in memory and dequantizes through a cache-resident window), and is
    BITWISE identical to whole-dequant-then-matmul: every output column's
    contraction is computed from the same dequantized values in the same
    order, chunking only groups the columns."""
    for c in (32, 16, 8, 4, 2):
        if n % c == 0 and n // c >= 128:
            return c
    return 1


@partial(jax.jit, static_argnames=("transpose",))
def _quantized_matmul_xla(x, q, scale, transpose: bool):
    """Off-TPU fallback: dequantize-then-matmul (chunked over N), the
    same HLO family as the oracle ``ref.quantized_matmul_ref`` and
    bit-identical to it."""
    N = q.shape[0] if transpose else q.shape[1]
    C = _chunks(N)
    if C == 1:
        return ref.quantized_matmul_ref(x, q, scale, transpose=transpose)
    Nc = N // C
    if transpose:
        qc = q.reshape(C, Nc, q.shape[1])
        sc = scale.reshape(C, Nc, 1)
        spec = "...k,nk->...n"
    else:
        qc = jnp.moveaxis(q.reshape(q.shape[0], C, Nc), 1, 0)
        sc = jnp.moveaxis(scale.reshape(1, C, Nc), 1, 0)
        spec = "...k,kn->...n"

    def body(_, qs):
        qi, si = qs
        w = (qi.astype(jnp.float32) * si).astype(x.dtype)
        return None, jnp.einsum(spec, x, w)

    _, outs = jax.lax.scan(body, None, (qc, sc))     # [C, ..., Nc]
    return jnp.moveaxis(outs, 0, -2).reshape(*x.shape[:-1], N)


def quantized_matmul(x: Array, w: QuantizedTensor, *,
                     transpose: bool = False,
                     interpret: bool = False) -> Array:
    """x [..., K] @ dequant(w)[(.T)] -> [..., N] in ``x.dtype``.

    ``w.q`` int8 [K, N] (projections / untied head) or, with
    ``transpose=True``, [N, K] (the tied embed table as the unembed).
    TPU (or ``interpret=True``) -> the Pallas dequant-in-register kernel
    (weight tiles stream HBM->VMEM as int8 and dequantize against the
    per-channel scale in-register before the MXU dot); elsewhere -> the
    chunked dequantize-then-matmul XLA form, bit-identical to the
    oracle. Both dequantize BEFORE the contraction (accuracy contract,
    KERNELS.md).
    """
    if _on_tpu() or interpret:
        lead = x.shape[:-1]
        out = quantized_matmul_pallas(
            x.reshape(-1, x.shape[-1]), w.q, w.scale,
            transpose=transpose, interpret=interpret)
        return out.reshape(*lead, out.shape[-1])
    return _quantized_matmul_xla(x, w.q, w.scale, transpose)


@jax.jit
def _fused_confidence_tpu(logits2d: Array) -> Tuple[Array, Array]:
    return fused_confidence_pallas(logits2d)


@jax.jit
def _fused_confidence_ref(logits2d: Array) -> Tuple[Array, Array]:
    return ref.confidence_ref(logits2d)


def fused_confidence(logits: Array) -> Tuple[Array, Array]:
    """logits [..., V] -> (conf [...], tok [...])."""
    shape = logits.shape[:-1]
    flat = logits.reshape(-1, logits.shape[-1])
    fn = _fused_confidence_tpu if _on_tpu() else _fused_confidence_ref
    conf, tok = fn(flat)
    return conf.reshape(shape), tok.reshape(shape)


def fused_step(x: Array, w, tau: Array, masked: Array, *,
               tied: bool, quota: int = 0, interpret: bool = False
               ) -> Tuple[Array, Array, Array]:
    """Fused denoising-step epilogue: unembed + confidence + select.

    x [..., M] final-norm'd hidden (``block_step(..., head=False)``);
    w [V, M] embed table (``tied=True``), [M, V] head, or a
    :class:`~repro.models.quantize.QuantizedTensor` of either (the int8
    lm head — tiles dequantize inside the epilogue stream); tau [...]
    per-row threshold; masked [...] bool. Returns ``(conf, tok, above)``
    — see ``ref.fused_step_ref``.

    ``quota > 0`` runs the fixed-step baseline's select instead of the
    threshold compare: ``above`` is the per-row top-``quota`` of the
    masked confidences over the LAST axis (x must be [B, bs, M] — one
    ranking group per block row). On the kernel path each block row is
    laid out as one row tile (padded to a multiple of 8 with
    ``masked=False`` rows) so the in-kernel pairwise rank sees the whole
    group; off-TPU the ref spells the decoder's stable-argsort quota
    rule exactly, so fused quota decode is bit-identical to the unfused
    baseline.

    TPU (or ``interpret=True``) -> the Pallas kernel streaming lm-head
    logit tiles straight through the running (max, argmax, sum-exp)
    accumulators and the select: the [rows, vocab] logits never touch
    HBM and the 3-dispatch epilogue chain (head matmul, confidence
    pass, select) collapses into ONE kernel. Elsewhere -> the unfused
    jnp chain, bit-identical to running the three steps separately.
    """
    if _on_tpu() or interpret:
        if quota:
            assert x.ndim == 3, "quota ranks over [B, bs, M] block rows"
            B, bs, _ = x.shape
            bsp = -(-bs // 8) * 8
            pad = ((0, 0), (0, bsp - bs))
            xq = jnp.pad(x, pad + ((0, 0),)).reshape(B * bsp, x.shape[-1])
            tauq = jnp.pad(tau.astype(jnp.float32), pad).reshape(-1)
            mq = jnp.pad(masked, pad).reshape(-1)
            conf, tok, above = _fused_pallas(
                xq, w, tauq, mq, tied=tied, row_tile=bsp, quota=quota,
                interpret=interpret)
            return (conf.reshape(B, bsp)[:, :bs],
                    tok.reshape(B, bsp)[:, :bs],
                    above.reshape(B, bsp)[:, :bs])
        lead = x.shape[:-1]
        conf, tok, above = _fused_pallas(
            x.reshape(-1, x.shape[-1]), w, tau.reshape(-1),
            masked.reshape(-1), tied=tied, interpret=interpret)
        return (conf.reshape(lead), tok.reshape(lead), above.reshape(lead))
    # shape-preserving: the ref lowers to the same HLO as the unfused
    # chain (bit-identity contract, see ref.fused_step_ref; the int8
    # head dequantizes first — whole-dequant is bitwise identical to
    # the chunked unfused unembed)
    if isinstance(w, QuantizedTensor):
        return _fused_step_ref_quant(x, w.q, w.scale, tau, masked, tied,
                                     quota)
    return _fused_step_ref(x, w, tau, masked, tied, quota)


def _fused_pallas(x2d, w, tau1d, mask1d, *, tied: bool, quota: int = 0,
                  interpret: bool = False, **tiles):
    if isinstance(w, QuantizedTensor):
        return quantized_fused_step_pallas(
            x2d, w.q, w.scale, tau1d, mask1d, tied=tied, quota=quota,
            interpret=interpret, **tiles)
    return fused_step_pallas(x2d, w, tau1d, mask1d, tied=tied, quota=quota,
                             interpret=interpret, **tiles)


@partial(jax.jit, static_argnames=("tied", "quota"))
def _fused_step_ref(x, w, tau, masked, tied: bool, quota: int):
    return ref.fused_step_ref(x, w, tau, masked, tied=tied, quota=quota)


@partial(jax.jit, static_argnames=("tied", "quota"))
def _fused_step_ref_quant(x, q, scale, tau, masked, tied: bool,
                          quota: int):
    return ref.fused_step_ref(x, q.astype(jnp.float32) * scale, tau,
                              masked, tied=tied, quota=quota)


@partial(jax.jit, static_argnames=("causal",))
def _flash_tpu(q, k, v, causal: bool):
    return flash_attention_pallas(q, k, v, causal=causal)


@partial(jax.jit, static_argnames=("causal",))
def _flash_ref(q, k, v, causal: bool):
    return ref.attention_ref(q, k, v, causal=causal)


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True
                    ) -> Array:
    """q [B,H,S,D], k/v [B,H,T,D] -> [B,H,S,D]."""
    fn = _flash_tpu if _on_tpu() else _flash_ref
    return fn(q, k, v, causal)


# ---------------------------------------------------------------------------
# cached block attention (the diffusion block-step hot path)
# ---------------------------------------------------------------------------

def _cba_xla(q, cache_k, cache_v, block_k, block_v, kv_pos, slot,
             block_start, kv_limit, exclude_start, *, exclude_len: int,
             window: int) -> Array:
    """Length-aware XLA fallback: ``cached_block_attend`` (the one shared
    write+mask+attend definition) forced onto the flash path, whose kv
    loop stops at the padded-length bucket instead of streaming the whole
    [T] buffer. Imported at call time — the models layer sits above the
    kernels package."""
    from repro.models import attention as A

    q_pos = _q_pos(block_start, block_k.shape[1])
    out, _ = A.cached_block_attend(
        q, cache_k, cache_v, block_k, block_v, kv_pos, slot=slot,
        q_pos=q_pos, kv_limit=kv_limit, exclude_start=exclude_start,
        exclude_len=exclude_len, window=window, impl="flash")
    return out


def _q_pos(block_start: Array, bs: int) -> Array:
    """[bs] query positions, or [B, bs] when ``block_start`` is per-row."""
    ar = jnp.arange(bs, dtype=jnp.int32)
    if getattr(block_start, "ndim", 0) == 1:
        return block_start[:, None] + ar
    return block_start + ar


def cached_block_attention(
        q: Array, cache_k: Array, cache_v: Array, block_k: Array,
        block_v: Array, *, kv_pos: Array, slot: Array, block_start: Array,
        kv_limit: Optional[Array] = None,
        exclude_start: Optional[Array] = None, exclude_len: int = 0,
        window: int = 0, interpret: bool = False) -> Array:
    """Block-step attention against the KV cache, without pre-writing it.

    q [B,bs,H,D]; cache_k/v [B,T,Kh,D]; block_k/v [B,bs,Kh,D]; kv_pos [T].
    Result equals writing the block at ``slot`` and attending the full
    buffer with ``block_step``'s mask (pos validity, exclude range, window,
    bidirectional in-block) — but dead cache tiles beyond ``kv_limit`` are
    never read: TPU -> the Pallas kernel (tile skipping + native GQA),
    elsewhere -> the bounded ``attend_flash`` path. ``interpret=True``
    forces the Pallas kernel in interpret mode (tests/benchmarks).

    ``slot`` / ``block_start`` / ``exclude_start`` / ``kv_limit`` may each
    be [] or PER-ROW [B] — the sliced decode loop's mixed-cursor batches
    ride the kernel's [5, B] scalar-prefetch operand natively (a sentinel
    ``slot >= T`` hides a finished row's fresh block), so there is no
    per-row XLA fallback on TPU anymore.
    """
    if kv_limit is None:
        kv_limit = kv_limit_from_pos(kv_pos)
    if exclude_start is None:
        exclude_start = jnp.zeros((), jnp.int32)
        exclude_len = 0
    if _on_tpu() or interpret:
        return cached_block_attention_pallas(
            q, cache_k, cache_v, block_k, block_v, kv_pos, slot=slot,
            block_start=block_start, kv_limit=kv_limit,
            exclude_start=exclude_start, exclude_len=exclude_len,
            window=window, interpret=interpret)
    return _cba_xla(q, cache_k, cache_v, block_k, block_v, kv_pos, slot,
                    block_start, kv_limit, exclude_start,
                    exclude_len=exclude_len, window=window)


def paged_block_attention(
        q: Array, pool_k: Array, pool_v: Array, block_k: Array,
        block_v: Array, *, kv_pos: Array, page_table: Array, slot: Array,
        block_start: Array, page_size: int,
        kv_limit: Optional[Array] = None,
        exclude_start: Optional[Array] = None, exclude_len: int = 0,
        window: int = 0, interpret: bool = False) -> Array:
    """Paged-layout block-step attention dispatch.

    q [B,bs,H,D]; pool_k/v [P,ps,Kh,D] (one layer of the page pool);
    block_k/v [B,bs,Kh,D]; kv_pos [T]; page_table [B, n_log] (-1 =
    unmapped). ``slot`` / ``block_start`` / ``exclude_start`` /
    ``kv_limit`` may each be [] or PER-ROW [B] (a retired row passes
    ``kv_limit=0`` and its still-mapped tail pages stop being touched
    within the batch; mixed-cursor slices ride the [5, B] scalar-prefetch
    operand natively). TPU (or ``interpret=True``) -> the paged Pallas
    kernel, which DMAs pool pages in place and skips dead/unmapped pages;
    elsewhere -> gather the dense logical view through the page table and
    run the length-aware ``paged_cached_block_attend`` flash path, which
    is bit-identical to the dense layout's fallback for fully-mapped
    rows (the equivalence suite's contract).
    """
    if kv_limit is None:
        kv_limit = kv_limit_from_pos(kv_pos)
    if exclude_start is None:
        exclude_start = jnp.zeros((), jnp.int32)
        exclude_len = 0
    if _on_tpu() or interpret:
        return paged_block_attention_pallas(
            q, pool_k, pool_v, block_k, block_v, kv_pos, page_table,
            slot=slot, block_start=block_start, kv_limit=kv_limit,
            exclude_start=exclude_start, exclude_len=exclude_len,
            window=window, interpret=interpret)
    from repro.models import attention as A

    q_pos = _q_pos(block_start, block_k.shape[1])
    out, _ = A.paged_cached_block_attend(
        q, pool_k, pool_v, block_k, block_v, page_table, kv_pos,
        slot=slot, q_pos=q_pos, page_size=page_size, kv_limit=kv_limit,
        exclude_start=exclude_start, exclude_len=exclude_len,
        window=window, impl="flash")
    return out
