"""Fused denoising-step epilogue Pallas-TPU kernel.

One denoising step's epilogue is unembed -> confidence -> threshold:

    logits = hidden @ head            # [rows, vocab] -> HBM   (dispatch 1)
    conf, tok = confidence(logits)    # 1 more HBM pass        (dispatch 2)
    above = masked & (conf > tau)     # elementwise            (dispatch 3)

At OSDT's vocab sizes (151k-202k) the [rows, vocab] logits round-trip
dominates the step (PAPERS.md, confidence-aware calibration). This kernel
streams each lm-head logit TILE straight out of the MXU into the running
(max, argmax, sum-exp) accumulators shared with ``kernels/confidence.py``
and applies the per-row threshold compare in the final-tile epilogue: the
logits never touch HBM, and the 3-dispatch chain collapses into ONE
kernel emitting ``(conf, tok, above)`` — [rows] each, a ~vocab/3 x
reduction in epilogue HBM traffic.

Grid: rows x vocab tiles, vocab minor ("arbitrary" so the accumulators
carry). The weight tile is [vocab_tile, M] (tied embed table) or
[M, vocab_tile] (untied head), kept in its stored dtype — vocab_tile
bounds the VMEM residency at ``vocab_tile * M`` weights per buffer, so
the default 512 keeps a 4k-wide bf16 head at 4 MiB. One row tile covers
up to 256 rows (a whole decode batch's block), so the head streams from
HBM once per step rather than once per 8 rows. The threshold table
lookup (per-row slot -> tau) and the cross-row argmax FALLBACK
(Algorithm 1 l.21) stay in the decode loop; they are [rows]-sized, not
[rows, vocab]. Oracle: ``ref.fused_step_ref``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.confidence import softmax_acc_reset, softmax_acc_update

Array = jax.Array


def _epilogue(logits, j, tau_ref, msk_ref, conf_ref, tok_ref, abv_ref,
              m_scr, s_scr, i_scr, *, nv: int, vt: int, vocab: int,
              quota: int):
    """Shared per-tile accumulate + final-tile select, threshold or quota.

    ``quota > 0`` switches the final-tile compare from the per-row
    threshold rule to the fixed-step baseline's top-``quota``: the whole
    row tile is ONE ranking group (the dispatch lays each batch row's
    block out as one tile), and the stable descending rank is computed
    by pairwise counting — ``rank_i = #{j : c_j > c_i or (c_j == c_i
    and j < i)}`` — which equals the decoder's stable
    ``argsort(argsort(-conf_m))`` spelling exactly (``quota_rank_ref``).
    """
    rt = logits.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (rt, vt), 1) + j * vt
    logits = jnp.where(col < vocab, logits, -jnp.inf)
    softmax_acc_update(logits, col, m_scr, s_scr, i_scr)

    @pl.when(j == nv - 1)
    def _finish():
        conf = 1.0 / s_scr[...]                               # [rt, 1]
        conf_ref[...] = conf
        tok_ref[...] = i_scr[...]
        msk = msk_ref[...] != 0
        if quota:
            cm = jnp.where(msk, conf, -jnp.inf)               # [rt, 1]
            cm_j = cm.T                                       # [1, rt]
            gt = cm_j > cm                                    # [rt, rt]
            row_i = jax.lax.broadcasted_iota(jnp.int32, (rt, rt), 0)
            col_j = jax.lax.broadcasted_iota(jnp.int32, (rt, rt), 1)
            tie = (cm_j == cm) & (col_j < row_i)
            rank = jnp.sum((gt | tie).astype(jnp.int32), axis=1,
                           keepdims=True)
            abv_ref[...] = ((rank < quota) & msk).astype(jnp.int32)
        else:
            abv_ref[...] = (msk & (conf > tau_ref[...])).astype(jnp.int32)


def _head_tile(x, w, tied: bool):
    """[rt, M] x the weight tile -> [rt, vt] float32 logits. The tied
    tile [vt, M] contracts on its minor dim in place (no transpose)."""
    dims = ((1,), (1,)) if tied else ((1,), (0,))
    return jax.lax.dot_general(x, w, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(x_ref, w_ref, tau_ref, msk_ref, conf_ref, tok_ref, abv_ref,
            m_scr, s_scr, i_scr, *, nv: int, vt: int, vocab: int,
            tied: bool, quota: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        softmax_acc_reset(m_scr, s_scr, i_scr)

    w = w_ref[...]                          # [vt, M] tied / [M, vt] untied
    logits = _head_tile(x_ref[...].astype(w.dtype), w, tied)
    _epilogue(logits, j, tau_ref, msk_ref, conf_ref, tok_ref, abv_ref,
              m_scr, s_scr, i_scr, nv=nv, vt=vt, vocab=vocab, quota=quota)


def _qkernel(x_ref, w_ref, s_ref, tau_ref, msk_ref, conf_ref, tok_ref,
             abv_ref, m_scr, s_scr, i_scr, *, nv: int, vt: int,
             vocab: int, tied: bool, quota: int):
    """Int8-head variant: the logit tile's weights stream as int8 and are
    dequantized against the per-vocab-channel scale IN the epilogue
    stream, keeping the 1-dispatch / no-HBM-logits property at half the
    head-weight bytes (KERNELS.md "Quantized matmuls")."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        softmax_acc_reset(m_scr, s_scr, i_scr)

    x = x_ref[...]                          # [rt, M]
    w = w_ref[...].astype(jnp.float32)      # int8 [vt, M] / [M, vt]
    sc = s_ref[...]                         # tied [vt, 1] / untied [1, vt]
    # dequantize FIRST, in the activation dtype (the quantized_matmul
    # accuracy contract), then contract
    w = (w * sc).astype(x.dtype)
    logits = _head_tile(x, w, tied)
    _epilogue(logits, j, tau_ref, msk_ref, conf_ref, tok_ref, abv_ref,
              m_scr, s_scr, i_scr, nv=nv, vt=vt, vocab=vocab, quota=quota)


def _call(kernel, operands, *, name, R, Rp, rt, Vp, vt, extra_specs,
          interpret):
    """Per-row operands and results ride as [Rp, 1] columns: a block of
    ``rt`` rows is then a whole-lane-dim block, which the TPU's tiling
    accepts for any ``rt`` that is a multiple of 8."""
    nr, nv = Rp // rt, Vp // vt
    row = pl.BlockSpec((rt, 1), lambda i, j: (i, 0))
    conf, tok, above = pl.pallas_call(
        kernel,
        name=name,
        grid=(nr, nv),
        in_specs=[pl.BlockSpec((rt, operands[0].shape[1]),
                               lambda i, j: (i, 0))] + extra_specs +
                 [row, row],
        out_specs=[row, row, row],
        out_shape=[jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
                   jax.ShapeDtypeStruct((Rp, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((rt, 1), jnp.float32),
                        pltpu.VMEM((rt, 1), jnp.float32),
                        pltpu.VMEM((rt, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return conf[:R, 0], tok[:R, 0], above[:R, 0] != 0


def _row_tiling(R: int, row_tile: int, quota: int):
    """(rt, Rp). A row tile covers up to ``row_tile`` rows at once, so
    the lm head streams from HBM once per ``row_tile`` rows. Under
    ``quota`` each row tile is one ranking group, so the rows must tile
    exactly."""
    rt = min(row_tile, -(-R // 8) * 8)
    assert not (quota and R % rt), "quota ranking groups must tile exactly"
    return rt, -(-R // rt) * rt


def fused_step_pallas(x: Array, w: Array, tau: Array, masked: Array, *,
                      tied: bool, row_tile: int = 256, vocab_tile: int = 512,
                      quota: int = 0, interpret: bool = False
                      ) -> Tuple[Array, Array, Array]:
    """x [R, M] hidden; w [V, M] (tied) or [M, V]; tau [R]; masked [R]
    -> (conf [R] f32, tok [R] i32, above [R] bool).

    ``quota > 0``: the fixed-step baseline's per-row top-k replaces the
    threshold compare, ranking WITHIN each row tile — the caller must
    lay one ranking group (one batch row's block, padded to ``row_tile``
    with ``masked=False`` rows) per tile and pass ``row_tile`` equal to
    the padded group size (``ops.fused_step`` does).
    """
    R, M = x.shape
    V = w.shape[0] if tied else w.shape[1]
    rt, Rp = _row_tiling(R, row_tile, quota)
    vt = min(vocab_tile, -(-V // 128) * 128)
    Vp = -(-V // vt) * vt
    Mp = -(-M // 128) * 128

    # zero padding everywhere: pad-M contributes 0 to every dot product,
    # pad-V columns are masked to -inf by ``col < vocab``, pad rows are
    # sliced off
    x = jnp.pad(x, ((0, Rp - R), (0, Mp - M)))
    w = jnp.pad(w, ((0, Vp - V), (0, Mp - M)) if tied
                else ((0, Mp - M), (0, Vp - V)))
    tau = jnp.pad(tau.astype(jnp.float32), (0, Rp - R)).reshape(Rp, 1)
    masked = jnp.pad(masked.astype(jnp.int32), (0, Rp - R)).reshape(Rp, 1)

    w_spec = pl.BlockSpec((vt, Mp), lambda i, j: (j, 0)) if tied \
        else pl.BlockSpec((Mp, vt), lambda i, j: (0, j))
    kernel = functools.partial(_kernel, nv=Vp // vt, vt=vt, vocab=V,
                               tied=tied, quota=quota)
    return _call(kernel, (x, w, tau, masked), name="fused_step", R=R,
                 Rp=Rp, rt=rt, Vp=Vp, vt=vt, extra_specs=[w_spec],
                 interpret=interpret)


def quantized_fused_step_pallas(x: Array, q: Array, scale: Array,
                                tau: Array, masked: Array, *, tied: bool,
                                row_tile: int = 256, vocab_tile: int = 512,
                                quota: int = 0, interpret: bool = False
                                ) -> Tuple[Array, Array, Array]:
    """Int8-head fused step: ``q`` int8 [V, M] (tied) or [M, V] with the
    per-vocab-channel f32 ``scale`` (any shape reshaping to [V]) — the
    lm-head tiles stream at 1 byte/weight and dequantize in the epilogue
    stream. Same contract as :func:`fused_step_pallas` otherwise."""
    R, M = x.shape
    V = q.shape[0] if tied else q.shape[1]
    rt, Rp = _row_tiling(R, row_tile, quota)
    vt = min(vocab_tile, -(-V // 128) * 128)
    Vp = -(-V // vt) * vt
    Mp = -(-M // 128) * 128

    x = jnp.pad(x, ((0, Rp - R), (0, Mp - M)))
    q = jnp.pad(q, ((0, Vp - V), (0, Mp - M)) if tied
                else ((0, Mp - M), (0, Vp - V)))
    # the scale lies along the weight tile's vocab dim: a column for
    # the tied [vt, M] tile, a row for the untied [M, vt] one
    svec = jnp.pad(scale.reshape(V).astype(jnp.float32), (0, Vp - V))
    svec = svec.reshape(Vp, 1) if tied else svec.reshape(1, Vp)
    tau = jnp.pad(tau.astype(jnp.float32), (0, Rp - R)).reshape(Rp, 1)
    masked = jnp.pad(masked.astype(jnp.int32), (0, Rp - R)).reshape(Rp, 1)

    w_spec = pl.BlockSpec((vt, Mp), lambda i, j: (j, 0)) if tied \
        else pl.BlockSpec((Mp, vt), lambda i, j: (0, j))
    s_spec = pl.BlockSpec((vt, 1), lambda i, j: (j, 0)) if tied \
        else pl.BlockSpec((1, vt), lambda i, j: (0, j))
    kernel = functools.partial(_qkernel, nv=Vp // vt, vt=vt, vocab=V,
                               tied=tied, quota=quota)
    return _call(kernel, (x, q, svec, tau, masked),
                 name="quantized_fused_step", R=R, Rp=Rp, rt=rt,
                 Vp=Vp, vt=vt, extra_specs=[w_spec, s_spec],
                 interpret=interpret)
