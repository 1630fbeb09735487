"""Fused confidence Pallas-TPU kernel: softmax-max + argmax + p(argmax).

The OSDT decoder calls this every denoising step on [rows, vocab] logits
(rows = batch x block positions). Unfused, the chain max / argmax / lse
reads the logits from HBM three times; fused, each [row_tile, vocab_tile]
tile is streamed through VMEM exactly once with running (max, argmax,
sum-exp) accumulators — the op is purely memory-bound (vocab up to 202k for
llama4), so one HBM pass is the roofline.

Tiling: rows x vocab grid, vocab minor (``arbitrary`` semantics so the
accumulators carry); tiles 128-aligned for the VPU lanes.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def softmax_acc_reset(m_scr, s_scr, i_scr) -> None:
    """Reset the running (max, sum-exp, argmax) accumulators — THE one
    definition, shared with the fused-step epilogue kernel."""
    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    s_scr[...] = jnp.zeros_like(s_scr)
    i_scr[...] = jnp.zeros_like(i_scr)


def softmax_acc_update(x, col, m_scr, s_scr, i_scr) -> None:
    """One vocab-tile update of the running (max, sum-exp, argmax).

    ``x`` [rt, vt] float32 logits (padding already -inf), ``col`` [rt, vt]
    int32 global column ids; the accumulators are [rt, 1] (rows on
    sublanes, the layout the TPU tiles a per-row column in). Tie-break
    is EXACTLY ``jnp.argmax`` (first occurrence), including across
    vocab tiles: within the tile the min column id among the tile maxima
    wins, and the strict ``tile_max > m_old`` compare rejects a later
    tile whose maximum only EQUALS the running max, keeping the earlier
    tile's index. (Verified
    against a crafted cross-tile-tie regression suite and an
    integer-logit fuzz sweep vs ``jnp.argmax`` — do not weaken either
    compare to ``>=``.)
    """
    tile_max = jnp.max(x, axis=-1, keepdims=True)
    # first-occurrence argmax within the tile
    hit = x == tile_max
    tile_arg = jnp.min(jnp.where(hit, col, jnp.iinfo(jnp.int32).max),
                       axis=-1, keepdims=True)
    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, tile_max)
    s_scr[...] = s_scr[...] * jnp.exp(m_old - m_new) + jnp.sum(
        jnp.exp(x - m_new), axis=-1, keepdims=True)
    i_scr[...] = jnp.where(tile_max > m_old, tile_arg, i_scr[...])
    m_scr[...] = m_new


def _kernel(x_ref, conf_ref, tok_ref, m_scr, s_scr, i_scr, *, nv: int,
            vt: int, vocab: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        softmax_acc_reset(m_scr, s_scr, i_scr)

    x = x_ref[...].astype(jnp.float32)  # [rt, vt]
    rt = x.shape[0]
    # column ids of this tile; mask tail padding beyond the true vocab
    col = jax.lax.broadcasted_iota(jnp.int32, (rt, vt), 1) + j * vt
    x = jnp.where(col < vocab, x, -jnp.inf)
    softmax_acc_update(x, col, m_scr, s_scr, i_scr)

    @pl.when(j == nv - 1)
    def _finish():
        conf_ref[...] = 1.0 / s_scr[...]
        tok_ref[...] = i_scr[...]


def fused_confidence_pallas(logits: Array, *, row_tile: int = 8,
                            vocab_tile: int = 2048,
                            interpret: bool = False
                            ) -> Tuple[Array, Array]:
    """logits [R, V] -> (conf [R] float32, tok [R] int32)."""
    R, V = logits.shape
    rt = min(row_tile, R)
    # pad rows to a multiple of rt and vocab to a multiple of vocab_tile
    Rp = -(-R // rt) * rt
    vt = min(vocab_tile, -(-V // 128) * 128)
    Vp = -(-V // vt) * vt
    if (Rp, Vp) != (R, V):
        logits = jnp.pad(logits, ((0, Rp - R), (0, Vp - V)),
                         constant_values=-jnp.inf)
    nr, nv = Rp // rt, Vp // vt

    kernel = functools.partial(_kernel, nv=nv, vt=vt, vocab=V)
    conf, tok = pl.pallas_call(
        kernel,
        name="fused_confidence",
        grid=(nr, nv),
        in_specs=[pl.BlockSpec((rt, vt), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((rt, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((rt, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((rt, 1), jnp.float32),
                        pltpu.VMEM((rt, 1), jnp.float32),
                        pltpu.VMEM((rt, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(logits)
    return conf[:R, 0], tok[:R, 0]
