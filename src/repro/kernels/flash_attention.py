"""Flash attention Pallas-TPU kernel (forward).

The prefill / block-step hot spot. Classic online-softmax tiling: grid
(batch*heads, q_blocks, kv_blocks), kv minor with carried (m, l, acc)
scratch in VMEM; q/k/v tiles sized for the MXU (128-aligned). Causal
masking by absolute position with an optional ``q_offset`` so the same
kernel serves self-attention (offset 0) and cache-suffix attention.

Oracle: ``ref.attention_ref``. The pure-XLA analogue used off-TPU is
``repro.models.attention.attend_flash``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array

NEG_INF = -1.0e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            nk: int, qt: int, kt: int, causal: bool, q_offset: int,
            t_real: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # [qt, D]
    k = k_ref[0].astype(jnp.float32)  # [kt, D]
    v = v_ref[0].astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [qt,kt]
    q_idx = jax.lax.broadcasted_iota(jnp.int32, (qt, kt), 0) + \
        pl.program_id(1) * qt + q_offset
    k_idx = jax.lax.broadcasted_iota(jnp.int32, (qt, kt), 1) + j * kt
    keep = k_idx < t_real
    if causal:
        keep = keep & (k_idx <= q_idx)
    s = jnp.where(keep, s, NEG_INF)

    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_old - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: Array, k: Array, v: Array, *,
                           causal: bool = True, q_offset: int = 0,
                           q_tile: int = 128, kv_tile: int = 128,
                           interpret: bool = False) -> Array:
    """q [B,H,S,D], k/v [B,H,T,D] -> [B,H,S,D].

    For GQA callers repeat kv heads beforehand (broadcast, no copy on TPU
    until VMEM load). ``causal`` uses absolute positions with ``q_offset``
    added to query indices (suffix decoding: q_offset = T - S).
    """
    B, H, S, D = q.shape
    T = k.shape[2]
    qt, kt = min(q_tile, S), min(kv_tile, T)
    Sp, Tp = -(-S // qt) * qt, -(-T // kt) * kt
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    if Tp != T:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    nq, nk = Sp // qt, Tp // kt

    qf = q.reshape(B * H, Sp, D)
    kf = k.reshape(B * H, Tp, D)
    vf = v.reshape(B * H, Tp, D)

    kernel = functools.partial(_kernel, nk=nk, qt=qt, kt=kt, causal=causal,
                               q_offset=q_offset, t_real=T)
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qt, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kt, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kt, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, qt, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((qt,), jnp.float32),
                        pltpu.VMEM((qt,), jnp.float32),
                        pltpu.VMEM((qt, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sp, D)[:, :, :S]
