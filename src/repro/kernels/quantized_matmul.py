"""Dequant-in-register int8 weight matmul Pallas-TPU kernels.

The decode roofline (``repro.roofline.step_time_model``) puts every step
variant on the memory roof, dominated by WEIGHT streaming. These kernels
stream the projection weights as int8 tiles — half the HBM bytes of
bf16, a quarter of f32 — and dequantize them against the per-output-
channel f32 scale IN REGISTER (VMEM -> vregs), immediately before the
MXU contraction:

    w_tile_f32 = w_tile_i8.astype(f32) * scale_tile      # in-register
    out_tile  += x_tile @ w_tile_f32                     # MXU, f32 acc

Activations stay bf16/f32 throughout; only the weight side is narrow.
Per-OUTPUT-channel scales make the dequant exact w.r.t. the contraction
(every element of an output column shares one scale), but the multiply
is applied BEFORE the dot — scaling the int32/f32 accumulator after the
contraction is mathematically equal yet not bitwise equal, and the
accuracy contract (KERNELS.md) is defined against the dequantize-first
oracle ``ref.quantized_matmul_ref``.

Layouts (matching the decode projections):

* ``transpose=False`` — ``w [K, N]`` int8, ``scale [1, N]``: the QKV/O
  and MLP projections and the untied lm head (``x @ dequant(w)``).
* ``transpose=True``  — ``w [N, K]`` int8, ``scale [N, 1]``: the tied
  embed table as the unembed (``x @ dequant(w).T``).

Grid is (row tiles x N tiles) with the full K width resident per tile
(decode K = d_model or d_ff — a [K, n_tile] int8 tile is K*n_tile bytes,
well inside VMEM at the sizes this repo serves). int8 min tile is
(32, 128): K and N pad to 128, rows to 8, all zero-padded (int8 zeros
dequantize to 0.0 and contribute nothing). Oracle:
``ref.quantized_matmul_ref``; dispatch: ``ops.quantized_matmul``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array


def _kernel(x_ref, w_ref, s_ref, o_ref, *, transpose: bool):
    x = x_ref[...].astype(jnp.float32)            # [rt, Kp]
    w = w_ref[...].astype(jnp.float32)            # [Kp, nt] / [nt, Kp]
    s = s_ref[...]                                # [1, nt] f32
    if transpose:
        w = w * s[0, :][:, None]                  # per-row scale
        out = jnp.dot(x, w.T, preferred_element_type=jnp.float32)
    else:
        w = w * s                                 # per-column scale
        out = jnp.dot(x, w, preferred_element_type=jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def quantized_matmul_pallas(x: Array, q: Array, scale: Array, *,
                            transpose: bool, row_tile: int = 8,
                            n_tile: int = 512,
                            interpret: bool = False) -> Array:
    """x [R, K] @ dequant(q, scale)[(.T)] -> [R, N] in ``x.dtype``.

    ``q`` int8 ``[K, N]`` (or ``[N, K]`` with ``transpose=True``);
    ``scale`` f32 with the contracted dim kept as size 1.
    """
    R, K = x.shape
    N = q.shape[0] if transpose else q.shape[1]
    svec = scale.reshape(1, N).astype(jnp.float32)
    rt = min(row_tile, -(-R // 8) * 8)
    Rp = -(-R // rt) * rt
    nt = min(n_tile, -(-N // 128) * 128)
    Np = -(-N // nt) * nt
    Kp = -(-K // 128) * 128
    nr, nn = Rp // rt, Np // nt

    x = jnp.pad(x, ((0, Rp - R), (0, Kp - K)))
    q = jnp.pad(q, ((0, Np - N), (0, Kp - K)) if transpose
                else ((0, Kp - K), (0, Np - N)))
    svec = jnp.pad(svec, ((0, 0), (0, Np - N)))

    w_spec = pl.BlockSpec((nt, Kp), lambda i, j: (j, 0)) if transpose \
        else pl.BlockSpec((Kp, nt), lambda i, j: (0, j))
    out = pl.pallas_call(
        functools.partial(_kernel, transpose=transpose),
        name="quantized_matmul",
        grid=(nr, nn),
        in_specs=[pl.BlockSpec((rt, Kp), lambda i, j: (i, 0)),
                  w_spec,
                  pl.BlockSpec((1, nt), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((rt, nt), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Rp, Np), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, q, svec)
    return out[:R, :N]
