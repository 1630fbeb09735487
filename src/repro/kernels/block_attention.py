"""Length-aware cached-block-attention Pallas-TPU kernel.

The diffusion hot spot: every denoising step the active block attends
[prefix cache ∥ fresh block ∥ (dual-cache suffix)] bidirectionally against a
KV cache buffer sized for the FULL sequence. The generic path masks dead
slots but still streams the whole ``[T, D]`` buffer through the MXU — at 25%
cache fill that is ~4x wasted HBM traffic and FLOPs on the op that dominates
Fast-dLLM-style decoding.

This kernel is purpose-built for ``model.block_step``:

* **Per-row scalar-prefetched block geometry** — each row's
  ``[slot, block_start, exc0, exc1, kv_limit]`` vector is scalar-prefetched
  as a ``[5, B]`` operand, so the BlockSpec index maps and ``pl.when``
  tile-liveness guards resolve EVERY row's own block geometry before any
  DMA is issued. The step-sliced decode loop's mixed-cursor batches (each
  row denoising its own cursor block) therefore stay on the fused Pallas
  path — uniform (scalar) calls are just the broadcast special case.
* **Length-aware tile skipping** — kv tiles entirely beyond a row's
  ``kv_limit`` are skipped via ``pl.when`` AND their BlockSpec index maps
  clamp to the row's last live tile, so revisited blocks issue no new DMA:
  zero FLOPs and zero HBM reads for the unfilled cache region. A retired
  row (``kv_limit == 0``) touches no cache tiles at all.
* **Native GQA** — queries are laid out ``[B, Kh, G*bs, D]`` so the whole
  q-group shares one kv head; no ``jnp.repeat`` materialisation of K/V.
* **Fresh-block operands** — the active block's K/V ride as separate
  ``[B, bs, Kh, D]`` inputs appended as extra kv tiles, so the step needs no
  pre-write of the cache (the generic path copies the whole cache buffer per
  layer per step just to insert the block). A sentinel write slot
  ``>= T`` (the sliced loop's finished rows) hides the fresh block, exactly
  like the XLA rows path's empty in-block window.
* **Exact ``block_step`` masking** — slot validity (``pos >= 0``), the
  dual-cache stale-slot ``[exc0, exc1)`` range, the sliding ``window``,
  and bidirectional attention within the block — all per row.

Because attention here is bidirectional ("full" mode) the mask depends only
on the KV side — every query row keeps the same columns — which is what lets
a single ``[kt]`` validity vector drive the whole tile.

The dense and paged layouts share ONE kernel body (``_attn_kernel``): the
paged variant only swaps the kv operand routing (pool pages resolved per
row through the scalar-prefetched page table) and adds the page-mapped
liveness term. Oracle: ``ref.cached_block_attention_ref`` /
``ref.paged_block_attention_ref`` (one shared core). Off-TPU the dispatch
in ``ops.py`` routes to the length-aware ``attend_flash`` path instead.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1.0e30

# rows of the [5, B] scalar-prefetch operand (one column per batch row)
SLOT, BSTART, EXC0, EXC1, KVLIM = range(5)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def kv_limit_from_pos(kv_pos: Array) -> Array:
    """Smallest bound such that every slot with ``pos >= 0`` lies below it.

    One [T] reduction — callers that track the fill (e.g. prefix-cache
    decoding, where it equals ``length``) can pass the bound directly.
    """
    ids1 = jnp.arange(kv_pos.shape[0], dtype=jnp.int32) + 1
    return jnp.max(jnp.where(kv_pos >= 0, ids1, 0))


def _acc_init(m_scr, l_scr, acc_scr, n_scr):
    """Reset the online-softmax scratch at the first kv tile."""
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    if n_scr is not None:
        n_scr[0] = 0


def _make_accumulate(q_ref, m_scr, l_scr, acc_scr, n_scr):
    """One online-softmax update over a kv tile for ALL kv heads at once —
    THE one definition of the flash-accumulator math, shared by the dense
    and paged bodies. ``k``/``v`` are [tile, Kh, D] in the cache's stored
    layout; the head axis is a batch dim of both contractions, so the
    tile is DMA'd whole and never transposed in HBM. ``valid`` is
    [1, tile] — kv-side only: "full" mode attention has no q-side mask.
    Contractions take their operands in the stored dtype and accumulate
    in float32."""
    q = q_ref[0]                                      # [Kh, qt, D]
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))

    def accumulate(k, v, valid):
        s = jnp.einsum("hqd,khd->hqk", q, k.astype(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        valid = valid[None]                           # [1, 1, tile]
        s = jnp.where(valid, s, NEG_INF)
        m_old = m_scr[...]                            # [Kh, qt, 1]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        # masked columns contribute exactly 0, so a row that no tile
        # validates keeps l == acc == 0 and outputs 0
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_old - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.einsum(
            "hqk,khd->hqd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        if n_scr is not None:
            n_scr[0] += 1

    return accumulate


def _acc_finish(o_ref, cnt_ref, m_scr, l_scr, acc_scr, n_scr):
    """Normalise and write the output tile (guarding fully-masked rows)."""
    l = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
    if cnt_ref is not None:
        cnt_ref[0, 0] = jnp.full(cnt_ref.shape[2:], n_scr[0], jnp.int32)


def _attn_kernel(s_ref, *args, paged: bool, nk: int, nkk: int, kt: int,
                 bt: int, bs: int, T: int, exclude: bool, window: int,
                 count_tiles: bool):
    """ONE body for the dense and paged layouts.

    ``s_ref`` is the [5, B] per-row scalar operand (rows SLOT..KVLIM);
    every mask term below reads row ``b = program_id(0)``'s own column, so
    mixed-cursor batches resolve their own geometry. The paged variant
    adds the page table (second prefetch operand) whose index maps routed
    the kv tile to this row's pool page, and gates tile liveness on the
    page being mapped. Each grid step covers every kv head of one
    (row, q tile, kv tile): the kv blocks span the whole ``Kh`` dim, which
    is what the TPU's (8, 128) tiling of the last two dims requires.
    """
    if paged:
        pt_ref, q_ref, ck_ref, cv_ref, bk_ref, bv_ref, pos_ref = args[:7]
        refs = args[7:]
    else:
        q_ref, ck_ref, cv_ref, bk_ref, bv_ref, pos_ref = args[:6]
        refs = args[6:]
    if count_tiles:
        o_ref, cnt_ref, m_scr, l_scr, acc_scr, n_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
        cnt_ref = n_scr = None
    b = pl.program_id(0)
    j = pl.program_id(2)
    slot = s_ref[SLOT, b]
    exc0 = s_ref[EXC0, b]
    exc1 = s_ref[EXC1, b]
    kv_limit = s_ref[KVLIM, b]

    @pl.when(j == 0)
    def _init():
        _acc_init(m_scr, l_scr, acc_scr, n_scr)

    accumulate = _make_accumulate(q_ref, m_scr, l_scr, acc_scr, n_scr)

    is_cache = j < nk
    tile_live = is_cache & ((j * kt) < kv_limit)
    if paged:
        jm = jnp.minimum(j, nk - 1)
        tile_live &= pt_ref[b, jm] >= 0

    @pl.when(tile_live)
    def _cache_tile():
        k = ck_ref[0]                               # [kt, Kh, D]
        v = cv_ref[0]
        if T % kt:
            # the last dense tile overhangs the buffer: zero the rows
            # past T so no out-of-bounds padding reaches the p @ v sum
            rid = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) + j * kt
            v = jnp.where(rid < T, v, jnp.zeros_like(v))
        pos = pos_ref[0]                            # [1, kt] int32
        ids = jax.lax.broadcasted_iota(jnp.int32, (1, kt), 1) + j * kt
        valid = (pos >= 0) & (ids < kv_limit) & (ids < T)
        # slots the fresh block virtually overwrites: stale, served by the
        # block operand instead
        valid &= ~((ids >= slot) & (ids < slot + bs))
        if exclude:
            valid &= ~((ids >= exc0) & (ids < exc1))
        if window:
            qmax = s_ref[BSTART, b] + bs - 1  # block's last absolute pos
            valid &= (qmax - pos) < window
        accumulate(k, v, valid)

    @pl.when(~is_cache)
    def _block_tile():
        jb = j - nk
        k = bk_ref[0]                               # [bt, Kh, D]
        v = bv_ref[0]
        r = jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1) + jb * bt
        # sentinel write slot >= T (sliced loop, finished rows): the fresh
        # block is invisible, matching the rows path's empty in-block window
        valid = (r < bs) & (slot + bs <= T)
        if exclude:
            ids = slot + r
            valid &= ~((ids >= exc0) & (ids < exc1))
        if window:
            valid &= (bs - 1 - r) < window
        accumulate(k, v, valid)

    @pl.when(j == nkk - 1)
    def _finish():
        _acc_finish(o_ref, cnt_ref, m_scr, l_scr, acc_scr, n_scr)


def _row_scalars(B: int, slot, block_start, exclude_start, kv_limit,
                 exclude_len: int) -> Array:
    """[5, B] int32 scalar-prefetch operand: each argument [] or [B] is
    broadcast to one per-row vector — the uniform (scalar) call is just
    the broadcast special case of the per-row layout."""
    def as_row(v):
        return jnp.broadcast_to(
            jnp.asarray(v, jnp.int32).reshape(-1), (B,))

    exc0 = as_row(exclude_start)
    return jnp.stack([as_row(slot), as_row(block_start), exc0,
                      exc0 + exclude_len, as_row(kv_limit)])


def _fold_q(q: Array, Kh: int, q_tile: int):
    """GQA layout: fold the q-group into rows so one kv head serves
    [G*bs, D]. Returns ``(qf [B, Kh, Rp, D], R, qt)``."""
    B, bs, H, D = q.shape
    G = H // Kh
    R = G * bs
    qt = min(q_tile, _round_up(R, 8))
    Rp = _round_up(R, qt)
    qf = q.reshape(B, bs, Kh, G, D).transpose(0, 2, 3, 1, 4)
    qf = qf.reshape(B, Kh, R, D)
    if Rp != R:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, Rp - R), (0, 0)))
    return qf, R, qt


def _pad_block(block_k: Array, block_v: Array, bt: int):
    """Zero-pad the fresh block's K/V to whole ``bt``-row tiles."""
    bs = block_k.shape[1]
    bsp = _round_up(bs, bt)
    if bsp != bs:
        pad = ((0, 0), (0, bsp - bs), (0, 0), (0, 0))
        block_k = jnp.pad(block_k, pad)
        block_v = jnp.pad(block_v, pad)
    return block_k, block_v, bsp // bt


def _attn_call(kernel, prefetch, qf, kv_k, kv_v, block_k, block_v, pos3, *,
               name: str, kv_index, live_m1, kt: int, bt: int, nk: int,
               qt: int, debug_tile_counts: bool, interpret: bool):
    """The ``pallas_call`` both layouts share. Grid ``(B, q tiles, kv
    tiles)``; every block spans all ``Kh`` heads and all ``D``, so the
    last two dims of each block are whole array dims. ``kv_index(b, j,
    *prefetch)`` gives the leading index of cache tile ``j`` (dense: the
    row, paged: the pool page) and ``live_m1`` the row's last live tile,
    where dead tiles clamp (no new DMA)."""
    B, Kh, Rp, D = qf.shape
    nq = Rp // qt
    nkk = pos3.shape[0] + block_k.shape[1] // bt
    out_shape = [jax.ShapeDtypeStruct((B, Kh, Rp, D), qf.dtype)]
    out_specs = [pl.BlockSpec((1, Kh, qt, D),
                              lambda b, i, j, *_: (b, 0, i, 0))]
    scratch = [pltpu.VMEM((Kh, qt, 1), jnp.float32),
               pltpu.VMEM((Kh, qt, 1), jnp.float32),
               pltpu.VMEM((Kh, qt, D), jnp.float32)]
    # the tile-count output exists only in debug mode — production calls
    # pay for exactly one output buffer
    if debug_tile_counts:
        out_shape.append(jax.ShapeDtypeStruct((B, nq, 1, 1), jnp.int32))
        out_specs.append(pl.BlockSpec((1, 1, 1, 1),
                                      lambda b, i, j, *_: (b, i, 0, 0)))
        scratch.append(pltpu.SMEM((1,), jnp.int32))
    kv_spec = pl.BlockSpec(
        (1, kt, Kh, D),
        lambda b, i, j, *pf: kv_index(b, jnp.minimum(j, live_m1(b, *pf)),
                                      *pf))
    blk_spec = pl.BlockSpec(
        (1, bt, Kh, D),
        lambda b, i, j, *_: (b, jnp.maximum(j - nk, 0), 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, nq, nkk),
        in_specs=[
            pl.BlockSpec((1, Kh, qt, D), lambda b, i, j, *_: (b, 0, i, 0)),
            kv_spec, kv_spec, blk_spec, blk_spec,
            pl.BlockSpec((1, 1, kt),
                         lambda b, i, j, *pf: (
                             jnp.minimum(j, live_m1(b, *pf)), 0, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, qf, kv_k, kv_v, block_k, block_v, pos3)


def _unfold_out(res, q_shape, R: int, debug_tile_counts: bool):
    """Undo ``_fold_q`` on the kernel output; in debug mode also return
    the per-(row, q tile) count of kv tiles processed, [B, nq]."""
    B, bs, H, D = q_shape
    Kh = res[0].shape[1]
    out = res[0][:, :, :R].reshape(B, Kh, H // Kh, bs, D)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, bs, H, D)
    if debug_tile_counts:
        return out, res[1][:, :, 0, 0]
    return out


def cached_block_attention_pallas(
        q: Array, cache_k: Array, cache_v: Array, block_k: Array,
        block_v: Array, kv_pos: Array, *, slot: Array, block_start: Array,
        kv_limit: Optional[Array] = None,
        exclude_start: Optional[Array] = None, exclude_len: int = 0,
        window: int = 0, q_tile: int = 128, kv_tile: int = 128,
        debug_tile_counts: bool = False, interpret: bool = False,
) -> Union[Array, Tuple[Array, Array]]:
    """Attention of the active block against the (virtually updated) cache.

    q        [B, bs, H, D]   block queries, RoPE applied
    cache_k/v [B, T, Kh, D]  KV cache for one layer, NOT pre-written
    block_k/v [B, bs, Kh, D] the block's fresh K/V (RoPE applied)
    kv_pos   [T] int32       absolute position per cache slot, -1 = empty
    slot     [] or [B] int32 cache slot the block would be written at;
                             a sentinel ``>= T`` hides the fresh block
                             (sliced-loop finished rows)
    block_start [] or [B]    absolute position of the block's first token
    kv_limit [] or [B] int32 slots >= kv_limit hold no valid entries — PER
                             ROW when rank 1 (a retired row passes 0 and
                             touches no cache tiles). Default: derived
                             from ``kv_pos`` (one [T] reduction)
    exclude_start/len        mask cache slots [start, start+len) per row
                             (dual-cache stale region); ``exclude_len`` is
                             static, ``exclude_start`` may be [B]
    window                   sliding window (0 = off), measured against the
                             block's LAST position as in ``block_step``

    Every block-geometry argument may be per-row [B]: the vectors ride as
    one [5, B] scalar-prefetch operand, so the index maps and liveness
    guards resolve each row's own geometry before DMA — the step-sliced
    mixed-cursor batches run this kernel natively (no XLA fallback).

    Semantics match ``model.block_step``'s attention exactly: the result
    equals writing the block at ``slot`` and attending the whole buffer with
    ``kv_valid`` masking. Returns [B, bs, H, D]; with
    ``debug_tile_counts=True`` also returns per-(B, q_tile) counts of kv
    tiles actually processed — the benchmark's HBM-traffic proxy.
    """
    B, bs, H, D = q.shape
    T, Kh = cache_k.shape[1], cache_k.shape[2]
    if kv_limit is None:
        kv_limit = kv_limit_from_pos(kv_pos)
    if exclude_start is None:
        exclude_start = jnp.zeros((), jnp.int32)
        exclude_len = 0

    qf, R, qt = _fold_q(q, Kh, q_tile)
    kt = min(kv_tile, _round_up(T, 8))
    nk = -(-T // kt)
    bt = min(kt, _round_up(bs, 8))
    block_k, block_v, nbk = _pad_block(block_k, block_v, bt)

    # positions as [nk, 1, kt] (padded with -1): one whole-dim block per
    # kv tile
    pos3 = jnp.pad(kv_pos.astype(jnp.int32), (0, nk * kt - T),
                   constant_values=-1).reshape(nk, 1, kt)
    scalars = _row_scalars(B, slot, block_start, exclude_start, kv_limit,
                           exclude_len)

    def live_m1(b, s):
        # last live cache tile of ROW b (index maps clamp dead tiles here:
        # revisiting the same block index issues no new DMA)
        return jnp.maximum(pl.cdiv(s[KVLIM, b], kt) - 1, 0)

    kernel = functools.partial(
        _attn_kernel, paged=False, nk=nk, nkk=nk + nbk, kt=kt, bt=bt,
        bs=bs, T=T, exclude=bool(exclude_len), window=window,
        count_tiles=debug_tile_counts)
    res = _attn_call(
        kernel, (scalars,), qf, cache_k, cache_v, block_k, block_v, pos3,
        name="block_attention", kv_index=lambda b, jc, s: (b, jc, 0, 0), live_m1=live_m1, kt=kt,
        bt=bt, nk=nk, qt=qt, debug_tile_counts=debug_tile_counts,
        interpret=interpret)
    return _unfold_out(res, q.shape, R, debug_tile_counts)


# ---------------------------------------------------------------------------
# paged variant: page-table indirection via scalar prefetch
# ---------------------------------------------------------------------------

def paged_block_attention_pallas(
        q: Array, pool_k: Array, pool_v: Array, block_k: Array,
        block_v: Array, kv_pos: Array, page_table: Array, *, slot: Array,
        block_start: Array, kv_limit: Optional[Array] = None,
        exclude_start: Optional[Array] = None, exclude_len: int = 0,
        window: int = 0, debug_tile_counts: bool = False,
        interpret: bool = False) -> Union[Array, Tuple[Array, Array]]:
    """Block attention against a PAGED cache: pool pages are DMA'd
    directly — the dense [B, T] view is never materialised.

    q         [B, bs, H, D]    block queries, RoPE applied
    pool_k/v  [P, ps, Kh, D]   page pool for one layer (no batch dim!)
    block_k/v [B, bs, Kh, D]   the block's fresh K/V
    kv_pos    [T] int32        logical-slot positions (shared across rows)
    page_table[B, n_log] int32 physical page per (row, logical page);
                               -1 = unmapped (dead row / reclaimed)
    slot / block_start / exclude_start / kv_limit — each [] or PER-ROW
    [B], exactly as the dense kernel: the [5, B] scalar-prefetch operand
    carries every row's own block geometry, so mixed-cursor slices run
    the paged kernel natively. A retired row passes ``kv_limit = 0`` and
    its still-mapped tail pages stop being touched *within* the batch
    (the fresh-block tile stays live unless the row's write slot is the
    ``>= T`` sentinel, so ride-along mask flushes keep working).

    The page table rides as a second scalar-prefetch operand, so the kv
    BlockSpec index maps resolve (row, logical page) → physical pool page
    before the tile's DMA is issued; tiles that are beyond the row's
    ``kv_limit`` OR unmapped clamp to the row's last live page (no new
    DMA) and skip compute via ``pl.when`` — the paged mirror of the dense
    kernel's ``kv_limit`` mechanism, which additionally skips *holes*
    (dead rows, reclaimed pages), not just the tail. One kv tile == one
    page, so ``page_size`` must be a multiple of 8 (float32 sublane
    tiling).
    """
    B, bs, H, D = q.shape
    ps, Kh = pool_k.shape[1], pool_k.shape[2]
    T = kv_pos.shape[0]
    n_log = page_table.shape[1]
    assert n_log * ps >= T, (n_log, ps, T)
    assert ps % 8 == 0, f"page_size {ps} must be a multiple of 8"
    if kv_limit is None:
        kv_limit = kv_limit_from_pos(kv_pos)
    if exclude_start is None:
        exclude_start = jnp.zeros((), jnp.int32)
        exclude_len = 0

    qf, R, qt = _fold_q(q, Kh, 128)
    bt = min(ps, _round_up(bs, 8))
    block_k, block_v, nbk = _pad_block(block_k, block_v, bt)

    # positions as [n_log, 1, ps] (padded with -1): one block per page
    pos3 = jnp.pad(kv_pos.astype(jnp.int32), (0, n_log * ps - T),
                   constant_values=-1).reshape(n_log, 1, ps)
    scalars = _row_scalars(B, slot, block_start, exclude_start, kv_limit,
                           exclude_len)
    pt = page_table.astype(jnp.int32)

    def live_m1(b, s, pt):
        # last live tile of ROW b (per-row kv_limit)
        return jnp.maximum(pl.cdiv(s[KVLIM, b], ps) - 1, 0)

    def page_for(b, jc, s, pt):
        # route (clamped) tile jc of row b to its pool page; dead or
        # unmapped tiles land on a page already fetched, so the revisited
        # block index issues no new DMA (compute is skipped by tile_live)
        return (jnp.maximum(pt[b, jc], 0), 0, 0, 0)

    kernel = functools.partial(
        _attn_kernel, paged=True, nk=n_log, nkk=n_log + nbk, kt=ps, bt=bt,
        bs=bs, T=T, exclude=bool(exclude_len), window=window,
        count_tiles=debug_tile_counts)
    res = _attn_call(
        kernel, (scalars, pt), qf, pool_k, pool_v, block_k, block_v, pos3,
        name="paged_block_attention", kv_index=page_for, live_m1=live_m1, kt=ps, bt=bt, nk=n_log, qt=qt,
        debug_tile_counts=debug_tile_counts, interpret=interpret)
    return _unfold_out(res, q.shape, R, debug_tile_counts)
