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
  as a ``[5, B]`` operand, so the index maps, DMA addresses and ``pl.when``
  liveness guards resolve EVERY row's own block geometry before any DMA is
  issued. The step-sliced decode loop's mixed-cursor batches (each row
  denoising its own cursor block) therefore stay on the fused Pallas path
  — uniform (scalar) calls are just the broadcast special case.
* **Length-aware tile skipping** — kv tiles entirely beyond a row's
  ``kv_limit`` are skipped via ``pl.when`` and fetch nothing: zero FLOPs
  and zero HBM reads for the unfilled cache region. A retired row
  (``kv_limit == 0``) touches no cache tiles at all.
* **Native GQA** — queries are laid out ``[B, Kh, G*bs, D]`` so the whole
  q-group shares one kv head; no ``jnp.repeat`` materialisation of K/V.
* **Fresh-block operands** — the active block's K/V ride as separate
  ``[B, bs, Kh, D]`` inputs, one extra kv tile, so the step needs no
  pre-write of the cache (the generic path copies the whole cache buffer
  per layer per step just to insert the block). A sentinel write slot
  ``>= T`` (the sliced loop's finished rows) hides the fresh block, exactly
  like the XLA rows path's empty in-block window.
* **Exact ``block_step`` masking** — slot validity (``pos >= 0``), the
  dual-cache stale-slot ``[exc0, exc1)`` range, the sliding ``window``,
  and bidirectional attention within the block — all per row.

Because attention here is bidirectional ("full" mode) the mask depends only
on the KV side — every query row keeps the same columns — which is what lets
a single ``[tile]`` validity vector drive the whole tile.

The two layouts differ in how a kv tile reaches VMEM. The dense kernel's
tile is a BlockSpec slab of the row's ``[T, Kh, D]`` buffer (``kt`` = 128
slots, dead tiles clamped to the row's last live one, so they issue no
DMA). The paged kernel's tile is a COMPUTE BLOCK of ``ppb`` pool pages:
the pool stays in HBM (``pl.ANY``) and the kernel DMAs each live page of
block ``c`` (row ``b``'s pages ``pt[b, c*ppb : (c+1)*ppb]``) into one slot
of a double-buffered ``[2, ppb*ps, Kh, D]`` VMEM buffer, starting block
``c+1``'s copies before it computes block ``c``. Pages past the row's
``kv_limit`` and unmapped pages (``pt < 0``) are neither fetched nor
computed, and a block with no live page is skipped whole; the V rows of
a live block's unfetched pages are zeroed, since stale VMEM may hold NaN
and ``0 * NaN`` would poison ``p @ v``. ``ppb`` comes from shapes alone
(``_pages_per_block``): 128 keys a block, at most the row's page count,
halved until the kernel's VMEM fits — so a per-step cost (pipeline
overhead, the online-softmax state's rescale, a 128-lane ``exp``) is paid
once per 128 keys, not once per 16-token page. Both bodies share the
online-softmax math (``_make_accumulate``, ``_acc_init``/``_acc_finish``)
and the mask terms. Oracle: ``ref.cached_block_attention_ref`` /
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

# keys per paged compute block: one 128-lane row of scores
BLOCK_KEYS = 128
# VMEM the paged kernel may plan for (v5e has 128 MiB, the scoped default
# is 16), and the limit it asks the compiler for, with room for the
# compiler's own temporaries
VMEM_BUDGET = 48 * 2**20
VMEM_LIMIT = 64 * 2**20


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
        for i in range(n_scr.shape[0]):
            n_scr[i] = 0


def _count(n_scr, *incs):
    """Debug counters: add ``incs[i]`` to counter ``i``."""
    if n_scr is not None:
        for i, inc in enumerate(incs):
            n_scr[i] += inc


def _make_accumulate(q_ref, m_scr, l_scr, acc_scr):
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

    return accumulate


def _acc_finish(o_ref, cnt_ref, m_scr, l_scr, acc_scr, n_scr):
    """Normalise and write the output tile (guarding fully-masked rows);
    in debug mode also write the counters."""
    l = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
    if cnt_ref is not None:
        col = jax.lax.broadcasted_iota(jnp.int32, cnt_ref.shape[2:], 1)
        cnt = jnp.zeros(cnt_ref.shape[2:], jnp.int32)
        for i in range(n_scr.shape[0]):
            cnt = jnp.where(col == i, n_scr[i], cnt)
        cnt_ref[0, 0] = cnt


def _cache_valid(s_ref, b, pos, ids, *, bs: int, T: int, exclude: bool,
                 window: int):
    """[1, tile] validity of cache slots ``ids`` (positions ``pos``) for
    row ``b``: filled, below the row's ``kv_limit``, not among the slots
    the fresh block virtually overwrites (stale, served by the block
    operand instead), outside the exclusion and inside the window."""
    slot = s_ref[SLOT, b]
    valid = (pos >= 0) & (ids < s_ref[KVLIM, b]) & (ids < T)
    valid &= ~((ids >= slot) & (ids < slot + bs))
    if exclude:
        valid &= ~((ids >= s_ref[EXC0, b]) & (ids < s_ref[EXC1, b]))
    if window:
        qmax = s_ref[BSTART, b] + bs - 1  # block's last absolute pos
        valid &= (qmax - pos) < window
    return valid


def _fresh_tile(s_ref, b, bk_ref, bv_ref, jb, accumulate, *, bt: int,
                bs: int, T: int, exclude: bool, window: int):
    """Attend fresh-block tile ``jb`` (rows ``[jb*bt, (jb+1)*bt)``)."""
    slot = s_ref[SLOT, b]
    r = jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1) + jb * bt
    # sentinel write slot >= T (sliced loop, finished rows): the fresh
    # block is invisible, matching the rows path's empty in-block window
    valid = (r < bs) & (slot + bs <= T)
    if exclude:
        ids = slot + r
        valid &= ~((ids >= s_ref[EXC0, b]) & (ids < s_ref[EXC1, b]))
    if window:
        valid &= (bs - 1 - r) < window
    accumulate(bk_ref[0], bv_ref[0], valid)           # [bt, Kh, D] each


def _split_refs(refs, count_tiles: bool):
    """(o_ref, cnt_ref, m, l, acc, layout scratch, n_scr) from the output
    and scratch refs; ``cnt_ref``/``n_scr`` are None outside debug mode."""
    if count_tiles:
        o_ref, cnt_ref, *scr = refs
        n_scr = scr.pop()
    else:
        (o_ref, *scr), cnt_ref, n_scr = refs, None, None
    return (o_ref, cnt_ref, *scr[:3], scr[3:], n_scr)


def _dense_kernel(s_ref, q_ref, ck_ref, cv_ref, bk_ref, bv_ref, pos_ref,
                  *refs, nk: int, nkk: int, kt: int, bt: int, bs: int,
                  T: int, exclude: bool, window: int, count_tiles: bool):
    """Dense layout: grid step ``j < nk`` is cache tile ``j`` (a BlockSpec
    slab of row ``b``'s buffer), the rest are fresh-block tiles.

    ``s_ref`` is the [5, B] per-row scalar operand (rows SLOT..KVLIM);
    every mask term reads row ``b = program_id(0)``'s own column, so
    mixed-cursor batches resolve their own geometry. Each grid step covers
    every kv head of one (row, q tile, kv tile): the kv blocks span the
    whole ``Kh`` dim, which is what the TPU's (8, 128) tiling of the last
    two dims requires.
    """
    o_ref, cnt_ref, m_scr, l_scr, acc_scr, _, n_scr = _split_refs(
        refs, count_tiles)
    b = pl.program_id(0)
    j = pl.program_id(2)
    geom = dict(bs=bs, T=T, exclude=exclude, window=window)

    @pl.when(j == 0)
    def _init():
        _acc_init(m_scr, l_scr, acc_scr, n_scr)

    accumulate = _make_accumulate(q_ref, m_scr, l_scr, acc_scr)
    is_cache = j < nk

    @pl.when(is_cache & ((j * kt) < s_ref[KVLIM, b]))
    def _cache_tile():
        v = cv_ref[0]                               # [kt, Kh, D]
        if T % kt:
            # the last dense tile overhangs the buffer: zero the rows
            # past T so no out-of-bounds padding reaches the p @ v sum
            rid = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) + j * kt
            v = jnp.where(rid < T, v, jnp.zeros_like(v))
        ids = jax.lax.broadcasted_iota(jnp.int32, (1, kt), 1) + j * kt
        accumulate(ck_ref[0], v,
                   _cache_valid(s_ref, b, pos_ref[0], ids, **geom))
        _count(n_scr, 1)

    @pl.when(~is_cache)
    def _block_tile():
        _fresh_tile(s_ref, b, bk_ref, bv_ref, j - nk, accumulate, bt=bt,
                    **geom)
        _count(n_scr, 1)

    @pl.when(j == nkk - 1)
    def _finish():
        _acc_finish(o_ref, cnt_ref, m_scr, l_scr, acc_scr, n_scr)


def _paged_kernel(s_ref, pt_ref, q_ref, pool_k, pool_v, bk_ref, bv_ref,
                  pos_ref, *refs, ppb: int, ps: int, n_log: int, nkb: int,
                  bt: int, bs: int, T: int, exclude: bool, window: int,
                  count_tiles: bool):
    """Paged layout: grid step 0 is the fresh block, step ``j >= 1`` is
    compute block ``c = j - 1`` of ``ppb`` pages, fetched by hand.

    ``pool_k``/``pool_v`` stay in HBM. Step 0 starts block 0's page
    copies and attends the fresh block meanwhile; step ``j`` starts block
    ``j``'s copies into the other buffer slot, then waits for block
    ``c``'s and attends it. Every started copy is waited for in the
    step of its block: both use the same liveness test, so the DMA
    semaphores balance whatever the row's holes and limit. Debug counters:
    live mapped pages plus the block tile, and kv grid steps that did work.
    """
    o_ref, cnt_ref, m_scr, l_scr, acc_scr, (kbuf, vbuf, sem), n_scr = (
        _split_refs(refs, count_tiles))
    b = pl.program_id(0)
    j = pl.program_id(2)
    bk = ppb * ps
    kv_limit = s_ref[KVLIM, b]
    geom = dict(bs=bs, T=T, exclude=exclude, window=window)
    accumulate = _make_accumulate(q_ref, m_scr, l_scr, acc_scr)

    def page(c, i):
        # (live, physical page) of page i of row b's block c: mapped,
        # inside the table and starting below the row's kv_limit
        lp = c * ppb + i
        phys = pt_ref[b, jnp.minimum(lp, n_log - 1)]
        live = (phys >= 0) & (lp * ps < kv_limit)
        if nkb * ppb > n_log:
            live &= lp < n_log
        return live, phys

    def copies(slot, i, phys):
        dst = pl.ds(i * ps, ps)
        return (pltpu.make_async_copy(pool_k.at[phys], kbuf.at[slot, dst],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(pool_v.at[phys], vbuf.at[slot, dst],
                                      sem.at[1, slot]))

    def fetch(c):
        for i in range(ppb):
            live, phys = page(c, i)

            @pl.when(live)
            def _start():
                for cp in copies(jax.lax.rem(c, 2), i, phys):
                    cp.start()

    @pl.when(j == 0)
    def _fresh():
        _acc_init(m_scr, l_scr, acc_scr, n_scr)
        fetch(jnp.int32(0))
        _fresh_tile(s_ref, b, bk_ref, bv_ref, 0, accumulate, bt=bt, **geom)
        _count(n_scr, 1, 1)

    @pl.when((j > 0) & (j < nkb))
    def _prefetch():
        fetch(j)

    c = jnp.maximum(j - 1, 0)
    pages = [page(c, i) for i in range(ppb)]
    n_live = sum(live.astype(jnp.int32) for live, _ in pages)

    @pl.when((j > 0) & (n_live > 0))
    def _cache_block():
        slot = jax.lax.rem(c, 2)
        pid = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        fetched = jnp.zeros((1, bk), jnp.int32)
        for i, (live, phys) in enumerate(pages):
            @pl.when(live)
            def _wait():
                for cp in copies(slot, i, phys):
                    cp.wait()

            @pl.when(~live)
            def _zero():
                vbuf[slot, pl.ds(i * ps, ps)] = jnp.zeros(
                    (ps,) + vbuf.shape[2:], vbuf.dtype)

            fetched = jnp.where((pid >= i * ps) & (pid < (i + 1) * ps),
                                live.astype(jnp.int32), fetched)
        valid = (fetched > 0) & _cache_valid(s_ref, b, pos_ref[c], pid + c * bk,
                                       **geom)
        accumulate(kbuf[slot], vbuf[slot], valid)
        _count(n_scr, n_live, 1)

    @pl.when(j == nkb)
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
               name: str, kv_spec, pos_spec, blk_index, scratch, nkk: int,
               bt: int, qt: int, n_counts: int, vmem_limit: Optional[int],
               interpret: bool):
    """The ``pallas_call`` both layouts share. Grid ``(B, q tiles, kv
    steps)``; every block spans all ``Kh`` heads and all ``D``, so the
    last two dims of each block are whole array dims. The layout supplies
    the kv and position specs, the fresh block's index map and its own
    scratch; in debug mode an extra output carries ``n_counts`` counters
    per (row, q tile)."""
    B, Kh, Rp, D = qf.shape
    nq = Rp // qt
    out_shape = [jax.ShapeDtypeStruct((B, Kh, Rp, D), qf.dtype)]
    out_specs = [pl.BlockSpec((1, Kh, qt, D),
                              lambda b, i, j, *_: (b, 0, i, 0))]
    scratch = [pltpu.VMEM((Kh, qt, 1), jnp.float32),
               pltpu.VMEM((Kh, qt, 1), jnp.float32),
               pltpu.VMEM((Kh, qt, D), jnp.float32)] + scratch
    # the counter output exists only in debug mode — production calls
    # pay for exactly one output buffer
    if n_counts:
        out_shape.append(
            jax.ShapeDtypeStruct((B, nq, 1, n_counts), jnp.int32))
        out_specs.append(pl.BlockSpec((1, 1, 1, n_counts),
                                      lambda b, i, j, *_: (b, i, 0, 0)))
        scratch.append(pltpu.SMEM((n_counts,), jnp.int32))
    blk_spec = pl.BlockSpec((1, bt, Kh, D), blk_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, nq, nkk),
        in_specs=[
            pl.BlockSpec((1, Kh, qt, D), lambda b, i, j, *_: (b, 0, i, 0)),
            kv_spec, kv_spec, blk_spec, blk_spec, pos_spec,
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
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(*prefetch, qf, kv_k, kv_v, block_k, block_v, pos3)


def _unfold_out(res, q_shape, R: int):
    """Undo ``_fold_q`` on the kernel output; in debug mode also return
    each per-(row, q tile) counter, [B, nq] each."""
    B, bs, H, D = q_shape
    Kh = res[0].shape[1]
    out = res[0][:, :, :R].reshape(B, Kh, H // Kh, bs, D)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, bs, H, D)
    if len(res) == 1:
        return out
    return (out,) + tuple(res[1][:, :, 0, i] for i in range(res[1].shape[3]))


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

    def tile(b, j, s):
        # clamp dead tiles to ROW b's last live tile: revisiting the same
        # block index issues no new DMA
        return jnp.minimum(j, jnp.maximum(pl.cdiv(s[KVLIM, b], kt) - 1, 0))

    kernel = functools.partial(
        _dense_kernel, nk=nk, nkk=nk + nbk, kt=kt, bt=bt, bs=bs, T=T,
        exclude=bool(exclude_len), window=window,
        count_tiles=debug_tile_counts)
    res = _attn_call(
        kernel, (scalars,), qf, cache_k, cache_v, block_k, block_v, pos3,
        name="block_attention",
        kv_spec=pl.BlockSpec((1, kt, Kh, D),
                             lambda b, i, j, s: (b, tile(b, j, s), 0, 0)),
        pos_spec=pl.BlockSpec((1, 1, kt),
                              lambda b, i, j, s: (tile(b, j, s), 0, 0)),
        blk_index=lambda b, i, j, *_: (b, jnp.maximum(j - nk, 0), 0, 0),
        scratch=[], nkk=nk + nbk, bt=bt, qt=qt,
        n_counts=int(debug_tile_counts), vmem_limit=None,
        interpret=interpret)
    return _unfold_out(res, q.shape, R)


# ---------------------------------------------------------------------------
# paged variant: multi-page compute blocks fetched by hand
# ---------------------------------------------------------------------------

def _paged_vmem_bytes(ppb: int, ps: int, n_log: int, Kh: int, qt: int,
                      D: int, bt: int, itemsize: int) -> int:
    """VMEM the paged kernel holds at ``ppb`` pages a block: the kv double
    buffer, the pipeline's double-buffered q, output and fresh-block
    tiles, the online-softmax state, the positions, and the block's
    temporaries (scores, probabilities, the head-major copies of K/V)."""
    bk = ppb * ps
    khp = _round_up(Kh, 8 * (4 // itemsize))          # packed sublanes
    kv = 2 * 2 * bk * khp * D * itemsize
    q_out = 2 * 2 * Kh * qt * D * itemsize
    fresh = 2 * 2 * bt * khp * D * itemsize
    state = Kh * qt * D * 4 + 2 * Kh * qt * 128 * 4  # acc, m and l
    pos = 2 * -(-n_log // ppb) * 8 * bk * 4
    temps = 3 * Kh * qt * bk * 4 + 2 * Kh * bk * D * itemsize
    return kv + q_out + fresh + state + pos + temps


def _pages_per_block(ps: int, n_log: int, Kh: int, qt: int, D: int,
                     bt: int, itemsize: int) -> int:
    """Pages per compute block, from shapes alone: ``BLOCK_KEYS`` keys,
    at most the row's ``n_log`` pages, halved until the VMEM fits."""
    ppb = min(max(1, BLOCK_KEYS // ps), n_log)
    while ppb > 1 and _paged_vmem_bytes(ppb, ps, n_log, Kh, qt, D, bt,
                                        itemsize) > VMEM_BUDGET:
        ppb //= 2
    return ppb


def paged_block_attention_pallas(
        q: Array, pool_k: Array, pool_v: Array, block_k: Array,
        block_v: Array, kv_pos: Array, page_table: Array, *, slot: Array,
        block_start: Array, kv_limit: Optional[Array] = None,
        exclude_start: Optional[Array] = None, exclude_len: int = 0,
        window: int = 0, debug_tile_counts: bool = False,
        interpret: bool = False
) -> Union[Array, Tuple[Array, Array, Array]]:
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

    The page table rides as a second scalar-prefetch operand, so the
    kernel resolves (row, logical page) → physical pool page before it
    issues a page's DMA. One kv grid step attends a compute block of
    ``ppb`` pages (``_pages_per_block``: 8 pages of 16 tokens), fetched
    page by page into a double-buffered VMEM slab; pages beyond the row's
    ``kv_limit`` OR unmapped are neither fetched nor computed, and a block
    with no live page is skipped — the paged mirror of the dense kernel's
    ``kv_limit`` mechanism, which additionally skips *holes* (dead rows,
    reclaimed pages), not just the tail. ``page_size`` must be a multiple
    of 8 (float32 sublane tiling). The fresh block is one tile of
    ``round_up(bs, 8)`` rows.

    With ``debug_tile_counts=True`` also returns two per-(B, q_tile)
    counters: live mapped pages plus the fresh-block tile (the HBM-traffic
    proxy, as the dense kernel's tile count), and the kv grid steps that
    did work (the fresh block plus each block with a live page).
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
    bt = _round_up(bs, 8)
    block_k, block_v, _ = _pad_block(block_k, block_v, bt)
    ppb = _pages_per_block(ps, n_log, Kh, qt, D, bt, pool_k.dtype.itemsize)
    bk = ppb * ps
    nkb = -(-n_log // ppb)

    # positions as [nkb, 1, bk] (padded with -1), resident in VMEM whole:
    # one [1, bk] row per compute block
    pos3 = jnp.pad(kv_pos.astype(jnp.int32), (0, nkb * bk - T),
                   constant_values=-1).reshape(nkb, 1, bk)
    scalars = _row_scalars(B, slot, block_start, exclude_start, kv_limit,
                           exclude_len)

    kernel = functools.partial(
        _paged_kernel, ppb=ppb, ps=ps, n_log=n_log, nkb=nkb, bt=bt, bs=bs,
        T=T, exclude=bool(exclude_len), window=window,
        count_tiles=debug_tile_counts)
    res = _attn_call(
        kernel, (scalars, page_table.astype(jnp.int32)), qf, pool_k, pool_v,
        block_k, block_v, pos3, name="paged_block_attention",
        kv_spec=pl.BlockSpec(memory_space=pl.ANY),
        pos_spec=pl.BlockSpec((nkb, 1, bk), lambda b, i, j, *_: (0, 0, 0)),
        blk_index=lambda b, i, j, *_: (b, 0, 0, 0),
        scratch=[pltpu.VMEM((2, bk, Kh, D), pool_k.dtype),
                 pltpu.VMEM((2, bk, Kh, D), pool_v.dtype),
                 pltpu.SemaphoreType.DMA((2, 2))],
        nkk=nkb + 1, bt=bt, qt=qt, n_counts=2 * int(debug_tile_counts),
        vmem_limit=VMEM_LIMIT, interpret=interpret)
    return _unfold_out(res, q.shape, R)
