"""Paged KV-cache subsystem (SERVING.md "Paged KV"): allocator semantics,
gather/scatter round-trips, the paged Pallas kernel vs its oracle, paged
vs dense token identity across cache modes x attention impls, shared-prefix
refcount/copy-on-write correctness, and page-reclaim accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import DecodeConfig, EngineConfig
from repro.config.registry import get_config
from repro.core.decoder import make_generate_fn
from repro.data import tokenizer as tok
from repro.kernels import ref
from repro.kernels.block_attention import (BLOCK_KEYS,
                                           paged_block_attention_pallas)
from repro.models import cache as cache_lib
from repro.models import model as M
from repro.serving.engine import DiffusionEngine
from repro.serving.scheduler import Request, Scheduler

PS = 8  # page size under test (kernel floor: multiples of 8)
DCFG_DENSE = DecodeConfig(max_new_tokens=16, block_size=4, policy="osdt",
                          mode="block", metric="q1", cap=0.9, slack=0.1,
                          threshold=0.9)
DCFG_PAGED = DecodeConfig(max_new_tokens=16, block_size=4, policy="osdt",
                          mode="block", metric="q1", cap=0.9, slack=0.1,
                          threshold=0.9, cache_layout="paged", page_size=PS)
PROMPT_LEN = 16


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("llada-8b").reduced()
    return cfg, M.init_params(jax.random.key(0), cfg)


def _pool(cfg, num_pages, dtype=jnp.float32):
    L, Kh, D = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    return (jnp.zeros((L, num_pages, PS, Kh, D), dtype),
            jnp.zeros((L, num_pages, PS, Kh, D), dtype))


# ---------------------------------------------------------------------------
# allocator: free list, refcounts, reclaim
# ---------------------------------------------------------------------------

def test_allocator_free_list_and_refcounts():
    a = cache_lib.PageAllocator(8)
    own = a.alloc(3)
    assert a.in_use == 3 and sorted(own) == sorted(set(own))
    a.share(own)                      # second owner of the same pages
    a.free(own)
    assert a.in_use == 3              # still referenced once
    a.free(own)
    assert a.in_use == 0 and a.available == 8
    with pytest.raises(ValueError):
        a.free(own)                   # double free detected
    with pytest.raises(MemoryError):
        a.alloc(9)                    # exceeds capacity
    # freed pages are reusable
    again = a.alloc(8)
    assert sorted(again) == list(range(8))


def _ledger(a):
    return list(a._refs), list(a._free)


def test_share_raising_midway_leaves_ledger_untouched():
    a = cache_lib.PageAllocator(8)
    own = a.alloc(3)
    stale = a.alloc(1)
    a.free(stale)                       # refcount 0: unshareable
    before = _ledger(a)
    with pytest.raises(ValueError):
        # the bad page sits LAST: a non-atomic share would bump the two
        # valid pages before raising and leak both references
        a.share(own[:2] + stale)
    assert _ledger(a) == before
    a.free(own)
    assert a.available == 8


def test_free_with_duplicate_page_leaves_ledger_untouched():
    a = cache_lib.PageAllocator(8)
    own = a.alloc(2)
    before = _ledger(a)
    with pytest.raises(ValueError):
        # duplicate inside ONE call: each page holds a single reference,
        # so the second drop is a double free even though the first
        # would have succeeded
        a.free([own[0], own[1], own[0]])
    assert _ledger(a) == before
    a.free(own)
    assert a.available == 8


def test_fork_exhaustion_midway_leaves_ledger_untouched():
    a = cache_lib.PageAllocator(8)
    shared = a.alloc(3)
    a.alloc(4)                          # only 1 page left
    before = _ledger(a)
    with pytest.raises(MemoryError):
        a.fork(shared, 2)               # private alloc cannot be met
    assert _ledger(a) == before
    assert all(a.refcount(p) == 1 for p in shared)


# ---------------------------------------------------------------------------
# gather / scatter round-trip through arbitrary page tables
# ---------------------------------------------------------------------------

def test_paged_write_gather_roundtrip():
    rng = np.random.default_rng(0)
    B, T, Kh, D = 3, 24, 2, 4
    n_log, num_pages = T // PS, 11
    # scrambled private mapping + one unmapped row
    pages = rng.permutation(num_pages)[: 2 * n_log]
    pt = np.full((B, n_log), -1, np.int32)
    pt[0], pt[2] = pages[:n_log], pages[n_log:]
    pt = jnp.asarray(pt)
    pool_k = jnp.zeros((num_pages, PS, Kh, D))
    pool_v = jnp.zeros((num_pages, PS, Kh, D))
    k = jnp.asarray(rng.standard_normal((B, 10, Kh, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, 10, Kh, D)), jnp.float32)
    start = jnp.asarray(5, jnp.int32)  # straddles page boundaries
    pool_k, pool_v = cache_lib.paged_kv_write(pool_k, pool_v, k, v, pt,
                                              start, page_size=PS)
    gk, gv, mapped = cache_lib.paged_kv_gather(pool_k, pool_v, pt, T,
                                               page_size=PS)
    for b in (0, 2):
        np.testing.assert_array_equal(np.asarray(gk)[b, 5:15],
                                      np.asarray(k)[b])
        np.testing.assert_array_equal(np.asarray(gv)[b, 5:15],
                                      np.asarray(v)[b])
        assert np.asarray(mapped)[b].all()
    # the unmapped row dropped its writes and reports unmapped
    assert not np.asarray(mapped)[1].any()
    assert (np.asarray(gk)[1] == np.asarray(gk)[1]).all()  # finite reads


def test_paged_prefill_layers_matches_dense(small_model):
    """M.prefill through an external paged cache must store exactly the
    K/V a dense prefill stores, page-scattered."""
    cfg, params = small_model
    B, P, max_len = 2, PROMPT_LEN, PROMPT_LEN + 16
    prompt = jax.random.randint(jax.random.key(1), (B, P), 1, 256)
    _, dense = M.prefill(params, cfg, prompt, max_len=max_len, mode="full")
    n_log = -(-max_len // PS)
    pt = cache_lib.identity_page_table(B, max_len, PS)
    pool_k, pool_v = _pool(cfg, B * n_log)
    cache = {"attn": {"kp": pool_k, "vp": pool_v, "pt": pt,
                      "pos": jnp.full((max_len,), -1, jnp.int32),
                      "length": jnp.zeros((), jnp.int32)}}
    _, paged = M.prefill(params, cfg, prompt, max_len=max_len, mode="full",
                         cache=cache, page_size=PS)
    kv = paged["attn"]
    gk, gv, _ = cache_lib.paged_kv_gather(kv["kp"][0], kv["vp"][0],
                                          kv["pt"], max_len, page_size=PS)
    np.testing.assert_array_equal(np.asarray(gk)[:, :P],
                                  np.asarray(dense["attn"]["k"][0])[:, :P])
    np.testing.assert_array_equal(np.asarray(gv)[:, :P],
                                  np.asarray(dense["attn"]["v"][0])[:, :P])
    np.testing.assert_array_equal(np.asarray(kv["pos"]),
                                  np.asarray(dense["attn"]["pos"]))
    assert int(kv["length"]) == P


# ---------------------------------------------------------------------------
# paged Pallas kernel vs oracle (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.paged
@pytest.mark.parametrize("fill,holes,exclude_len,window", [
    (8, False, 0, 0),
    (20, False, 0, 0),
    (20, True, 0, 0),
    (20, False, 4, 0),
    (20, False, 0, 12),
    (36, True, 4, 0),  # slot + bs == T: the fullest in-contract cache
])
def test_paged_kernel_matches_oracle(fill, holes, exclude_len, window):
    rng = np.random.default_rng(fill + exclude_len + window)
    B, bs, H, Kh, D = 2, 8, 8, 2, 32
    T, n_log = 44, 6
    num_pages = B * n_log + 3
    q = jnp.asarray(rng.standard_normal((B, bs, H, D)), jnp.float32)
    pool_k = jnp.asarray(rng.standard_normal((num_pages, PS, Kh, D)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((num_pages, PS, Kh, D)),
                         jnp.float32)
    bk = jnp.asarray(rng.standard_normal((B, bs, Kh, D)), jnp.float32)
    bv = jnp.asarray(rng.standard_normal((B, bs, Kh, D)), jnp.float32)
    kv_pos = jnp.where(jnp.arange(T) < fill, jnp.arange(T), -1)
    kv_pos = kv_pos.astype(jnp.int32)
    perm = rng.permutation(num_pages)
    pt = np.stack([perm[:n_log], perm[n_log:2 * n_log]]).astype(np.int32)
    if holes:
        pt[1, 2] = -1  # a reclaimed page inside the valid extent
    pt = jnp.asarray(pt)
    slot = jnp.asarray(fill, jnp.int32)
    bstart = jnp.asarray(fill, jnp.int32)
    exc = jnp.asarray(4, jnp.int32) if exclude_len else None
    got = paged_block_attention_pallas(
        q, pool_k, pool_v, bk, bv, kv_pos, pt, slot=slot,
        block_start=bstart, exclude_start=exc, exclude_len=exclude_len,
        window=window, interpret=True)
    want = ref.paged_block_attention_ref(
        q, pool_k, pool_v, bk, bv, kv_pos, pt, slot=slot,
        block_start=bstart, exclude_start=exc, exclude_len=exclude_len,
        window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.paged
def test_paged_kernel_skips_dead_and_unmapped_pages():
    """Tile counts: work scales with the LIVE MAPPED pages of each row —
    a fully unmapped (dead) row touches only its fresh-block tile."""
    rng = np.random.default_rng(9)
    B, bs, H, Kh, D = 2, 8, 8, 2, 32
    T, n_log = 48, 6
    num_pages = n_log + 2
    q = jnp.asarray(rng.standard_normal((B, bs, H, D)), jnp.float32)
    pool_k = jnp.asarray(rng.standard_normal((num_pages, PS, Kh, D)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((num_pages, PS, Kh, D)),
                         jnp.float32)
    bk = jnp.asarray(rng.standard_normal((B, bs, Kh, D)), jnp.float32)
    bv = jnp.asarray(rng.standard_normal((B, bs, Kh, D)), jnp.float32)
    fill = 24  # 3 of 6 logical pages live
    kv_pos = jnp.where(jnp.arange(T) < fill, jnp.arange(T), -1)
    kv_pos = kv_pos.astype(jnp.int32)
    pt = np.full((B, n_log), -1, np.int32)
    pt[0, :] = np.arange(n_log)  # live row; row 1 stays dead
    _, cnt, _ = paged_block_attention_pallas(
        q, pool_k, pool_v, bk, bv, kv_pos, jnp.asarray(pt),
        slot=jnp.asarray(fill, jnp.int32),
        block_start=jnp.asarray(fill, jnp.int32),
        debug_tile_counts=True, interpret=True)
    cnt = np.asarray(cnt)
    assert (cnt[0] == fill // PS + 1).all()   # live pages + block tile
    assert (cnt[1] == 1).all()                # dead row: block tile only


@pytest.mark.paged
def test_paged_kernel_per_row_kv_limit_skips_retired_rows():
    """Per-row ``kv_limit``: a row retired mid-batch (limit 0) stops
    touching its STILL-MAPPED tail pages — tile counts prove the dead
    row's pages are skipped while the live row's work is unchanged, and
    the output matches the oracle under the same per-row limits."""
    rng = np.random.default_rng(17)
    B, bs, H, Kh, D = 2, 8, 8, 2, 32
    T, n_log = 48, 6
    num_pages = B * n_log
    q = jnp.asarray(rng.standard_normal((B, bs, H, D)), jnp.float32)
    pool_k = jnp.asarray(rng.standard_normal((num_pages, PS, Kh, D)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((num_pages, PS, Kh, D)),
                         jnp.float32)
    bk = jnp.asarray(rng.standard_normal((B, bs, Kh, D)), jnp.float32)
    bv = jnp.asarray(rng.standard_normal((B, bs, Kh, D)), jnp.float32)
    fill = 24
    kv_pos = jnp.where(jnp.arange(T) < fill, jnp.arange(T), -1)
    kv_pos = kv_pos.astype(jnp.int32)
    # BOTH rows fully mapped: only the limit distinguishes them
    pt = jnp.asarray(np.arange(B * n_log).reshape(B, n_log), np.int32)
    lim = jnp.asarray([fill, 0], jnp.int32)  # row 1 retired
    got, cnt, _ = paged_block_attention_pallas(
        q, pool_k, pool_v, bk, bv, kv_pos, pt,
        slot=jnp.asarray(fill, jnp.int32),
        block_start=jnp.asarray(fill, jnp.int32), kv_limit=lim,
        debug_tile_counts=True, interpret=True)
    cnt = np.asarray(cnt)
    assert (cnt[0] == fill // PS + 1).all()   # live row: unchanged
    assert (cnt[1] == 1).all()                # retired row: block tile only
    want = ref.paged_block_attention_ref(
        q, pool_k, pool_v, bk, bv, kv_pos, pt,
        slot=jnp.asarray(fill, jnp.int32),
        block_start=jnp.asarray(fill, jnp.int32), kv_limit=lim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # a partial per-row limit (mid-batch retirement boundary) also agrees
    lim2 = jnp.asarray([fill, PS], jnp.int32)
    got2 = paged_block_attention_pallas(
        q, pool_k, pool_v, bk, bv, kv_pos, pt,
        slot=jnp.asarray(fill, jnp.int32),
        block_start=jnp.asarray(fill, jnp.int32), kv_limit=lim2,
        interpret=True)
    want2 = ref.paged_block_attention_ref(
        q, pool_k, pool_v, bk, bv, kv_pos, pt,
        slot=jnp.asarray(fill, jnp.int32),
        block_start=jnp.asarray(fill, jnp.int32), kv_limit=lim2)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.paged
def test_paged_kernel_per_row_mixed_cursors():
    """The sliced loop's mixed-cursor batch, paged: per-row slot /
    block_start / kv_limit / exclusion PLUS a reclaimed page inside one
    row's live extent and a retired sentinel row — all against the
    oracle in one call."""
    rng = np.random.default_rng(23)
    B, bs, H, Kh, D = 4, 8, 8, 2, 32
    T, n_log = 48, 6
    num_pages = B * n_log
    q = jnp.asarray(rng.standard_normal((B, bs, H, D)), jnp.float32)
    pool_k = jnp.asarray(rng.standard_normal((num_pages, PS, Kh, D)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((num_pages, PS, Kh, D)),
                         jnp.float32)
    bk = jnp.asarray(rng.standard_normal((B, bs, Kh, D)), jnp.float32)
    bv = jnp.asarray(rng.standard_normal((B, bs, Kh, D)), jnp.float32)
    kv_pos = jnp.arange(T, dtype=jnp.int32)  # per-row limits do the work
    pt = np.arange(B * n_log).reshape(B, n_log).astype(np.int32)
    pt[1, 1] = -1                            # hole inside row 1's extent
    pt = jnp.asarray(pt)
    slot = jnp.asarray([8, 24, 40, T], jnp.int32)   # row 3 retired
    bstart = jnp.asarray([8, 24, 40, 0], jnp.int32)
    lim = jnp.asarray([8, 24, 40, 0], jnp.int32)
    exc = jnp.asarray([0, 0, 16, 0], jnp.int32)     # row 2 excludes
    got, cnt, _ = paged_block_attention_pallas(
        q, pool_k, pool_v, bk, bv, kv_pos, pt, slot=slot,
        block_start=bstart, kv_limit=lim, exclude_start=exc,
        exclude_len=PS, debug_tile_counts=True, interpret=True)
    want = ref.paged_block_attention_ref(
        q, pool_k, pool_v, bk, bv, kv_pos, pt, slot=slot,
        block_start=bstart, kv_limit=lim, exclude_start=exc,
        exclude_len=PS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert np.abs(np.asarray(got)[3]).max() == 0.0  # retired row -> zeros
    # per-row tile counts: own live MAPPED pages + the block tile
    cnt = np.asarray(cnt)
    assert (cnt[0] == 8 // PS + 1).all()
    assert (cnt[1] == 24 // PS - 1 + 1).all()       # hole page skipped
    assert (cnt[2] == 40 // PS + 1).all()
    assert (cnt[3] == 1).all()                      # masked block tile only


MP_PS, MP_NLOG, MP_T, MP_BS = 16, 20, 316, 8   # 20 pages: 2.5 compute blocks


def _multi_page_case(case):
    """(page table, kv_limit, slot, extra kwargs) per row for a 4-row call
    over 20 logical pages of 16 slots (T = 316: the last page is partial,
    the last compute block holds 4 pages). Physical page 0 stays mapped
    at row 0's first page: the oracle reads it for unmapped slots."""
    B, T, bs = 4, MP_T, MP_BS
    pt = np.random.default_rng(5).permutation(B * MP_NLOG).reshape(
        B, MP_NLOG).astype(np.int32)
    r0, c0 = np.argwhere(pt == 0)[0]
    pt[r0, c0], pt[0, 0] = pt[0, 0], 0
    lim = np.full(B, T)
    slot = np.full(B, T - bs)
    kw = {}
    if case == "limit_mid_page_mid_block":
        lim = np.asarray([100, 200, 37, 250])
        slot = lim.copy()
        # the last row's exclusion straddles the block 0 / block 1 edge
        kw = dict(exclude_start=jnp.asarray([40, 120, 8, 120], jnp.int32),
                  exclude_len=MP_PS)
    elif case == "unmapped_block_edges":
        pt[1, 8] = pt[1, 15] = -1      # first and last page of block 1
        pt[2, 7] = pt[2, 16] = -1      # last of block 0, first of block 2
        pt[3, 8:16] = -1               # a whole block unmapped: skipped
        kw = dict(window=200)
    elif case == "shared_prefix":
        pt[1:, :12] = pt[0, :12]       # a document over 1.5 blocks
        slot = np.asarray([T - bs, 292, 252, 300])
        lim = slot + bs
    elif case == "retired_and_sentinel":
        lim = np.asarray([150, 0, 200, T])    # row 1 retired, still mapped
        slot = np.asarray([150, 0, T, T - bs])  # row 2: sentinel slot
    return pt, lim, slot, kw


@pytest.mark.paged
@pytest.mark.parametrize("case", [
    "partial_last_block", "limit_mid_page_mid_block",
    "unmapped_block_edges", "shared_prefix", "retired_and_sentinel"])
def test_paged_kernel_multi_page_blocks(case):
    """Compute blocks of several pages, fetched by hand: per-row limits
    that end mid-page and mid-block, holes at a block's edges, a whole
    unmapped block, shared pages, a retired row and the sentinel slot,
    against the oracle. Every pool page that no row maps holds NaN, so a
    page that is fetched or summed when it should not be shows; the V
    rows a live block did not fetch must be zeroed (interpret mode fills
    fresh VMEM with NaN). The counters read live mapped pages plus the
    block tile, and one kv step per block with a live page plus one."""
    pt, lim, slot, kw = _multi_page_case(case)
    rng = np.random.default_rng(31)
    B, bs, H, Kh, D, T = 4, MP_BS, 4, 2, 32, MP_T
    num_pages = B * MP_NLOG + 4
    pool_k = rng.standard_normal((num_pages, MP_PS, Kh, D))
    pool_v = rng.standard_normal((num_pages, MP_PS, Kh, D))
    unmapped = np.setdiff1d(np.arange(num_pages), pt[pt >= 0])
    pool_k[unmapped] = np.nan
    pool_v[unmapped] = np.nan
    q, bk, bv = (jnp.asarray(rng.standard_normal(s), jnp.float32) for s in
                 [(B, bs, H, D), (B, bs, Kh, D), (B, bs, Kh, D)])
    args = (q, jnp.asarray(pool_k, jnp.float32),
            jnp.asarray(pool_v, jnp.float32), bk, bv,
            jnp.arange(T, dtype=jnp.int32), jnp.asarray(pt))
    kw.update(slot=jnp.asarray(slot, jnp.int32),
              block_start=jnp.asarray(slot, jnp.int32),
              kv_limit=jnp.asarray(lim, jnp.int32))
    got, tiles, steps = paged_block_attention_pallas(
        *args, **kw, debug_tile_counts=True, interpret=True)
    want = ref.paged_block_attention_ref(*args, **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    ppb = BLOCK_KEYS // MP_PS
    live = (pt >= 0) & (np.arange(MP_NLOG) * MP_PS < lim[:, None])
    blocks = np.pad(live, ((0, 0), (0, -MP_NLOG % ppb))).reshape(B, -1, ppb)
    np.testing.assert_array_equal(np.asarray(tiles)[:, 0],
                                  live.sum(1) + 1)
    np.testing.assert_array_equal(np.asarray(steps)[:, 0],
                                  blocks.any(2).sum(1) + 1)


@pytest.mark.paged
def test_block_step_row_live_only_affects_retired_rows(small_model):
    """``block_step(row_live=...)``: an all-live mask is a bitwise no-op
    (live rows' limits equal the cache's valid extent, which ``pos``
    already enforces); a retired row attends only the fresh block."""
    cfg, params = small_model
    B, P, max_len = 2, PROMPT_LEN, PROMPT_LEN + 16
    prompt = jax.random.randint(jax.random.key(7), (B, P), 1, 256)
    n_log = -(-max_len // PS)
    pt = cache_lib.identity_page_table(B, max_len, PS)
    pool_k, pool_v = _pool(cfg, B * n_log)
    cache = {"attn": {"kp": pool_k, "vp": pool_v, "pt": pt,
                      "pos": jnp.full((max_len,), -1, jnp.int32),
                      "length": jnp.zeros((), jnp.int32)}}
    _, cache = M.prefill(params, cfg, prompt, max_len=max_len,
                         mode="full", cache=cache, page_size=PS)
    block = jnp.full((B, 4), tok.MASK_ID, jnp.int32)
    start = jnp.asarray(P, jnp.int32)
    for impl in ("auto", "flash", "kernel"):
        base, _ = M.block_step(params, cfg, block, start, cache,
                               attn_impl=impl, page_size=PS)
        same, _ = M.block_step(params, cfg, block, start, cache,
                               attn_impl=impl, page_size=PS,
                               row_live=jnp.asarray([True, True]))
        np.testing.assert_array_equal(np.asarray(base), np.asarray(same))
        part, _ = M.block_step(params, cfg, block, start, cache,
                               attn_impl=impl, page_size=PS,
                               row_live=jnp.asarray([True, False]))
        part = np.asarray(part)
        np.testing.assert_array_equal(part[0], np.asarray(base)[0])
        assert not np.array_equal(part[1], np.asarray(base)[1])


# ---------------------------------------------------------------------------
# tentpole acceptance: paged decode == dense decode, all modes x impls
# ---------------------------------------------------------------------------

@pytest.mark.paged
@pytest.mark.parametrize("cache_mode,attn_impl", [
    ("prefix", "auto"), ("prefix", "kernel"), ("prefix", "xla"),
    ("dual", "auto"), ("dual", "kernel"), ("dual", "xla"),
    ("none", "auto"),
])
def test_paged_token_identity(small_model, cache_mode, attn_impl):
    """Paged decode must be token-identical to dense for every cache mode
    and attention impl ("xla" spells the length-aware flash path)."""
    cfg, params = small_model
    impl = "flash" if attn_impl == "xla" else attn_impl
    B, P = 2, PROMPT_LEN
    prompt = jax.random.randint(jax.random.key(2), (B, P), 1, 256)
    table = jnp.full((DCFG_DENSE.num_blocks, DCFG_DENSE.steps_cap), 0.9,
                     jnp.float32)
    mask = jnp.asarray(tok.MASK_ID, jnp.int32)
    dense = make_generate_fn(cfg, DCFG_DENSE, cache_mode=cache_mode,
                             attn_impl=impl)
    want = dense(params, prompt, table, mask)
    paged = make_generate_fn(cfg, DCFG_PAGED, cache_mode=cache_mode,
                             attn_impl=impl, cache_layout="paged")
    if cache_mode == "none":       # cacheless: nothing to page — the
        got = paged(params, prompt, table, mask)   # same program serves
    else:
        max_len = P + DCFG_PAGED.max_new_tokens + \
            (DCFG_PAGED.block_size if cache_mode == "dual" else 0)
        n_log = DCFG_PAGED.pages_per_seq(max_len)
        pt = cache_lib.identity_page_table(B, max_len, PS)
        pool_k, pool_v = _pool(cfg, B * n_log)
        got = paged(params, prompt, table, mask, None, None,
                    pool_k, pool_v, pt)
    np.testing.assert_array_equal(np.asarray(got.tokens),
                                  np.asarray(want.tokens))
    assert int(got.nfe) == int(want.nfe)
    np.testing.assert_array_equal(np.asarray(got.seq_steps),
                                  np.asarray(want.seq_steps))


@pytest.mark.paged
def test_paged_scheduler_matches_dense(small_model):
    """End-to-end: the paged engine serves byte-identical responses to
    the dense engine on the same mixed stream."""
    cfg, params = small_model
    ecfg = EngineConfig(batch_size=4, prompt_len=PROMPT_LEN)
    reqs = [Request(i, t, f"{t} question {i}?")
            for i, t in enumerate(["alpha", "beta", "alpha"])]
    out_d = DiffusionEngine(params, cfg, DCFG_DENSE,
                            ecfg=ecfg).submit(list(reqs))
    out_p = DiffusionEngine(params, cfg, DCFG_PAGED,
                            ecfg=ecfg).submit(list(reqs))
    for d, p in zip(out_d, out_p):
        assert (d.uid, d.text, d.tokens_out) == (p.uid, p.text,
                                                 p.tokens_out)


# ---------------------------------------------------------------------------
# shared prefix: refcounts, copy-on-write, reclaim accounting
# ---------------------------------------------------------------------------

def _shared_scheduler(cfg, params, num_pages=0):
    ecfg = EngineConfig(batch_size=2, prompt_len=32,
                        shared_prefix="SYSTEM: be terse. ",
                        num_pages=num_pages)
    return Scheduler(params, cfg, DCFG_PAGED, ecfg=ecfg)


@pytest.mark.paged
def test_shared_prefix_pages_are_refcounted_and_cow(small_model):
    """The shared pages are prefilled once, mapped into every active
    slot, never written by decode (copy-on-write with page-aligned
    boundaries => the copy is elided), and survive retirement via the
    scheduler's permanent reference."""
    cfg, params = small_model
    sch = _shared_scheduler(cfg, params)
    n_shared = len(sch._shared_pages)
    assert n_shared == sch.shared_len // PS > 0
    before_k = np.asarray(sch._pool_k)[:, sch._shared_pages].copy()
    assert (np.abs(before_k).sum() > 0)  # the one-time prefill wrote them

    sch.submit([Request(0, "a", "q0?"), Request(1, "b", "q1?")])
    # while the rows decode each active slot holds a reference
    base = sch.allocator
    sch.slice_step()
    for p in sch._shared_pages:
        assert base.refcount(p) == 1 + 2
    sch.run()
    # decode never wrote the shared pages (COW contract)
    after_k = np.asarray(sch._pool_k)[:, sch._shared_pages]
    np.testing.assert_array_equal(before_k, after_k)
    # retirement dropped the per-slot references; only the scheduler's
    # permanent reference remains
    for p in sch._shared_pages:
        assert base.refcount(p) == 1
    assert base.in_use == n_shared


@pytest.mark.paged
def test_page_reclaim_accounting_after_eos(small_model):
    """EOS-retired rows' private pages return to the free list at
    retirement and the stats ledger balances: peak <= capacity,
    freed == allocated-private, end occupancy == shared pages."""
    cfg, params = small_model
    sch = _shared_scheduler(cfg, params)
    sch.submit([Request(i, "t", f"question {i}?") for i in range(5)])
    out = sch.run()
    assert len(out) == 5
    st = sch.stats
    assert st.page_capacity == sch.allocator.num_pages
    assert st.pages_shared == len(sch._shared_pages)
    assert st.pages_peak <= st.page_capacity
    assert st.pages_freed == st.requests * sch.private_per_slot
    assert sch.allocator.in_use == st.pages_shared  # full reclaim


@pytest.mark.paged
def test_shared_prefix_aligns_when_prompt_len_is_odd(small_model):
    """A prompt_len that is NOT a page multiple must still yield a
    page-aligned shared length (the cap rounds down too) — previously
    this crashed engine construction."""
    cfg, params = small_model
    ecfg = EngineConfig(batch_size=2, prompt_len=20,
                        shared_prefix="SYSTEM: be terse and precise. ")
    sch = Scheduler(params, cfg, DCFG_PAGED, ecfg=ecfg)
    assert sch.shared_len % PS == 0 and 0 < sch.shared_len <= 20 - PS
    sch.submit([Request(0, "t", "q?")])
    assert len(sch.run()) == 1


@pytest.mark.paged
def test_page_scarcity_limits_admission(small_model):
    """A pool sized below batch_size * pages-per-request admits partial
    batches — requests wait for PAGES, not free slots — and the queue
    still drains completely."""
    cfg, params = small_model
    probe = _shared_scheduler(cfg, params)
    n_shared = len(probe._shared_pages)
    per = probe.private_per_slot
    sch = _shared_scheduler(cfg, params, num_pages=n_shared + per)
    sch.submit([Request(i, "t", f"question {i}?") for i in range(3)])
    first = sch.slice_step()
    # pages for exactly one request, though a second slot is free
    assert sum(s.state == "active" for s in sch.slots) == 1
    assert sch.pending() == 2
    rest = sch.run()
    assert sorted(r.uid for r in first + rest) == [0, 1, 2]
    assert sch.allocator.in_use == n_shared


# ---------------------------------------------------------------------------
# conservation audit: every page is free or named by exactly one ledger
# ---------------------------------------------------------------------------

def _audit_pages(sch):
    """Page-conservation invariant, checkable at any slice boundary:
    the pool balances (``num_pages == available + in_use``) and
    ``in_use`` equals the de-duplicated union of every holder the
    scheduler can name — the shared-prefix pin, radix-tree nodes, and
    live slots' (prefix + private) page tables. A page in ``in_use``
    with no holder is a leak; a holder naming a free page is a
    use-after-free."""
    a = sch.allocator
    assert a.num_pages == a.available + a.in_use
    held = set(sch._shared_pages)
    if sch.prefix_tree is not None:
        stack = list(sch.prefix_tree.root.children.values())
        tree_pages = 0
        while stack:
            n = stack.pop()
            held.update(n.pages)
            tree_pages += len(n.pages)
            stack.extend(n.children.values())
        assert tree_pages == sch.prefix_tree.pages_pinned
    for sl in sch.slots:
        if sl.state == "active":
            held.update(sl.pages or [])
            held.update(sl.prefix_pages or [])
    assert a.in_use == len(held), (a.in_use, sorted(held))
    assert all(a.refcount(p) >= 1 for p in held)


@pytest.mark.paged
def test_page_conservation_across_prefix_lifecycle(small_model):
    """Walk a prefix-cache sliced run under genuine eviction pressure —
    admissions, retirements with tree promotion, LRU evictions, a warm
    revisit, and one injected failed slice — auditing the pool at EVERY
    slice boundary: pages allocated must always equal free + live +
    tree-held, with shared/tree pages counted once however many rows
    map them."""
    cfg, params = small_model
    ecfg = EngineConfig(batch_size=2, prompt_len=PROMPT_LEN,
                        prefix_cache=True, num_pages=12)
    sch = Scheduler(params, cfg, DCFG_PAGED, ecfg=ecfg)
    reqs = [Request(i, "t", f"question number {i}?") for i in range(5)]
    reqs.append(Request(99, "t", "question number 0?"))  # warm revisit
    sch.submit(reqs)
    _audit_pages(sch)

    out, boundaries, failed_at = [], 0, 3
    while sch.pending() or any(s.state == "active" for s in sch.slots):
        boundaries += 1
        assert boundaries < 200, "queue failed to drain"
        if boundaries == failed_at:
            real = sch._slice_fn
            sch._slice_fn = lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("boom"))
            with pytest.raises(RuntimeError):
                sch.slice_step()
            sch._slice_fn = real
            _audit_pages(sch)  # requeue reclaimed, seeds kept, no leak
            continue
        out.extend(sch.slice_step())
        _audit_pages(sch)
    assert sorted(r.uid for r in out) == [0, 1, 2, 3, 4, 99]
    assert sch.stats.prefix_evictions > 0  # the pressure was real
    # rest state: only the tree (+ the shared pin, empty here) holds pages
    assert sch.allocator.in_use == \
        sch.prefix_tree.pages_pinned + len(sch._shared_pages)


@pytest.mark.paged
@pytest.mark.parametrize("phase", ["slice", "admit"])
def test_failed_slice_exact_stats_backout(small_model, phase):
    """A raising boundary program — the slice, or the admission prefill
    before it — must back the admission ledger out EXACTLY: afterwards
    the stats equal the pre-submit snapshot except the fields a (real)
    admission prefill moved — ``nfe`` / ``weight_bytes_streamed`` /
    ``prefill_nfe`` / ``prefill_rows`` — and ``pages_peak``, a
    high-water mark that is never unwound."""
    cfg, params = small_model
    ecfg = EngineConfig(batch_size=2, prompt_len=32,
                        shared_prefix="SYSTEM: be terse. ")
    sch = Scheduler(params, cfg, DCFG_PAGED, ecfg=ecfg)
    n_shared = len(sch._shared_pages)
    before = sch.stats.as_dict()
    sch.submit([Request(i, "t", f"question {i}?") for i in range(2)])
    name = f"_{phase}_fn"
    real = getattr(sch, name)
    setattr(sch, name, lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("boom")))
    with pytest.raises(RuntimeError):
        sch.slice_step()
    assert sch.carry is None and all(s.state == "free" for s in sch.slots)
    after = sch.stats.as_dict()
    moved = {"pages_peak", "nfe", "weight_bytes_streamed", "prefill_nfe",
             "prefill_rows"}
    assert {k: v for k, v in after.items() if k not in moved} == \
        {k: v for k, v in before.items() if k not in moved}
    assert after["pages_peak"] >= before["pages_peak"]
    assert sch.allocator.in_use == n_shared  # full page reclaim
    assert sch.pending() == 2
    setattr(sch, name, real)
    out = sch.run()                          # retry serves every uid
    assert sorted(r.uid for r in out) == [0, 1]
    assert sch.allocator.in_use == n_shared


@pytest.mark.paged
def test_shared_pages_equal_private_copies(small_model):
    """Mapping ONE set of shared-prefix pages into every row must decode
    identically to giving each row its own private copy of those pages —
    sharing is pure memory dedup, never a semantic change."""
    cfg, params = small_model
    B, P, Sp = 2, 24, PS
    max_len = P + DCFG_PAGED.max_new_tokens
    n_log = DCFG_PAGED.pages_per_seq(max_len)
    n_shared = Sp // PS
    n_priv = n_log - n_shared
    num_pages = 3 * n_shared + B * n_priv
    pool_k, pool_v = _pool(cfg, num_pages)

    shared_tokens = jax.random.randint(jax.random.key(5), (1, Sp), 1, 256)
    spt = np.full((1, n_log), -1, np.int32)
    spt[0, :n_shared] = np.arange(n_shared)
    cache = {"attn": {"kp": pool_k, "vp": pool_v, "pt": jnp.asarray(spt),
                      "pos": jnp.full((max_len,), -1, jnp.int32),
                      "length": jnp.zeros((), jnp.int32)}}
    _, cache = M.prefill(params, cfg, shared_tokens, max_len=max_len,
                         mode="full", cache=cache, page_size=PS)
    pool_k, pool_v = cache["attn"]["kp"], cache["attn"]["vp"]
    # two extra byte-identical copies of the shared pages
    for c in (1, 2):
        dst = np.arange(c * n_shared, (c + 1) * n_shared)
        pool_k = pool_k.at[:, dst].set(pool_k[:, :n_shared])
        pool_v = pool_v.at[:, dst].set(pool_v[:, :n_shared])

    prompt = jnp.concatenate(
        [jnp.broadcast_to(shared_tokens, (B, Sp)),
         jax.random.randint(jax.random.key(6), (B, P - Sp), 1, 256)], 1)
    table = jnp.full((DCFG_PAGED.num_blocks, DCFG_PAGED.steps_cap), 0.9,
                     jnp.float32)
    mask = jnp.asarray(tok.MASK_ID, jnp.int32)
    gen = make_generate_fn(cfg, DCFG_PAGED, cache_layout="paged",
                           shared_prefix_len=Sp)
    tails = 3 * n_shared + np.arange(B * n_priv).reshape(B, n_priv)
    pt_shared = np.concatenate(
        [np.tile(np.arange(n_shared), (B, 1)), tails], 1).astype(np.int32)
    pt_private = np.concatenate(
        [np.stack([np.arange(n_shared) + n_shared,
                   np.arange(n_shared) + 2 * n_shared]), tails],
        1).astype(np.int32)
    res_s = gen(params, prompt, table, mask, None, None,
                pool_k, pool_v, jnp.asarray(pt_shared))
    res_p = gen(params, prompt, table, mask, None, None,
                pool_k, pool_v, jnp.asarray(pt_private))
    np.testing.assert_array_equal(np.asarray(res_s.tokens),
                                  np.asarray(res_p.tokens))


# ---------------------------------------------------------------------------
# wrap-aware kv_write_slice (ring-buffer regression)
# ---------------------------------------------------------------------------

def test_kv_write_slice_wraps_ring():
    """A chunk crossing the ring boundary must wrap to slot 0 — the old
    dynamic_update_slice clamped the start and silently corrupted slots
    [T-S, T) instead."""
    B, T, S, Kh, D = 2, 8, 4, 1, 2
    rng = np.random.default_rng(1)
    ck0 = jnp.asarray(rng.standard_normal((B, T, Kh, D)), jnp.float32)
    cv0 = jnp.asarray(rng.standard_normal((B, T, Kh, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Kh, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Kh, D)), jnp.float32)

    @jax.jit
    def write(ck, cv, start):
        return cache_lib.kv_write_slice(ck, cv, k, v, start)

    ck, cv = write(ck0, cv0, jnp.asarray(6, jnp.int32))
    for i, slot in enumerate([6, 7, 0, 1]):
        np.testing.assert_array_equal(np.asarray(ck)[:, slot],
                                      np.asarray(k)[:, i])
        np.testing.assert_array_equal(np.asarray(cv)[:, slot],
                                      np.asarray(v)[:, i])
    # untouched slots keep their contents
    for slot in (2, 3, 4, 5):
        np.testing.assert_array_equal(np.asarray(ck)[:, slot],
                                      np.asarray(ck0)[:, slot])
    # the contiguous fast path is unchanged
    ck, cv = write(ck0, cv0, jnp.asarray(2, jnp.int32))
    np.testing.assert_array_equal(np.asarray(ck)[:, 2:6], np.asarray(k))

    pos = jnp.full((T,), -1, jnp.int32)
    pos = cache_lib.pos_write_slice(pos, jnp.arange(10, 14),
                                    jnp.asarray(6, jnp.int32))
    np.testing.assert_array_equal(np.asarray(pos),
                                  [12, 13, -1, -1, -1, -1, 10, 11])


def test_kv_write_slice_chunk_longer_than_ring():
    """S > T: ring semantics keep exactly the LAST T entries (a naive
    modular scatter has duplicate indices with undefined winner)."""
    B, T, S, Kh, D = 1, 4, 6, 1, 2
    rng = np.random.default_rng(2)
    ck0 = jnp.zeros((B, T, Kh, D), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Kh, D)), jnp.float32)
    ck, _ = cache_lib.kv_write_slice(ck0, ck0, k, k,
                                     jnp.asarray(1, jnp.int32))
    # entries 2..5 land at slots (1+2..1+5) % 4 = 3, 0, 1, 2
    for i, slot in zip(range(2, 6), [3, 0, 1, 2]):
        np.testing.assert_array_equal(np.asarray(ck)[:, slot],
                                      np.asarray(k)[:, i])
    pos = cache_lib.pos_write_slice(jnp.full((T,), -1, jnp.int32),
                                    jnp.arange(10, 16),
                                    jnp.asarray(1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(pos), [13, 14, 15, 12])
