"""Radix-tree prefix cache (SERVING.md "Radix prefix cache").

Contracts enforced here:

* **Tree mechanics** — longest whole-node match with LRU tick refresh;
  ``insert`` adopts pages by refcount transfer and rejects empty /
  unaligned / boundary-mismatched / duplicate runs; ``evict`` frees LRU
  leaves only when no live row references their pages.
* **Warm-hit bit-identity** — resubmitting a request to a warm engine
  (same calibration) reproduces the cold texts exactly, with ZERO
  additional prefill forwards on a full-prompt hit.
* **Cold determinism** — a fresh engine (empty tree, same store)
  reproduces the same texts: seeding is a pure function of the prefix
  stream, so cache state never changes outputs.
* **Full-miss degradation** — prefix-free requests through a
  prefix_cache engine are token-identical to the cache-off engine and
  the monolithic paged oracle (``make_generate_fn``).
* **Eviction under pressure** — LRU reclaims tree-only nodes before
  load-shedding and the allocator ledger stays balanced (the evict-time
  assert guarantees no live row ever loses a mapped page).
* **Bucketed admission scatters** — ``admit_carry_rows`` pads each
  admission to a power-of-two program and leaves untouched rows
  bit-identical.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import DecodeConfig, EngineConfig
from repro.config.registry import get_config
from repro.core.decoder import admit_carry_rows, init_decode_carry
from repro.data import tokenizer as tok
from repro.models import model as M
from repro.models.cache import PageAllocator, RadixPrefixCache
from repro.serving.scheduler import Request, Scheduler

pytestmark = pytest.mark.prefix

PS = 4
DCFG = DecodeConfig(max_new_tokens=16, block_size=4, policy="osdt",
                    mode="block", metric="q1", cap=0.9, slack=0.1,
                    threshold=0.9, page_size=PS, cache_layout="paged")
PROMPT_LEN = 16


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("llada-8b").reduced()
    return cfg, M.init_params(jax.random.key(0), cfg)


def _engine(cfg, params, *, prefix_cache=True, store=None, batch=2,
            num_pages=0, spec=False, shared="", watermark=0.0):
    ecfg = EngineConfig(batch_size=batch, prompt_len=PROMPT_LEN,
                        num_pages=num_pages,
                        shared_prefix=shared, spec_decode=spec,
                        prefix_cache=prefix_cache,
                        prefix_cache_watermark=watermark)
    return Scheduler(params, cfg, DCFG, ecfg=ecfg, store=store)


def _texts(responses):
    return [r.text for r in sorted(responses, key=lambda r: r.uid)]


def _calibrated_store(cfg, params, reqs):
    """One throwaway engine calibrates every task in ``reqs`` so the
    engines under test all decode with identical threshold tables."""
    s = _engine(cfg, params, prefix_cache=False)
    s.submit([Request(r.uid, r.task, r.prompt, prefix=r.prefix)
              for r in reqs])
    s.run()
    return s.store


# ---------------------------------------------------------------------------
# tree mechanics (no model)
# ---------------------------------------------------------------------------

def test_radix_match_insert_and_refcount_transfer():
    a = PageAllocator(16)
    t = RadixPrefixCache(a, PS)
    ids = list(range(100, 116))  # a 16-token row, 4 pages
    root_pages = a.alloc(2)
    assert t.insert(ids, 0, root_pages)   # node A: [0, 8)
    assert t.pages_pinned == 2 and t.nodes == 1
    # ownership transferred: the tree's reference IS the caller's old one
    assert all(a.refcount(p) == 1 for p in root_pages)
    child_pages = a.alloc(1)
    assert t.insert(ids, 8, child_pages)  # node B: [8, 12) under A
    m, pages, chain = t.match(ids)
    assert m == 12 and pages == root_pages + child_pages
    assert [n.start for n in chain] == [0, 8]
    # a different row sharing only the first 8 tokens matches node A only
    other = ids[:8] + [7] * 8
    m, pages, _ = t.match(other)
    assert m == 8 and pages == root_pages
    # no match at all
    assert t.match([1] * 16)[0] == 0
    # rejected inserts keep caller ownership (nothing pinned)
    extra = a.alloc(1)
    assert not t.insert(ids, 0, extra)    # node at 0 already exists
    assert not t.insert(ids, 6, extra)    # unaligned start
    assert not t.insert(ids, 4, extra)    # inside node A: boundary mismatch
    assert not t.insert(ids, 0, [])       # empty run
    a.free(extra)
    assert t.pages_pinned == 3 and t.nodes == 2


def test_radix_lru_eviction_respects_live_references():
    a = PageAllocator(16)
    t = RadixPrefixCache(a, PS)
    base = list(range(50, 58))
    row1 = base + [1] * 8
    row2 = base + [2] * 8
    t.insert(row1, 0, a.alloc(2))             # shared parent [0, 8)
    t.insert(row1, 8, a.alloc(2))             # leaf 1
    t.insert(row2, 8, a.alloc(2))             # leaf 2
    t.match(row2)                             # leaf 2 is now most recent
    # a live row shares leaf-1's chain: its pages are pinned > 1
    _, live_pages, _ = t.match(row1)
    a.share(live_pages)
    n, freed = t.evict(16)
    # only leaf 2 is evictable (leaf 1 + parent pinned by the live row)
    assert (n, freed) == (1, 2) and t.nodes == 2
    # releasing the live row exposes leaf 1, then the parent
    a.free(live_pages)
    n, freed = t.evict(16)
    assert (n, freed) == (2, 4) and t.nodes == 0 and t.pages_pinned == 0
    assert a.in_use == 0


def test_radix_trim_enforces_page_budget():
    a = PageAllocator(16)
    t = RadixPrefixCache(a, PS, max_pages=2)
    row = list(range(60, 76))
    t.insert(row, 0, a.alloc(2))
    t.insert(row, 8, a.alloc(2))
    n, freed = t.trim()
    assert t.pages_pinned <= 2 and n == 1 and freed == 2


# ---------------------------------------------------------------------------
# engine: hit identity, miss degradation, eviction
# ---------------------------------------------------------------------------

def _tenant_reqs():
    return [Request(0, "t", "what is 2+2?", prefix="you are tenant A. "),
            Request(1, "t", "what is 3+3?", prefix="you are tenant A. ")]


def test_warm_full_hit_is_token_identical_and_skips_prefill(small_model):
    cfg, params = small_model
    store = _calibrated_store(cfg, params, _tenant_reqs())
    s = _engine(cfg, params, store=store)
    s.submit(_tenant_reqs())
    cold = s.run()
    nfe_prefill = s.stats.prefill_nfe
    assert s.stats.prefix_misses >= 1      # the seeder missed
    assert s.stats.prefix_hits >= 1        # its batchmate already hit
    s.submit(_tenant_reqs())
    warm = s.run()
    assert _texts(warm) == _texts(cold)
    # retirement promoted the full prompt: the resubmission is a
    # full-prompt hit and pays ZERO prefill forwards
    assert s.stats.prefill_nfe == nfe_prefill
    assert all(r.prefix_hit_pages == PROMPT_LEN // PS for r in warm)
    assert all(r.prefill_tokens_saved == PROMPT_LEN for r in warm)
    assert s.stats.prefix_hit_rate > 0.5


def test_cold_engine_reproduces_warm_texts(small_model):
    cfg, params = small_model
    store = _calibrated_store(cfg, params, _tenant_reqs())
    s1 = _engine(cfg, params, store=store)
    s1.submit(_tenant_reqs())
    first = s1.run()
    s2 = _engine(cfg, params, store=store)  # fresh engine, empty tree
    s2.submit(_tenant_reqs())
    assert _texts(s2.run()) == _texts(first)


def test_full_miss_matches_cache_off_and_monolithic(small_model,
                                                   generate_reference):
    cfg, params = small_model
    reqs = lambda: [Request(0, "t", "what is 2+2?"),
                    Request(1, "t", "what is 3+3?")]
    store = _calibrated_store(cfg, params, reqs())
    runs = []
    for kw in (dict(prefix_cache=True), dict(prefix_cache=False)):
        s = _engine(cfg, params, store=store, **kw)
        s.submit(reqs())
        runs.append(_texts(s.run()))
    want, _ = generate_reference(
        _engine(cfg, params, store=store, prefix_cache=False), reqs())
    runs.append([want[uid][0] for uid in sorted(want)])  # monolithic
    assert runs[0] == runs[1] == runs[2]
    # and the prefix engine's resubmission (now a promoted full hit)
    # still reproduces the miss texts exactly
    s = _engine(cfg, params, store=store)
    s.submit(reqs())
    miss = _texts(s.run())
    assert s.stats.prefix_hits == 0
    s.submit(reqs())
    assert _texts(s.run()) == miss == runs[0]
    assert s.stats.prefix_hits == 2


def test_shared_template_node_is_reused_across_tenants(small_model):
    cfg, params = small_model
    shared = "be terse. "  # >= 1 page after rounding (11 tokens w/ BOS)
    reqs = [Request(0, "t", "what is 2+2?", prefix="tenant A. "),
            Request(1, "t", "what is 3+3?", prefix="tenant B. ")]
    store = _calibrated_store(cfg, params, reqs)
    s = _engine(cfg, params, store=store, batch=1, shared=shared)
    s.submit([reqs[0]])
    first = s.run()
    hits_before = s.stats.prefix_hit_pages
    s.submit([reqs[1]])
    second = s.run()
    # tenant B never ran before, but its chain goes through the shared
    # template node tenant A seeded -> a cross-tenant partial hit
    assert s.stats.prefix_hit_pages > hits_before
    assert second[0].prefix_hit_pages >= 1
    # determinism: a fresh engine reproduces both tenants' texts
    s2 = _engine(cfg, params, store=store, batch=1, shared=shared)
    s2.submit([reqs[0]])
    assert _texts(s2.run()) == _texts(first)
    s2.submit([reqs[1]])
    assert _texts(s2.run()) == _texts(second)


def test_eviction_reclaims_lru_nodes_under_page_pressure(small_model):
    cfg, params = small_model
    # the digit sits inside the page-capped prefix window, so every
    # tenant seeds a DISTINCT radix chain (no accidental sharing)
    tenants = [Request(i, "t", f"question {i}?",
                       prefix=f"tenant {i} says. ")
               for i in range(5)]
    store = _calibrated_store(cfg, params, tenants[:1])
    # pool fits ~one request + one cached chain: serving five distinct
    # tenants forces LRU eviction instead of load-shedding forever
    s = _engine(cfg, params, store=store, batch=1, num_pages=12)
    for r in tenants:
        s.submit([r])
        out = s.run()
        assert len(out) == 1 and out[0].uid == r.uid
    assert s.stats.prefix_evictions >= 1
    assert s.stats.requests == len(tenants)
    # ledger: with every row retired, all live references are the
    # tree's own — nothing leaked, nothing double-freed
    assert s.allocator.in_use == s.prefix_tree.pages_pinned
    assert all(s.allocator.refcount(p) == 1
               for n in s.prefix_tree.root.children.values()
               for p in n.pages)


def test_spec_decode_rides_along(small_model):
    cfg, params = small_model
    store = _calibrated_store(cfg, params, _tenant_reqs())
    s = _engine(cfg, params, store=store, spec=True)
    s.submit(_tenant_reqs())
    cold = s.run()
    s.submit(_tenant_reqs())
    assert _texts(s.run()) == _texts(cold)


# ---------------------------------------------------------------------------
# bucketed admission scatters (per-admission-count recompile fix)
# ---------------------------------------------------------------------------

def _fresh_carry(cfg):
    B, n_log = 4, DCFG.pages_per_seq(PROMPT_LEN + DCFG.max_new_tokens)
    L, Kh, D = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    pool = jnp.zeros((L, 8 * B, PS, Kh, D), jnp.float32)
    return init_decode_carry(
        cfg, DCFG, batch=B, prompt_len=PROMPT_LEN, mask_id=tok.MASK_ID,
        cache_mode="prefix", cache_layout="paged", shared_prefix_len=0,
        pool_k=pool, pool_v=pool,
        page_table=np.full((B, n_log), -1, np.int32))


@pytest.mark.parametrize("rows", [[2], [0, 3], [0, 1, 3]])
def test_bucketed_admit_sets_only_the_admitted_rows(small_model, rows):
    cfg, _ = small_model
    carry = _fresh_carry(cfg)
    nb, sc = carry.table.shape[1], carry.table.shape[2]
    n_log = carry.cache["attn"]["pt"].shape[1]
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 200, (len(rows), PROMPT_LEN)).astype(np.int32)
    tables = rng.random((len(rows), nb, sc), np.float32)
    pages = np.arange(len(rows) * n_log, dtype=np.int32) \
        .reshape(len(rows), n_log)
    live = [True] * (len(rows) - 1) + [False]
    out = admit_carry_rows(carry, rows, prompts, tables, tok.MASK_ID,
                           page_rows=pages, live=live)
    np.testing.assert_array_equal(np.asarray(out.prompt)[rows], prompts)
    np.testing.assert_allclose(np.asarray(out.table)[rows], tables,
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(out.cache["attn"]["pt"])[rows],
                                  pages)
    assert np.asarray(out.live)[rows].tolist() == live
    assert (np.asarray(out.cursor)[rows] == 0).all()
    assert (np.asarray(out.resp)[rows] == tok.MASK_ID).all()
    # rows NOT in the admission are bit-identical to the fresh carry
    other = [i for i in range(4) if i not in rows]
    for field in ("resp", "prompt", "table", "live", "cursor",
                  "seq_steps", "blocks_drafted", "blocks_accepted"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out, field))[other],
            np.asarray(getattr(carry, field))[other])
    np.testing.assert_array_equal(
        np.asarray(out.cache["attn"]["pt"])[other],
        np.asarray(carry.cache["attn"]["pt"])[other])


def test_bucketed_admit_marks_prompt_positions(small_model):
    cfg, _ = small_model
    carry = _fresh_carry(cfg)
    nb, sc = carry.table.shape[1], carry.table.shape[2]
    n_log = carry.cache["attn"]["pt"].shape[1]
    out = admit_carry_rows(
        carry, [1], np.zeros((1, PROMPT_LEN), np.int32),
        np.zeros((1, nb, sc), np.float32), tok.MASK_ID,
        page_rows=np.arange(n_log, dtype=np.int32)[None],
        mark_prompt_pos=True)
    pos = np.asarray(out.cache["attn"]["pos"])
    np.testing.assert_array_equal(pos[:PROMPT_LEN], np.arange(PROMPT_LEN))
    assert int(out.cache["attn"]["length"]) == PROMPT_LEN
    # idempotent with a later full prefill's own marking
    again = admit_carry_rows(
        out, [2], np.zeros((1, PROMPT_LEN), np.int32),
        np.zeros((1, nb, sc), np.float32), tok.MASK_ID,
        page_rows=np.arange(n_log, dtype=np.int32)[None],
        mark_prompt_pos=True)
    np.testing.assert_array_equal(np.asarray(again.cache["attn"]["pos"]),
                                  pos)
