"""Speculative block drafting (SERVING.md "Speculative drafting"):
signature derivation from stored profiles, the draft-and-verify decode
variant's identity/fallback contracts, COW page forking, and the
engine-level draft lifecycle + stats."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import DecodeConfig, EngineConfig
from repro.config.registry import get_config
from repro.core.calibrate import CalibrationProfile, build_table
from repro.core.decoder import make_generate_fn
from repro.core.osdt import CalibrationStore
from repro.data import tokenizer as tok
from repro.models import model as M
from repro.models.cache import PageAllocator
from repro.spec import Drafter, block_signature, predicted_steps
from repro.serving.engine import DiffusionEngine
from repro.serving.scheduler import Request, Scheduler

DCFG = DecodeConfig(max_new_tokens=16, block_size=4, policy="osdt",
                    mode="block", metric="q1", cap=0.9, slack=0.1,
                    threshold=0.9)
NB, SC, BS = DCFG.num_blocks, DCFG.steps_cap, DCFG.block_size
PROMPT_LEN = 16
MASK = jnp.asarray(tok.MASK_ID, jnp.int32)


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("llada-8b").reduced()
    return cfg, M.init_params(jax.random.key(0), cfg)


def _profile(conf, valid=None, steps=None) -> CalibrationProfile:
    conf = np.asarray(conf, np.float32)
    if valid is None:
        valid = np.ones_like(conf, bool)
    if steps is None:
        steps = np.full((conf.shape[0],), conf.shape[1], np.int32)
    return CalibrationProfile(conf=conf, valid=np.asarray(valid, bool),
                              steps=np.asarray(steps, np.int32))


# ---------------------------------------------------------------------------
# signature: predicted steps-to-clear from the stored profile
# ---------------------------------------------------------------------------

@pytest.mark.spec
def test_predicted_steps_replays_threshold_rule():
    """Block 0 clears at step 0 everywhere -> 1 step; block 1 clears one
    position per step via the argmax fallback -> block_size steps; block 2
    was never reached during calibration -> steps_cap (never drafted)."""
    conf = np.zeros((3, SC, BS), np.float32)
    valid = np.zeros((3, SC, BS), bool)
    table = np.full((3, SC), 0.5, np.float32)
    # block 0: every position confident at step 0
    conf[0, 0] = 0.9
    valid[0, 0] = True
    # block 1: nothing ever clears 0.5 -> fallback, one position per step
    for s in range(SC):
        conf[1, s] = 0.1 + 0.01 * np.arange(BS)
        valid[1, s] = np.arange(BS) >= s  # one fewer masked each step
    got = predicted_steps(_profile(conf, valid), table)
    assert got[0] == 1
    assert got[1] == min(BS, SC)
    assert got[2] == SC


@pytest.mark.spec
def test_predicted_steps_is_conservative_without_recordings():
    """Positions whose confidence was not recorded at a step cannot clear
    there — predictions overshoot (safe: verification catches optimism,
    nothing catches a block never drafted)."""
    conf = np.full((1, SC, BS), 0.4, np.float32)  # below tau everywhere
    valid = np.zeros((1, SC, BS), bool)
    valid[0, 0] = True  # recorded at step 0 only: the calibration run
    #                     cleared everything there, the replay does not
    got = predicted_steps(_profile(conf, valid),
                          np.full((1, SC), 0.5, np.float32))
    assert got[0] == SC  # recording exhausted -> never predicted easy


@pytest.mark.spec
def test_drafter_masks_only_calibrated_tasks():
    store = CalibrationStore(DCFG)
    prof = _profile(np.full((NB, SC, BS), 0.99, np.float32))
    store.ingest("easy", prof)
    drafter = Drafter(store, DCFG)
    # calibrated task: tau = min(0.99, cap) * (1 - slack) = 0.81 < 0.99,
    # so every recorded block clears in one step
    sig = block_signature(prof, store.tables["easy"], DCFG)
    assert (sig == 1).all()
    mask = drafter.mask_for(["easy", "unseen", "easy"])
    assert mask.shape == (3, NB)
    assert mask[0].all() and mask[2].all() and not mask[1].any()
    # invalidation drops the cache (recomputed next call)
    drafter.invalidate("easy")
    assert drafter.mask_for(["easy"]).all()


# ---------------------------------------------------------------------------
# decode variant: identity and fallback contracts
# ---------------------------------------------------------------------------

def _gen_pair(cfg, dcfg, **kw):
    return (make_generate_fn(cfg, dcfg, **kw),
            make_generate_fn(cfg, dcfg, variant="draft", **kw))


@pytest.mark.spec
@pytest.mark.parametrize("cache_mode", ["prefix", "dual", "none"])
def test_draft_disabled_is_bit_identical(small_model, cache_mode):
    """The draft program with no draft mask must reproduce the stepped
    program exactly — tokens, NFE, per-row step counts."""
    cfg, params = small_model
    step, draft = _gen_pair(cfg, DCFG, cache_mode=cache_mode)
    prompt = jax.random.randint(jax.random.key(2), (2, PROMPT_LEN), 1, 256)
    table = jnp.full((NB, SC), 0.9, jnp.float32)
    want = step(params, prompt, table, MASK)
    got = draft(params, prompt, table, MASK)  # draft_mask=None
    np.testing.assert_array_equal(np.asarray(got.tokens),
                                  np.asarray(want.tokens))
    assert int(got.nfe) == int(want.nfe)
    np.testing.assert_array_equal(np.asarray(got.seq_steps),
                                  np.asarray(want.seq_steps))
    assert (np.asarray(got.blocks_drafted) == 0).all()


@pytest.mark.spec
def test_rejected_drafts_fall_back_to_stepped(small_model):
    """A verification threshold nothing clears rejects every draft: the
    demoted blocks decode through the stepped loop bit-identically, at
    exactly +2 forwards (the draft + verify)."""
    cfg, params = small_model
    step, draft = _gen_pair(cfg, DCFG)
    prompt = jax.random.randint(jax.random.key(3), (2, PROMPT_LEN), 1, 256)
    table = jnp.full((NB, SC), 2.0, jnp.float32)  # conf can never clear
    dm = jnp.ones((2, NB), bool)
    want = step(params, prompt, table, MASK)
    got = draft(params, prompt, table, MASK, None, None, None, None, None,
                dm)
    np.testing.assert_array_equal(np.asarray(got.tokens),
                                  np.asarray(want.tokens))
    assert int(got.nfe) == int(want.nfe) + 2
    assert (np.asarray(got.blocks_drafted) == NB).all()
    assert (np.asarray(got.blocks_accepted) == 0).all()


@pytest.mark.spec
def test_single_block_draft_is_token_identical(small_model):
    """With one response block the draft forward IS the stepped step-0
    forward (same context, same shapes), so accept or reject the output
    matches the stepped path token for token."""
    cfg, params = small_model
    d1 = dataclasses.replace(DCFG, max_new_tokens=4)
    step, draft = _gen_pair(cfg, d1)
    prompt = jax.random.randint(jax.random.key(4), (2, PROMPT_LEN), 1, 256)
    table = jnp.full((1, d1.steps_cap), 0.0, jnp.float32)  # 1-step blocks
    want = step(params, prompt, table, MASK)
    got = draft(params, prompt, table, MASK, None, None, None, None, None,
                jnp.ones((2, 1), bool))
    np.testing.assert_array_equal(np.asarray(got.tokens),
                                  np.asarray(want.tokens))
    assert (np.asarray(got.blocks_drafted) == 1).all()
    acc = np.asarray(got.blocks_accepted)
    # accepted rows cost one extra forward (draft+verify replace the one
    # step), fully-rejected ones two
    assert int(got.nfe) in (int(want.nfe) + 1, int(want.nfe) + 2)
    assert ((acc == 0) | (acc == 1)).all()


@pytest.mark.spec
@pytest.mark.parametrize("cache_mode", ["prefix", "dual", "none"])
def test_accepted_drafts_match_stepped_and_save_forwards(small_model,
                                                         cache_mode):
    """Deterministic accept-everything: with all-zero parameters the
    logits are context-independent (argmax stable, conf = 1/V > 0), so
    every drafted block verifies. Tokens must equal the stepped path's
    and the draft program must spend nb fewer step forwards (+2 for
    draft/verify)."""
    cfg, params = small_model
    zero = jax.tree.map(jnp.zeros_like, params)
    step, draft = _gen_pair(cfg, DCFG, cache_mode=cache_mode)
    prompt = jax.random.randint(jax.random.key(5), (2, PROMPT_LEN), 1, 256)
    table = jnp.full((NB, SC), 0.0, jnp.float32)
    want = step(zero, prompt, table, MASK)
    assert (np.asarray(want.seq_steps) == 1).all()  # 1-step blocks
    got = draft(zero, prompt, table, MASK, None, None, None, None, None,
                jnp.ones((2, NB), bool))
    np.testing.assert_array_equal(np.asarray(got.tokens),
                                  np.asarray(want.tokens))
    assert (np.asarray(got.blocks_accepted) == NB).all()
    assert (np.asarray(got.seq_steps) == 0).all()  # zero denoising steps
    assert int(got.nfe) == int(want.nfe) - NB + 2
    # the recording of accepted blocks stays empty: nothing may leak into
    # a calibration profile from skipped steps
    assert not np.asarray(got.conf_valid).any()


@pytest.mark.spec
def test_draft_respects_dead_rows(small_model):
    """Dead rows never draft (their flush tokens must not be 'accepted')
    and an all-dead batch skips the draft forwards entirely."""
    cfg, params = small_model
    _, draft = _gen_pair(cfg, DCFG)
    prompt = jax.random.randint(jax.random.key(6), (2, PROMPT_LEN), 1, 256)
    table = jnp.full((2, NB, SC), 0.0, jnp.float32)
    dm = jnp.ones((2, NB), bool)
    half = draft(params, prompt, table, MASK, jnp.asarray([True, False]),
                 None, None, None, None, dm)
    assert int(np.asarray(half.blocks_drafted)[1]) == 0
    dead = draft(params, prompt, table, MASK, jnp.asarray([False, False]),
                 None, None, None, None, dm)
    assert int(dead.nfe) == 1  # prefill only: lax.cond skipped the draft


# ---------------------------------------------------------------------------
# COW page forking
# ---------------------------------------------------------------------------

@pytest.mark.spec
def test_fork_reject_reclaim_restores_refcounts():
    a = PageAllocator(8)
    parent = a.alloc(2)
    shared, private = a.fork(parent, 3)
    assert shared == parent and len(private) == 3
    assert a.in_use == 5
    for p in parent:
        assert a.refcount(p) == 2
    for p in private:
        assert a.refcount(p) == 1
    # reject the fork: reclaim restores every refcount exactly
    a.free(shared)
    a.free(private)
    assert a.in_use == 2
    for p in parent:
        assert a.refcount(p) == 1
    a.free(parent)
    assert a.available == 8


@pytest.mark.spec
def test_fork_is_atomic_on_exhaustion():
    a = PageAllocator(4)
    parent = a.alloc(2)
    with pytest.raises(MemoryError):
        a.fork(parent, 3)  # only 2 pages free
    # the failed fork took no parent reference
    for p in parent:
        assert a.refcount(p) == 1
    assert a.available == 2


# ---------------------------------------------------------------------------
# engine-level lifecycle + stats
# ---------------------------------------------------------------------------

def _easy_store(dcfg=DCFG) -> CalibrationStore:
    """A store whose task 'easy' predicts every block clears in 1 step."""
    store = CalibrationStore(dcfg)
    store.ingest("easy", _profile(
        np.full((dcfg.num_blocks, dcfg.steps_cap, dcfg.block_size), 0.99,
                np.float32)))
    return store


@pytest.mark.spec
def test_engine_rejected_drafts_match_plain_engine(small_model):
    """Force full drafting with an impossible verification threshold: the
    spec engine must serve byte-identical responses to the plain engine
    (each rejected block demotes to the same stepped loop), while the
    stats record the drafted-but-rejected blocks."""
    cfg, params = small_model
    reqs = [Request(i, "t", f"question {i}?") for i in range(3)]
    ecfg = EngineConfig(batch_size=4, prompt_len=PROMPT_LEN)

    def impossible_store():
        s = CalibrationStore(DCFG)
        s.ingest("t", _profile(np.full((NB, SC, BS), 0.99, np.float32)))
        s.tables["t"] = np.full((NB, SC), 2.0, np.float32)
        return s

    plain = DiffusionEngine(params, cfg, DCFG, ecfg=ecfg,
                            store=impossible_store())
    out_p = plain.submit(list(reqs))

    spec_ecfg = dataclasses.replace(ecfg, spec_decode=True)
    eng = DiffusionEngine(params, cfg, DCFG, ecfg=spec_ecfg,
                          store=impossible_store())
    # the signature would never flag a block under tau=2.0; force the
    # plan so the REJECT path is what's exercised
    eng.scheduler.drafter.row_mask = lambda task: np.ones((NB,), bool)
    out_s = eng.submit(list(reqs))

    for p, s in zip(out_p, out_s):
        assert (p.uid, p.text, p.tokens_out) == (s.uid, s.text,
                                                 s.tokens_out)
        assert s.blocks_drafted == NB and s.blocks_accepted == 0
    st = eng.stats
    assert st.blocks_drafted == 3 * NB and st.blocks_accepted == 0
    assert st.draft_accept_rate == 0.0
    # all three rows admitted at one boundary: one plan, whose draft and
    # verify forwards are the only extra cost
    assert st.draft_batches == 1
    assert st.nfe == plain.stats.nfe + 2


@pytest.mark.spec
def test_engine_draft_lifecycle_and_stats(small_model):
    """A calibrated easy task drafts on every post-calibration request;
    the calibrating request itself and unseen tasks draft nothing; the
    ledger stays coherent; paged pools reclaim fully."""
    cfg, params = small_model
    dcfg = dataclasses.replace(DCFG, cache_layout="paged", page_size=8)
    ecfg = EngineConfig(batch_size=4, prompt_len=PROMPT_LEN,
                        spec_decode=True,
                        shared_prefix="SYSTEM: be terse. ")
    sch = Scheduler(params, cfg, dcfg, ecfg=ecfg, store=_easy_store(dcfg))
    sch.submit([Request(0, "easy", "q0?"), Request(1, "new", "q1?"),
                Request(2, "easy", "q2?")])
    out = {r.uid: r for r in sch.run()}
    assert out[0].blocks_drafted == NB and out[2].blocks_drafted == NB
    assert out[1].blocks_drafted == 0      # was calibrating its task
    st = sch.stats
    assert st.blocks_drafted == 2 * NB
    assert 0 <= st.blocks_accepted <= st.blocks_drafted
    assert st.draft_batches == 1
    assert 0.0 <= st.draft_accept_rate <= 1.0
    assert sch.store.calibrated("new")     # calibration still worked
    assert sch.allocator.in_use == st.pages_shared  # forks released
    # the now-calibrated task drafts on its next request
    sch.submit([Request(3, "new", "q3?")])
    (r3,) = sch.run()
    assert r3.blocks_drafted >= 0  # plan derived from its own signature


@pytest.mark.spec
def test_engine_paged_spec_matches_dense_spec(small_model):
    """The draft program preserves the paged==dense contract: the same
    spec-decoded stream produces identical responses under both cache
    layouts."""
    cfg, params = small_model
    ecfg = EngineConfig(batch_size=4, prompt_len=PROMPT_LEN,
                        spec_decode=True)
    reqs = [Request(i, "easy", f"question {i}?") for i in range(3)]
    dcfg_p = dataclasses.replace(DCFG, cache_layout="paged", page_size=8)
    out_d = DiffusionEngine(params, cfg, DCFG, ecfg=ecfg,
                            store=_easy_store()).submit(list(reqs))
    out_p = DiffusionEngine(params, cfg, dcfg_p, ecfg=ecfg,
                            store=_easy_store(dcfg_p)).submit(list(reqs))
    for d, p in zip(out_d, out_p):
        assert (d.uid, d.text, d.blocks_drafted, d.blocks_accepted) == \
            (p.uid, p.text, p.blocks_drafted, p.blocks_accepted)


@pytest.mark.spec
def test_build_table_signature_roundtrip():
    """build_table -> block_signature is the store-level contract the
    drafter relies on: a uniformly confident profile yields an all-ones
    signature under its OWN calibrated table."""
    store = _easy_store()
    sig = block_signature(store.profiles["easy"], store.tables["easy"],
                          DCFG)
    assert (sig == 1).all()
    # and the table itself is what Algorithm 1 line 17 prescribes
    np.testing.assert_allclose(
        store.tables["easy"],
        build_table(store.profiles["easy"], DCFG))
