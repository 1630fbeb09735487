"""Continuous-batching scheduler: mixed-task identity, per-row lifecycle
(EOS early-exit, dead slots), calibration-store persistence, and the
engine's repaired stats accounting (SERVING.md)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import DecodeConfig, EngineConfig
from repro.config.registry import get_config
from repro.core.decoder import make_generate_fn, result_profile
from repro.core.osdt import CalibrationStore
from repro.data import tokenizer as tok
from repro.serving.engine import DiffusionEngine
from repro.serving.scheduler import Request, Scheduler

DCFG = DecodeConfig(max_new_tokens=16, block_size=4, policy="osdt",
                    mode="block", metric="q1", cap=0.9, slack=0.1,
                    threshold=0.9)
PROMPT_LEN = 16


@pytest.fixture(scope="module")
def small_model():
    from repro.models.model import init_params
    cfg = get_config("llada-8b").reduced()
    return cfg, init_params(jax.random.key(0), cfg)


def _requests(task: str, n: int, base_uid: int = 0):
    return [Request(base_uid + i, task, f"{task} question {i}?")
            for i in range(n)]


@pytest.fixture(scope="module")
def calibrated_store(small_model):
    """Deterministic pre-calibration for two tasks (treated read-only:
    every scheduler run below sees identical per-task tables)."""
    cfg, params = small_model
    store = CalibrationStore(DCFG)
    gen = make_generate_fn(cfg, DCFG)
    mask = jnp.asarray(tok.MASK_ID, jnp.int32)
    for task in ("alpha", "beta"):
        ids = [tok.encode(r.prompt, bos=True)[-PROMPT_LEN:]
               for r in _requests(task, 4)]
        prompt = jnp.asarray(tok.batch_prompts(ids, PROMPT_LEN))
        store.ingest(task, result_profile(
            gen(params, prompt, jnp.asarray(store.static), mask)))
    assert store.tasks() == ["alpha", "beta"]
    return store


def _engine(cfg, params, store, cache_mode="prefix", attn_impl="auto"):
    ecfg = EngineConfig(batch_size=4, prompt_len=PROMPT_LEN,
                        cache_mode=cache_mode, attn_impl=attn_impl)
    return DiffusionEngine(params, cfg, DCFG, ecfg=ecfg, store=store)


# ---------------------------------------------------------------------------
# tentpole: mixed-task batches decode token-identically to isolated ones
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache_mode,attn_impl", [
    ("prefix", "auto"), ("prefix", "kernel"),
    ("dual", "auto"), ("dual", "kernel"),
    ("none", "auto"),
])
def test_mixed_task_identity(small_model, calibrated_store, cache_mode,
                             attn_impl):
    """One batch mixing tasks alpha/beta must produce byte-identical
    responses to per-task batches (dead-slot padded, same batch shape =>
    same compiled program => bitwise-identical row math)."""
    cfg, params = small_model
    alpha, beta = _requests("alpha", 2, 0), _requests("beta", 2, 10)
    mixed = _engine(cfg, params, calibrated_store, cache_mode, attn_impl)
    got = {r.uid: r for r in mixed.submit([alpha[0], beta[0], alpha[1],
                                           beta[1]])}
    assert mixed.stats.batches == 1  # genuinely one mixed batch

    for reqs in (alpha, beta):
        iso = _engine(cfg, params, calibrated_store, cache_mode, attn_impl)
        for r in iso.submit(list(reqs)):
            assert r.text == got[r.uid].text, (cache_mode, attn_impl, r.uid)
            assert r.tokens_out == got[r.uid].tokens_out
        assert iso.stats.dead_slots == 2  # explicit dead-slot padding


# ---------------------------------------------------------------------------
# per-row lifecycle
# ---------------------------------------------------------------------------

def test_eos_early_exit_reduces_seq_steps(small_model):
    """A row whose completed block contains EOS retires: zero recorded
    steps for every later block, and the result reports it not-live."""
    cfg, params = small_model
    gen = make_generate_fn(cfg, DCFG)
    mask = jnp.asarray(tok.MASK_ID, jnp.int32)
    prompt = jax.random.randint(jax.random.key(3), (2, 8), 1, 256)
    table = jnp.full((DCFG.num_blocks, DCFG.steps_cap), 0.9, jnp.float32)
    base = gen(params, prompt, table, mask)
    eos = int(np.asarray(base.tokens)[0, 0])  # a token row 0 emits in block 0
    res = gen(params, prompt, table, mask, None, eos)
    seq = np.asarray(res.seq_steps)
    assert (seq[0, 1:] == 0).all()
    assert not bool(np.asarray(res.live)[0])
    assert seq.sum() < np.asarray(base.seq_steps).sum()
    # the calibration recording follows each row's liveness: nothing after
    # row 0's retirement block may be marked valid (would poison ingest())
    assert not np.asarray(res.conf_valid)[0, 1:].any()
    assert np.asarray(base.conf_valid)[0, 1:].any()
    # blocks decoded before retirement are identical to the baseline
    np.testing.assert_array_equal(np.asarray(res.tokens)[0, :DCFG.block_size],
                                  np.asarray(base.tokens)[0, :DCFG.block_size])


def test_dead_rows_cost_no_steps_and_no_interference(small_model):
    cfg, params = small_model
    gen = make_generate_fn(cfg, DCFG)
    mask = jnp.asarray(tok.MASK_ID, jnp.int32)
    prompt = jax.random.randint(jax.random.key(4), (2, 8), 1, 256)
    table = jnp.full((2, DCFG.num_blocks, DCFG.steps_cap), 0.9, jnp.float32)
    full = gen(params, prompt, table, mask, jnp.asarray([True, True]))
    half = gen(params, prompt, table, mask, jnp.asarray([True, False]))
    assert (np.asarray(half.seq_steps)[1] == 0).all()
    np.testing.assert_array_equal(np.asarray(half.tokens)[0],
                                  np.asarray(full.tokens)[0])
    # an all-dead batch costs only the prefill forward
    dead = gen(params, prompt, table, mask, jnp.asarray([False, False]))
    assert int(dead.nfe) == 1 and int(np.asarray(dead.seq_steps).sum()) == 0


def test_scheduler_calibrates_several_new_tasks_per_batch(small_model):
    """Parallel calibration: every row records a profile, so two
    uncalibrated tasks admitted into ONE mixed batch both calibrate —
    each from its own first request's row, not the batch-max counts."""
    cfg, params = small_model
    ecfg = EngineConfig(batch_size=4, prompt_len=PROMPT_LEN)
    sched = Scheduler(params, cfg, DCFG, ecfg=ecfg)
    sched.submit(_requests("t1", 1, 0) + _requests("t2", 1, 1)
                 + _requests("t1", 1, 2))
    out1 = sched.step()
    assert sorted(r.uid for r in out1) == [0, 1, 2]
    assert sched.store.calibrated("t1") and sched.store.calibrated("t2")
    assert sched.stats.batches == 1 and sched.pending() == 0


def test_parallel_calibration_matches_isolated(small_model):
    """A task calibrated from row r of a mixed batch must get the same
    table as when it calibrates alone (same prompt, same static table,
    same compiled program => identical row math and step counts)."""
    cfg, params = small_model
    ecfg = EngineConfig(batch_size=4, prompt_len=PROMPT_LEN)
    mixed = Scheduler(params, cfg, DCFG, ecfg=ecfg)
    mixed.submit([_requests("a", 1, 0)[0], _requests("b", 1, 1)[0]])
    mixed.step()
    for task in ("a", "b"):
        iso = Scheduler(params, cfg, DCFG, ecfg=ecfg)
        iso.submit(_requests(task, 1, 0))
        iso.step()
        np.testing.assert_array_equal(iso.store.tables[task],
                                      mixed.store.tables[task])


def test_engine_stats_accounting(small_model, calibrated_store):
    """Delivered tokens are post-EOS-truncation counts and per-request
    wall is its queue wait + its own batch's decode wall (not the whole
    submit wall for every member)."""
    cfg, params = small_model
    eng = _engine(cfg, params, calibrated_store)
    out = eng.submit(_requests("alpha", 6))  # 2 batches of 4 (2 dead slots)
    st = eng.stats
    assert st.requests == 6 and st.batches == 2 and st.dead_slots == 2
    assert st.tokens == sum(r.tokens_out for r in out)
    assert st.tokens + st.tokens_dropped == 6 * DCFG.max_new_tokens
    for r in out:
        assert r.tokens_out + r.tokens_dropped == DCFG.max_new_tokens
        assert len(r.token_ids) == r.tokens_out
        assert tok.decode(r.token_ids) == r.text
        assert r.wall_s == pytest.approx(r.queue_s + r.decode_s)
        assert r.decode_s < st.wall_s + 1e-9  # one batch, not the whole run
        assert r.nfe <= DCFG.num_blocks * DCFG.steps_cap


def test_failed_batch_conserves_stats_ledger(small_model, calibrated_store):
    """Monolithic ``step()`` mutates EngineStats only on success: after
    an injected decode failure the ledger must equal its pre-step
    snapshot EXACTLY (dense layout — no page watermark to move and no
    admission prefill), the requests must be back at the queue head,
    and a retry serves every uid with the usual accounting."""
    cfg, params = small_model
    ecfg = EngineConfig(batch_size=4, prompt_len=PROMPT_LEN)
    sch = Scheduler(params, cfg, DCFG, ecfg=ecfg, store=calibrated_store)
    sch.submit(_requests("alpha", 3))
    before = sch.stats.as_dict()
    real_gen = sch._gen
    sch._gen = lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom"))
    with pytest.raises(RuntimeError):
        sch.step()
    assert sch.stats.as_dict() == before     # conservation: exact
    assert sch.pending() == 3                # nothing swallowed
    assert all(s.state == "free" for s in sch.slots)
    sch._gen = real_gen
    out = sch.run()
    assert sorted(r.uid for r in out) == [0, 1, 2]
    st = sch.stats
    assert st.requests == 3 and st.batches == 1 and st.dead_slots == 1
    assert st.tokens == sum(r.tokens_out for r in out)


# ---------------------------------------------------------------------------
# calibration store persistence
# ---------------------------------------------------------------------------

def test_store_npz_roundtrip(tmp_path, calibrated_store):
    path = str(tmp_path / "store.npz")
    calibrated_store.save(path)
    loaded = CalibrationStore.load(path, DCFG)
    assert loaded.tasks() == calibrated_store.tasks()
    for task in calibrated_store.tasks():
        np.testing.assert_array_equal(loaded.tables[task],
                                      calibrated_store.tables[task])
        np.testing.assert_array_equal(loaded.profiles[task].conf,
                                      calibrated_store.profiles[task].conf)
        np.testing.assert_array_equal(loaded.profiles[task].valid,
                                      calibrated_store.profiles[task].valid)
    # a batch assembled from the loaded store is bit-identical
    np.testing.assert_array_equal(
        loaded.tables_for(["alpha", "beta", "__dead__"]),
        calibrated_store.tables_for(["alpha", "beta", "__dead__"]))


def test_store_rejects_other_geometry(tmp_path, calibrated_store):
    path = str(tmp_path / "store.npz")
    calibrated_store.save(path)
    other = dataclasses.replace(DCFG, max_new_tokens=32, block_size=8)
    with pytest.raises(AssertionError):
        CalibrationStore.load(path, other)


def test_engine_persists_store(tmp_path, small_model):
    """EngineConfig.store_path: calibration survives an engine restart —
    the second engine serves the task without re-calibrating."""
    cfg, params = small_model
    # a bare path: np.savez appends '.npz', existence check must agree
    path = str(tmp_path / "calib")
    ecfg = EngineConfig(batch_size=2, prompt_len=PROMPT_LEN,
                        store_path=path)
    eng1 = DiffusionEngine(params, cfg, DCFG, ecfg=ecfg)
    eng1.submit(_requests("gamma", 2))
    tab = eng1.store.tables["gamma"].copy()
    eng2 = DiffusionEngine(params, cfg, DCFG, ecfg=ecfg)
    assert eng2.store.calibrated("gamma")
    np.testing.assert_array_equal(eng2.store.tables["gamma"], tab)
    # an explicitly passed store wins over the on-disk npz
    fresh = CalibrationStore(DCFG)
    eng3 = DiffusionEngine(params, cfg, DCFG, ecfg=ecfg, store=fresh)
    assert eng3.store is fresh and not eng3.store.calibrated("gamma")
