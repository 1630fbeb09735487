"""Continuous-batching scheduler: mixed-task identity, per-row lifecycle
(EOS early-exit, dead rows), calibration-store persistence, and the
engine's stats accounting (SERVING.md)."""
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
NB = DCFG.num_blocks


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
    dcfg = dataclasses.replace(DCFG, attn_impl=attn_impl)
    ecfg = EngineConfig(batch_size=4, prompt_len=PROMPT_LEN,
                        cache_mode=cache_mode)
    return DiffusionEngine(params, cfg, dcfg, ecfg=ecfg, store=store)


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
    responses to per-task batches (free slots ride along dead, same carry
    shape => same compiled slice program => bitwise-identical row
    math)."""
    cfg, params = small_model
    alpha, beta = _requests("alpha", 2, 0), _requests("beta", 2, 10)
    mixed = _engine(cfg, params, calibrated_store, cache_mode, attn_impl)
    got = {r.uid: r for r in mixed.submit([alpha[0], beta[0], alpha[1],
                                           beta[1]])}
    # genuinely one mixed batch: all four admitted at the first boundary
    # and decoded side by side
    assert mixed.stats.mid_admits == 0 and mixed.stats.slices <= NB

    for reqs in (alpha, beta):
        iso = _engine(cfg, params, calibrated_store, cache_mode, attn_impl)
        for r in iso.submit(list(reqs)):
            assert r.text == got[r.uid].text, (cache_mode, attn_impl, r.uid)
            assert r.tokens_out == got[r.uid].tokens_out
        # two rows of a batch of four: the other two slots stayed free
        assert iso.stats.requests == 2 and iso.stats.slices <= NB


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
    uncalibrated tasks admitted at ONE boundary both calibrate — each
    from its own first request's row, not the batch-max counts — while
    the second t1 request rides along on the static table."""
    cfg, params = small_model
    ecfg = EngineConfig(batch_size=4, prompt_len=PROMPT_LEN)
    sched = Scheduler(params, cfg, DCFG, ecfg=ecfg)
    sched.submit(_requests("t1", 1, 0) + _requests("t2", 1, 1)
                 + _requests("t1", 1, 2))
    out1 = sched.slice_step()
    assert sched._calibrating == {"t1": 0, "t2": 1}
    assert sched.pending() == 0
    out1 += sched.run()
    assert sorted(r.uid for r in out1) == [0, 1, 2]
    assert sched.store.calibrated("t1") and sched.store.calibrated("t2")
    assert sched._calibrating == {} and sched.stats.mid_admits == 0


def test_parallel_calibration_matches_isolated(small_model):
    """A task calibrated from row r of a mixed batch must get the same
    table as when it calibrates alone (same prompt, same static table,
    same compiled program => identical row math and step counts)."""
    cfg, params = small_model
    ecfg = EngineConfig(batch_size=4, prompt_len=PROMPT_LEN)
    mixed = Scheduler(params, cfg, DCFG, ecfg=ecfg)
    mixed.submit([_requests("a", 1, 0)[0], _requests("b", 1, 1)[0]])
    mixed.run()
    for task in ("a", "b"):
        iso = Scheduler(params, cfg, DCFG, ecfg=ecfg)
        iso.submit(_requests(task, 1, 0))
        iso.run()
        np.testing.assert_array_equal(iso.store.tables[task],
                                      mixed.store.tables[task])


def test_engine_stats_accounting(small_model, calibrated_store):
    """Delivered tokens are post-EOS-truncation counts and per-request
    wall is its queue wait + the slices it was decoded in (not the whole
    submit wall for every member)."""
    cfg, params = small_model
    eng = _engine(cfg, params, calibrated_store)
    out = eng.submit(_requests("alpha", 6))  # 4 admitted, then 2 more
    st = eng.stats
    # two admission prefills: a bucket of 4 rows, then one of 2
    assert st.requests == 6 and st.prefill_nfe == 2
    assert st.prefill_rows == 6
    assert st.tokens == sum(r.tokens_out for r in out)
    assert st.tokens + st.tokens_dropped == 6 * DCFG.max_new_tokens
    for r in out:
        assert r.tokens_out + r.tokens_dropped == DCFG.max_new_tokens
        assert len(r.token_ids) == r.tokens_out
        assert tok.decode(r.token_ids) == r.text
        assert r.wall_s == pytest.approx(r.queue_s + r.decode_s)
        assert r.decode_s < st.wall_s + 1e-9  # one batch, not the whole run
        assert r.nfe <= DCFG.num_blocks * DCFG.steps_cap


@pytest.mark.parametrize("slice_len", [0, -1])
def test_scheduler_refuses_slice_len_below_one(small_model, slice_len):
    """The sliced loop is the one runtime: a slice of fewer than one
    block is a configuration error, refused when the scheduler is
    built."""
    cfg, params = small_model
    with pytest.raises(ValueError, match="slice_len"):
        Scheduler(params, cfg, DCFG,
                  ecfg=EngineConfig(batch_size=2, prompt_len=PROMPT_LEN,
                                    slice_len=slice_len))


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
