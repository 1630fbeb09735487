"""Step-sliced decode loop (SERVING.md "Async admission").

The contracts enforced here:

* **Slice identity** — driving ``make_slice_fn`` (slice_len 1 / 2 / nb)
  until every cursor reaches ``nb`` is token-, seq_steps-, conf- and
  nfe-identical to the monolithic ``make_generate_fn`` oracle with the
  same admitted set, across cache modes x attention impls x cache
  layouts x spec on/off.
* **Mid-loop admission** — a request admitted while the batch is
  mid-generation produces exactly the tokens it would get in a fresh
  batch (per-row cursors, per-row prefill, per-row valid extents).
* **Mid-loop retirement** — an EOS-retired row's pages return to the
  allocator at the slice boundary, while the rest of the batch is still
  decoding (ledger assert).
* **Latency accounting** — per-request ``time_to_first_block`` and
  queue/decode walls are measured at slice boundaries.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import DecodeConfig, EngineConfig
from repro.config.registry import get_config
from repro.core.decoder import (admit_carry_rows, init_decode_carry,
                                make_admit_fn, make_generate_fn,
                                make_slice_fn, result_profile)
from repro.core.osdt import CalibrationStore
from repro.data import tokenizer as tok
from repro.models import model as M
from repro.models.cache import identity_page_table
from repro.serving.scheduler import Request, Scheduler

pytestmark = getattr(pytest.mark, "async")

DCFG = DecodeConfig(max_new_tokens=16, block_size=4, policy="osdt",
                    mode="block", metric="q1", cap=0.9, slack=0.1,
                    threshold=0.9, page_size=4)
PROMPT_LEN = 16
NB = DCFG.num_blocks


@pytest.fixture(scope="module")
def small_model():
    from repro.models.model import init_params
    cfg = get_config("llada-8b").reduced()
    return cfg, init_params(jax.random.key(0), cfg)


@pytest.fixture(scope="module")
def prompts():
    return np.asarray(jax.random.randint(jax.random.key(3),
                                         (2, PROMPT_LEN), 1, 256))


def _pool(cfg, mode):
    max_len = PROMPT_LEN + DCFG.max_new_tokens \
        + (DCFG.block_size if mode == "dual" else 0)
    n_log = DCFG.pages_per_seq(max_len)
    pt = identity_page_table(2, max_len, DCFG.page_size)
    shape = (cfg.num_layers, 2 * n_log, DCFG.page_size,
             cfg.num_kv_heads, cfg.resolved_head_dim)
    dt = M.param_dtype(cfg)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt), pt


def _run_sliced(cfg, params, prompts, table, *, slice_len, mode, impl,
                layout, eos_id=None, draft_mask=None, spec=False):
    kw = dict(cache_mode=mode, attn_impl=impl, cache_layout=layout)
    pool_kw = {}
    if layout == "paged":
        pk, pv, pt = _pool(cfg, mode)
        pool_kw = dict(pool_k=pk, pool_v=pv, page_table=pt)
    carry = init_decode_carry(cfg, DCFG, batch=2, prompt_len=PROMPT_LEN,
                              mask_id=tok.MASK_ID, cache_mode=mode,
                              cache_layout=layout, **pool_kw)
    carry = admit_carry_rows(
        carry, [0, 1], prompts, table, tok.MASK_ID,
        page_rows=np.asarray(pool_kw["page_table"])
        if layout == "paged" else None)
    if mode != "none":
        adm = make_admit_fn(cfg, DCFG, **kw)
        carry = adm(params, carry, jnp.asarray([True, True]))
    sf = make_slice_fn(cfg, DCFG, slice_len=slice_len,
                       variant="draft" if spec else "step", **kw)
    mask = jnp.asarray(tok.MASK_ID, jnp.int32)
    eid = None if eos_id is None else jnp.asarray(eos_id, jnp.int32)
    dm = None if draft_mask is None else jnp.asarray(draft_mask)
    while int(np.asarray(carry.cursor).min()) < NB:
        carry = sf(params, carry, mask, eid, dm)
        dm = None  # the plan is handed over exactly once
    return carry


def _run_monolithic(cfg, params, prompts, table, *, mode, impl, layout,
                    eos_id=None, draft_mask=None, spec=False):
    gen = make_generate_fn(cfg, DCFG, cache_mode=mode, attn_impl=impl,
                           cache_layout=layout,
                           variant="draft" if spec else "step")
    args = [params, jnp.asarray(prompts), jnp.asarray(table),
            jnp.asarray(tok.MASK_ID, jnp.int32),
            jnp.asarray([True, True]), eos_id]
    if layout == "paged":
        args += list(_pool(cfg, mode))
    kwargs = {}
    if draft_mask is not None:
        kwargs["draft_mask"] = jnp.asarray(draft_mask)
    return gen(*args, **kwargs)


SWEEP = [
    # (cache_mode, attn_impl, layout, spec, slice_lens)
    ("prefix", "auto", "dense", False, (1, 2, NB)),
    ("prefix", "kernel", "paged", False, (1, NB)),
    ("dual", "auto", "paged", False, (1, 2)),
    ("dual", "kernel", "dense", False, (1, NB)),
    ("none", "auto", "dense", False, (1, 2)),
    ("prefix", "auto", "paged", True, (1, 2)),
    ("dual", "auto", "dense", True, (1,)),
]


@pytest.mark.parametrize("mode,impl,layout,spec,slice_lens", SWEEP)
def test_slice_identity(small_model, prompts, mode, impl, layout, spec,
                        slice_lens):
    """Sliced loop == monolithic program, bitwise, for every slice_len
    (including slice_len = nb: ONE slice covering the whole sequence)."""
    cfg, params = small_model
    table = np.full((2, NB, DCFG.steps_cap), 0.9, np.float32)
    dm = None
    if spec:
        # a permissive table accepts everything; flag half the blocks
        table = np.zeros((2, NB, DCFG.steps_cap), np.float32)
        dm = np.zeros((2, NB), bool)
        dm[:, ::2] = True
    base = _run_monolithic(cfg, params, prompts, table, mode=mode,
                           impl=impl, layout=layout, draft_mask=dm,
                           spec=spec)
    for sl in slice_lens:
        got = _run_sliced(cfg, params, prompts, table, slice_len=sl,
                          mode=mode, impl=impl, layout=layout,
                          draft_mask=dm, spec=spec)
        key = (mode, impl, layout, spec, sl)
        np.testing.assert_array_equal(np.asarray(base.tokens),
                                      np.asarray(got.resp), err_msg=str(key))
        np.testing.assert_array_equal(np.asarray(base.seq_steps),
                                      np.asarray(got.seq_steps))
        np.testing.assert_array_equal(np.asarray(base.conf),
                                      np.asarray(got.conf))
        np.testing.assert_array_equal(np.asarray(base.conf_valid),
                                      np.asarray(got.conf_valid))
        assert int(base.nfe) == int(got.nfe), key
        if spec:
            np.testing.assert_array_equal(np.asarray(base.blocks_drafted),
                                          np.asarray(got.blocks_drafted))
            np.testing.assert_array_equal(np.asarray(base.blocks_accepted),
                                          np.asarray(got.blocks_accepted))


def test_sliced_kernel_impl_dispatches_pallas(small_model, prompts,
                                              monkeypatch):
    """``attn_impl="kernel"`` in the SLICED loop rides the per-row Pallas
    kernel — no more per-row-offsets XLA fallback. With the TPU gate
    forced on (kernel run in interpret mode), every block-attention call
    inside the slice program must reach ``cached_block_attention_pallas``
    with PER-ROW [B] geometry, and the decode must match the auto path."""
    from repro.kernels import ops

    cfg, params = small_model
    dcfg = dataclasses.replace(DCFG, max_new_tokens=8)  # fresh program key
    nb = dcfg.num_blocks
    table = np.full((2, nb, dcfg.steps_cap), 0.9, np.float32)

    def run(impl, patched):
        carry = init_decode_carry(cfg, dcfg, batch=2,
                                  prompt_len=PROMPT_LEN, mask_id=tok.MASK_ID,
                                  cache_mode="prefix")
        carry = admit_carry_rows(carry, [0, 1], prompts, table, tok.MASK_ID)
        adm = make_admit_fn(cfg, dcfg, cache_mode="prefix")
        carry = adm(params, carry, jnp.asarray([True, True]))
        with monkeypatch.context() as mp:
            if patched:
                real = ops.cached_block_attention_pallas

                def record(*args, **kw):
                    calls.append(getattr(kw.get("slot"), "ndim", 0))
                    kw["interpret"] = True
                    return real(*args, **kw)

                mp.setattr(ops, "cached_block_attention_pallas", record)
                mp.setattr(ops, "_on_tpu", lambda: True)
            sf = make_slice_fn(cfg, dcfg, slice_len=1, cache_mode="prefix",
                               attn_impl=impl)
            mask = jnp.asarray(tok.MASK_ID, jnp.int32)
            while int(np.asarray(carry.cursor).min()) < nb:
                carry = sf(params, carry, mask, None, None)
        return carry

    calls = []
    base = run("auto", patched=False)
    got = run("kernel", patched=True)
    assert calls, "kernel impl fell back: Pallas was never dispatched"
    assert all(nd == 1 for nd in calls), \
        "kernel saw scalar geometry — the sliced loop is per-row"
    np.testing.assert_array_equal(np.asarray(base.resp),
                                  np.asarray(got.resp))
    np.testing.assert_array_equal(np.asarray(base.seq_steps),
                                  np.asarray(got.seq_steps))
    assert int(base.nfe) == int(got.nfe)


def test_slice_identity_with_eos(small_model, prompts):
    """EOS retirement fires at the same step in the sliced loop."""
    cfg, params = small_model
    table = np.full((2, NB, DCFG.steps_cap), 0.9, np.float32)
    probe = _run_monolithic(cfg, params, prompts, table, mode="prefix",
                            impl="auto", layout="dense")
    eos = int(np.asarray(probe.tokens)[0, 0])
    base = _run_monolithic(cfg, params, prompts, table, mode="prefix",
                           impl="auto", layout="dense", eos_id=eos)
    got = _run_sliced(cfg, params, prompts, table, slice_len=1,
                      mode="prefix", impl="auto", layout="dense",
                      eos_id=eos)
    np.testing.assert_array_equal(np.asarray(base.tokens),
                                  np.asarray(got.resp))
    np.testing.assert_array_equal(np.asarray(base.seq_steps),
                                  np.asarray(got.seq_steps))
    np.testing.assert_array_equal(np.asarray(base.live),
                                  np.asarray(got.live))
    assert int(base.nfe) == int(got.nfe)


# ---------------------------------------------------------------------------
# scheduler-level: mid-loop admission / retirement / stats
# ---------------------------------------------------------------------------

def _requests(task, n, base=0):
    return [Request(base + i, task, f"{task} question {i}?")
            for i in range(n)]


@pytest.fixture(scope="module")
def calibrated_store(small_model):
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
    return store


def _sched(cfg, params, store, **ecfg_kw):
    kw = dict(batch_size=2, prompt_len=PROMPT_LEN, slice_len=1)
    kw.update(ecfg_kw)
    dcfg_kw = kw.pop("dcfg_kw", {})
    dcfg = dataclasses.replace(DCFG, **dcfg_kw) if dcfg_kw else DCFG
    return Scheduler(params, cfg, dcfg, ecfg=EngineConfig(**kw),
                     store=store)


def _drain(s):
    out = []
    while s.queue or any(sl.state == "active" for sl in s.slots):
        out.extend(s.slice_step())
    return out


def test_sliced_matches_batch_boundary(small_model, calibrated_store):
    """The sliced runtime delivers the same responses as the monolithic
    batch runtime for the same admitted set (pre-calibrated tables)."""
    cfg, params = small_model
    reqs = _requests("alpha", 2) + _requests("beta", 2, 10)
    ref = _sched(cfg, params, calibrated_store, batch_size=4, slice_len=0)
    ref.submit(list(reqs))
    got_ref = {r.uid: r for r in ref.run()}
    sl = _sched(cfg, params, calibrated_store, batch_size=4, slice_len=1)
    sl.submit(list(reqs))
    got = {r.uid: r for r in sl.run()}
    assert got.keys() == got_ref.keys()
    for uid, r in got.items():
        assert r.text == got_ref[uid].text, uid
        assert r.nfe == got_ref[uid].nfe
    assert sl.stats.tokens == ref.stats.tokens
    assert sl.stats.nfe == ref.stats.nfe
    assert sl.stats.slices >= NB


@pytest.mark.parametrize("paged", [False, True])
def test_mid_loop_admission_matches_fresh_batch(small_model,
                                                calibrated_store, paged):
    """A request admitted mid-generation decodes to exactly the tokens it
    gets in a fresh batch — per-row cursors + per-row prefill."""
    cfg, params = small_model
    kw = {}
    if paged:
        kw = dict(dcfg_kw=dict(cache_layout="paged"))
    mid = _sched(cfg, params, calibrated_store, **kw)
    mid.submit(_requests("alpha", 1))
    out = list(mid.slice_step())        # alpha starts decoding
    mid.submit(_requests("beta", 1, 50))  # arrives mid-generation
    out += _drain(mid)
    got = {r.uid: r for r in out}
    assert mid.stats.mid_admits == 1
    fresh = _sched(cfg, params, calibrated_store, **kw)
    fresh.submit(_requests("beta", 1, 50))
    ref = {r.uid: r for r in _drain(fresh)}
    assert got[50].text == ref[50].text
    assert got[50].nfe == ref[50].nfe


def test_mid_loop_retirement_frees_pages(small_model, calibrated_store):
    """A retired row's private pages return to the allocator at the
    slice boundary while other rows are still decoding (staggered
    admission guarantees the rows finish at different boundaries)."""
    cfg, params = small_model
    s = _sched(cfg, params, calibrated_store,
               dcfg_kw=dict(cache_layout="paged"))
    s.submit(_requests("alpha", 1))
    s.slice_step()                       # alpha: one block ahead
    s.submit(_requests("beta", 1, 10))
    s.slice_step()                       # beta admitted mid-generation
    per_slot = s.private_per_slot
    assert s.allocator.in_use == len(s._shared_pages) + 2 * per_slot
    freed_mid = False
    while s.queue or any(sl.state == "active" for sl in s.slots):
        s.slice_step()
        active = sum(sl.state == "active" for sl in s.slots)
        if active == 1:
            # ledger: exactly the retired row's pages came back
            assert s.allocator.in_use == \
                len(s._shared_pages) + per_slot
            freed_mid = True
    assert s.allocator.in_use == len(s._shared_pages)
    assert s.stats.pages_freed == 2 * per_slot
    assert freed_mid  # the stagger forces a mid-loop reclaim boundary


def test_sliced_latency_accounting(small_model, calibrated_store):
    """time_to_first_block is measured at the first slice boundary a row
    participated in, and a mid-batch admit is only charged the slices it
    was actually decoding in — not the whole batch's wall."""
    cfg, params = small_model
    s = _sched(cfg, params, calibrated_store, eos_early_exit=False)
    s.submit(_requests("alpha", 1))
    out = list(s.slice_step())
    s.submit(_requests("beta", 1, 50))
    out += _drain(s)
    got = {r.uid: r for r in out}
    total = s.stats.wall_s
    for r in got.values():
        assert r.ttfb_s > 0.0
        assert len(r.token_ids) == r.tokens_out
        assert tok.decode(r.token_ids) == r.text
        assert r.wall_s == pytest.approx(r.queue_s + r.decode_s)
        assert r.decode_s <= total + 1e-9
    # the late request was admitted after alpha's first slice: its decode
    # wall excludes that slice, so it is strictly below the total wall
    assert got[50].decode_s < total
    assert s.stats.ttfb_s == pytest.approx(
        sum(r.ttfb_s for r in got.values()))


def test_sliced_calibration_matches_batch(small_model):
    """An uncalibrated task's first request calibrates identically under
    the sliced runtime (same recording rows, ingested at retirement)."""
    cfg, params = small_model
    a = Scheduler(params, cfg, DCFG,
                  ecfg=EngineConfig(batch_size=2, prompt_len=PROMPT_LEN))
    a.submit(_requests("gamma", 1))
    a.run()
    b = Scheduler(params, cfg, DCFG,
                  ecfg=EngineConfig(batch_size=2, prompt_len=PROMPT_LEN,
                                    slice_len=1))
    b.submit(_requests("gamma", 1))
    b.run()
    assert b.store.calibrated("gamma")
    np.testing.assert_array_equal(a.store.tables["gamma"],
                                  b.store.tables["gamma"])


def test_sliced_spec_matches_monolithic_spec(small_model,
                                             calibrated_store):
    """spec_decode engines: sliced vs batch-boundary runtimes deliver the
    same texts and draft the same blocks (plan handed over once, at the
    row's admission slice)."""
    cfg, params = small_model
    reqs = _requests("alpha", 2) + _requests("beta", 2, 10)
    kw = dict(batch_size=4, spec_decode=True, eos_early_exit=False,
              dcfg_kw=dict(cache_layout="paged"))
    ref = _sched(cfg, params, calibrated_store, slice_len=0, **kw)
    ref.submit(list(reqs))
    got_ref = {r.uid: r for r in ref.run()}
    sl = _sched(cfg, params, calibrated_store, slice_len=2, **kw)
    sl.submit(list(reqs))
    got = {r.uid: r for r in sl.run()}
    for uid in got_ref:
        assert got[uid].text == got_ref[uid].text, uid
        assert got[uid].blocks_drafted == got_ref[uid].blocks_drafted
        assert got[uid].blocks_accepted == got_ref[uid].blocks_accepted
    assert sl.stats.blocks_drafted == ref.stats.blocks_drafted
    assert sl.stats.blocks_accepted == ref.stats.blocks_accepted


def test_sliced_shared_prefix_matches_batch(small_model,
                                            calibrated_store):
    """Paged + shared system prompt: the sliced admission program encodes
    only the per-row remainder against the shared pages (same responses
    as the batch-boundary engine, mid-generation admission included)."""
    cfg, params = small_model
    kw = dict(dcfg_kw=dict(cache_layout="paged"),
              shared_prefix="answer briefly answer briefly ")
    ref = _sched(cfg, params, calibrated_store, slice_len=0, **kw)
    assert ref.shared_len > 0  # the prefix actually occupies pages
    ref.submit(_requests("alpha", 1) + _requests("beta", 1, 10))
    got_ref = {r.uid: r for r in ref.run()}
    sl = _sched(cfg, params, calibrated_store, slice_len=1, **kw)
    sl.submit(_requests("alpha", 1))
    out = list(sl.slice_step())
    sl.submit(_requests("beta", 1, 10))   # admits against shared pages
    out += _drain(sl)
    got = {r.uid: r for r in out}
    for uid in got_ref:
        assert got[uid].text == got_ref[uid].text, uid
    assert sl.stats.mid_admits == 1
    assert sl.allocator.in_use == len(sl._shared_pages)


def test_drafter_plan_remaining_masks_done_blocks(small_model,
                                                  calibrated_store):
    from repro.spec.drafter import Drafter
    d = Drafter(calibrated_store, DCFG)
    full = d.row_mask("alpha")
    plan = d.plan_remaining(["alpha", None, "alpha"],
                            np.asarray([0, 0, 2]))
    np.testing.assert_array_equal(plan[0], full)
    assert not plan[1].any()
    np.testing.assert_array_equal(plan[2][:2], [False, False])
    np.testing.assert_array_equal(plan[2][2:], full[2:])


def test_failed_slice_requeues_and_retries_cleanly(small_model):
    """A slice that raises must not swallow requests, leak pages,
    double-count stats, or pin the task's calibration claim — a retried
    run() serves every uid and still calibrates the task."""
    cfg, params = small_model
    s = Scheduler(params, cfg,
                  dataclasses.replace(DCFG, cache_layout="paged"),
                  ecfg=EngineConfig(batch_size=2, prompt_len=PROMPT_LEN,
                                    slice_len=1))
    real = s._slice_fn
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected slice failure")
        return real(*a, **kw)

    s._slice_fn = flaky
    s.submit(_requests("delta", 2))
    with pytest.raises(RuntimeError):
        s.slice_step()
    assert s.allocator.in_use == len(s._shared_pages)  # no page leak
    assert s.pending() == 2 and s.stats.requests == 0
    assert "delta" not in s._calibrating  # claim released for the retry
    out = s.run()
    assert sorted(r.uid for r in out) == [0, 1]
    assert s.stats.requests == 2 and s.store.calibrated("delta")


def test_cpu_donation_fallback(small_model, prompts):
    """On CPU the carry is NOT donated (jax would ignore it with a
    warning): the input carry's buffers stay alive after a slice."""
    cfg, params = small_model
    if jax.default_backend() != "cpu":
        pytest.skip("CPU-only fallback check")
    table = np.full((2, NB, DCFG.steps_cap), 0.9, np.float32)
    carry = init_decode_carry(cfg, DCFG, batch=2, prompt_len=PROMPT_LEN,
                              mask_id=tok.MASK_ID)
    carry = admit_carry_rows(carry, [0, 1], prompts, table, tok.MASK_ID)
    adm = make_admit_fn(cfg, DCFG)
    carry = adm(params, carry, jnp.asarray([True, True]))
    sf = make_slice_fn(cfg, DCFG, slice_len=1)
    out = sf(params, carry, jnp.asarray(tok.MASK_ID, jnp.int32), None,
             None)
    assert not carry.resp.is_deleted()       # no donation on CPU
    assert not out.resp.is_deleted()
