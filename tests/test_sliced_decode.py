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
        carry = adm(params, carry, jnp.asarray([0, 1]))
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
        carry = adm(params, carry, jnp.asarray([0, 1]))
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
    kw = dict(batch_size=2, prompt_len=PROMPT_LEN)
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


def test_sliced_matches_batch_boundary(small_model, calibrated_store,
                                       generate_reference):
    """The scheduler delivers what the monolithic oracle decodes for the
    same admitted set as one batch (pre-calibrated tables): texts,
    per-request NFE and the forward count."""
    cfg, params = small_model
    reqs = _requests("alpha", 2) + _requests("beta", 2, 10)
    sl = _sched(cfg, params, calibrated_store, batch_size=4)
    want, res = generate_reference(sl, reqs)
    sl.submit(list(reqs))
    got = {r.uid: r for r in sl.run()}
    assert got.keys() == want.keys()
    for uid, r in got.items():
        assert (r.text, r.nfe) == want[uid][:2], uid
    assert sl.stats.tokens == sum(len(r.token_ids) for r in got.values())
    assert sl.stats.nfe == int(res.nfe)
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


def test_sliced_calibration_matches_batch(small_model, generate_reference):
    """An uncalibrated task's first request calibrates to the table the
    monolithic oracle's recording of the same row gives (same recording
    rows, ingested at retirement)."""
    cfg, params = small_model
    b = Scheduler(params, cfg, DCFG,
                  ecfg=EngineConfig(batch_size=2, prompt_len=PROMPT_LEN))
    _, res = generate_reference(b, _requests("gamma", 1))
    ref = CalibrationStore(DCFG)
    ref.ingest("gamma", result_profile(res, row=0))
    b.submit(_requests("gamma", 1))
    b.run()
    assert b.store.calibrated("gamma")
    np.testing.assert_array_equal(ref.tables["gamma"],
                                  b.store.tables["gamma"])


def test_sliced_spec_matches_monolithic_spec(small_model,
                                             calibrated_store,
                                             generate_reference):
    """spec_decode engines: the scheduler delivers the texts of the
    monolithic draft-variant oracle and drafts the same blocks (plan
    handed over once, at the row's admission slice)."""
    cfg, params = small_model
    reqs = _requests("alpha", 2) + _requests("beta", 2, 10)
    sl = _sched(cfg, params, calibrated_store, batch_size=4,
                spec_decode=True, eos_early_exit=False, slice_len=2,
                dcfg_kw=dict(cache_layout="paged"))
    want, res = generate_reference(sl, reqs)
    sl.submit(list(reqs))
    got = {r.uid: r for r in sl.run()}
    assert got.keys() == want.keys()
    for uid, (text, nfe, drafted, accepted) in want.items():
        assert got[uid].text == text, uid
        assert got[uid].nfe == nfe
        assert (got[uid].blocks_drafted, got[uid].blocks_accepted) == \
            (drafted, accepted)
    assert sl.stats.blocks_drafted == int(np.asarray(res.blocks_drafted).sum())
    assert sl.stats.blocks_accepted == \
        int(np.asarray(res.blocks_accepted).sum())


def test_sliced_shared_prefix_matches_batch(small_model,
                                            calibrated_store,
                                            generate_reference):
    """Paged + shared system prompt: the sliced admission program encodes
    only the per-row remainder against the shared pages (the responses
    the monolithic oracle decodes for both rows as one batch,
    mid-generation admission included)."""
    cfg, params = small_model
    sl = _sched(cfg, params, calibrated_store,
                dcfg_kw=dict(cache_layout="paged"),
                shared_prefix="answer briefly answer briefly ")
    assert sl.shared_len > 0  # the prefix actually occupies pages
    want, _ = generate_reference(
        sl, _requests("alpha", 1) + _requests("beta", 1, 10))
    sl.submit(_requests("alpha", 1))
    out = list(sl.slice_step())
    sl.submit(_requests("beta", 1, 10))   # admits against shared pages
    out += _drain(sl)
    got = {r.uid: r for r in out}
    for uid, (text, nfe, _, _) in want.items():
        assert (got[uid].text, got[uid].nfe) == (text, nfe), uid
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


@pytest.mark.parametrize("phase", ["slice", "admit"])
def test_failed_slice_requeues_and_retries_cleanly(small_model, phase):
    """A slice — or the admission prefill before it — that raises must
    not swallow requests, leak pages, double-count stats, or pin the
    task's calibration claim — a retried run() serves every uid and
    still calibrates the task."""
    cfg, params = small_model
    s = Scheduler(params, cfg,
                  dataclasses.replace(DCFG, cache_layout="paged"),
                  ecfg=EngineConfig(batch_size=2, prompt_len=PROMPT_LEN))
    name = f"_{phase}_fn"
    real = getattr(s, name)
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError(f"injected {phase} failure")
        return real(*a, **kw)

    setattr(s, name, flaky)
    s.submit(_requests("delta", 2))
    with pytest.raises(RuntimeError):
        s.slice_step()
    assert s.allocator.in_use == len(s._shared_pages)  # no page leak
    assert s.pending() == 2 and s.stats.requests == 0
    assert s.carry is None and all(sl.state == "free" for sl in s.slots)
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
    carry = adm(params, carry, jnp.asarray([0, 1]))
    sf = make_slice_fn(cfg, DCFG, slice_len=1)
    out = sf(params, carry, jnp.asarray(tok.MASK_ID, jnp.int32), None,
             None)
    assert not carry.resp.is_deleted()       # no donation on CPU
    assert not out.resp.is_deleted()


# ---------------------------------------------------------------------------
# row-bucketed admission: the prefill runs over the admitted rows only
# ---------------------------------------------------------------------------

ADMIT_B = 4


def _admit_carry(cfg, branch):
    """A B=4 carry with every row's prompt and page row in place and the
    cache filled with noise, so a write that should not happen shows."""
    Sp = 8 if branch == "shared" else 0
    layout = "dense" if branch == "dense" else "paged"
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, 256, (ADMIT_B, PROMPT_LEN)).astype(np.int32)
    table = np.full((ADMIT_B, NB, DCFG.steps_cap), 0.9, np.float32)
    pool_kw, page_rows = {}, None
    if layout == "paged":
        max_len = PROMPT_LEN + DCFG.max_new_tokens
        n_log = DCFG.pages_per_seq(max_len)
        pt = np.array(identity_page_table(ADMIT_B, max_len,
                                           DCFG.page_size))
        pt[:, :Sp // DCFG.page_size] = pt[0, :Sp // DCFG.page_size]
        shape = (cfg.num_layers, ADMIT_B * n_log, DCFG.page_size,
                 cfg.num_kv_heads, cfg.resolved_head_dim)
        pool_kw = dict(pool_k=jnp.asarray(rng.standard_normal(shape),
                                          M.param_dtype(cfg)),
                       pool_v=jnp.asarray(rng.standard_normal(shape),
                                          M.param_dtype(cfg)),
                       page_table=jnp.asarray(pt))
        page_rows = pt
    carry = init_decode_carry(cfg, DCFG, batch=ADMIT_B,
                              prompt_len=PROMPT_LEN, mask_id=tok.MASK_ID,
                              cache_layout=layout, shared_prefix_len=Sp,
                              **pool_kw)
    if layout == "dense":
        kv = dict(carry.cache["attn"])
        for name in ("k", "v"):
            kv[name] = jnp.asarray(rng.standard_normal(kv[name].shape),
                                   kv[name].dtype)
        carry = carry._replace(cache={"attn": kv})
    carry = admit_carry_rows(carry, list(range(ADMIT_B)), prompts, table,
                             tok.MASK_ID, page_rows=page_rows)
    adm = make_admit_fn(cfg, DCFG, cache_layout=layout, shared_prefix_len=Sp)
    return carry, adm


@pytest.mark.parametrize("branch", ["paged", "shared", "radix", "dense"])
def test_bucketed_admission_matches_whole_batch(small_model, branch):
    """Admitting rows {1} then {0, 2} of a B=4 carry writes what the
    whole-batch admission writes for those rows; row 3's cache stays
    bit-exact, and a bucket of pad entries (index B) writes nothing at
    all. The shared-prefix branch is bitwise on CPU. The full-prompt
    prefills are not: XLA's CPU matmuls order their sums by the batch's
    row count, so a 1- or 2-row prefill differs from the 4-row one by
    ~2e-6 (a 4-row bucket with a pad entry is bitwise); those branches
    hold to the paged-cache tests' tolerance."""
    cfg, params = small_model
    # radix: row 1 hits two pages, row 2 one, rows 0 and 3 miss
    pfx = np.asarray([0, 8, 4, 0], np.int32) if branch == "radix" else None

    def call(adm, carry, rows):
        rows = np.asarray(rows, np.int32)
        if pfx is None:
            return adm(params, carry, jnp.asarray(rows))
        return adm(params, carry, jnp.asarray(rows),
                   jnp.asarray(np.where(rows < ADMIT_B,
                                        pfx[np.minimum(rows, ADMIT_B - 1)],
                                        0)))

    init, adm = _admit_carry(cfg, branch)
    whole = call(adm, init, range(ADMIT_B))
    part, _ = _admit_carry(cfg, branch)
    part = call(adm, part, [1])
    part = call(adm, part, [0, 2])
    kv0, kvw, kvp = (c.cache["attn"] for c in (init, whole, part))
    if branch == "dense":
        names, untouched = ("k", "v"), (slice(None), 3)
    else:
        names = ("kp", "vp")
        untouched = (slice(None), np.asarray(kv0["pt"])[3])
    for name in names:
        got = np.asarray(kvp[name])
        want = np.asarray(kvw[name]).copy()
        want[untouched] = np.asarray(kv0[name])[untouched]
        np.testing.assert_array_equal(got[untouched], want[untouched])
        if branch == "shared":
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for name in ("pos", "length"):
        np.testing.assert_array_equal(np.asarray(kvp[name]),
                                      np.asarray(kvw[name]))
    assert int(part.nfe) == int(init.nfe) + 2

    pads = call(adm, part, [ADMIT_B, ADMIT_B])
    for name, a in pads.cache["attn"].items():
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(part.cache["attn"][name]),
                                      err_msg=name)


def test_admission_prefill_rows_counter(small_model, calibrated_store):
    """``prefill_rows`` counts the rows the admission forward computed:
    one request admitted mid-generation into a B=4 batch adds 1, not 4;
    a burst of 3 adds 4 (its power-of-two bucket)."""
    cfg, params = small_model
    s = _sched(cfg, params, calibrated_store, batch_size=4,
               dcfg_kw=dict(cache_layout="paged"))
    s.submit(_requests("alpha", 1))
    s.slice_step()
    assert s.stats.prefill_rows == 1
    s.submit(_requests("beta", 1, 10))   # arrives mid-generation
    s.slice_step()
    assert s.stats.mid_admits == 1
    assert s.stats.prefill_rows == 2
    _drain(s)
    s.submit(_requests("alpha", 3, 20))  # a burst of three
    s.slice_step()
    assert s.stats.prefill_rows == 6
    assert s.stats.requests == 5
    _drain(s)
