"""Fused denoising-step epilogue (``ops.fused_step``): kernel vs oracle,
threshold semantics, fused-vs-unfused decode bit-identity, and the
µs/step roofline model's invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import DecodeConfig
from repro.config.registry import get_config
from repro.core import policies
from repro.core.decoder import (admit_carry_rows, init_decode_carry,
                                make_admit_fn, make_generate_fn,
                                make_slice_fn)
from repro.kernels import ops
from repro.kernels.fused_step import fused_step_pallas
from repro.kernels.ref import fused_step_ref
from repro.models import model as M
from repro.models.cache import identity_page_table
from repro.roofline.analytic import STEP_VARIANTS, step_time_model

pytestmark = pytest.mark.fused


# ---------------------------------------------------------------------------
# kernel vs oracle (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,M_,V", [
    (1, 128, 128),      # single row, tile-exact
    (8, 256, 2048),     # multi-tile vocab
    (13, 200, 1000),    # everything ragged: row/model/vocab padding
    (32, 128, 513),     # vocab one past a tile boundary
])
@pytest.mark.parametrize("tied", [True, False])
def test_fused_step_kernel_matches_oracle(rng, R, M_, V, tied):
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (R, M_), jnp.float32)
    w = jax.random.normal(ks[1], (V, M_) if tied else (M_, V), jnp.float32)
    tau = jax.random.uniform(ks[2], (R,), jnp.float32)
    masked = jax.random.bernoulli(ks[3], 0.7, (R,))
    conf, tok, above = fused_step_pallas(x, w, tau, masked, tied=tied,
                                         vocab_tile=256, interpret=True)
    cr, tr, ar = fused_step_ref(x, w, tau, masked, tied=tied)
    np.testing.assert_allclose(np.asarray(conf), np.asarray(cr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(tr))
    np.testing.assert_array_equal(np.asarray(above), np.asarray(ar))


def test_fused_step_cross_tile_argmax_tie():
    """Equal logit maxima in different vocab tiles: the fused kernel must
    return the FIRST occurrence (jnp.argmax), also when the tie's first
    element sits at a tile boundary or in the last (padded) tile."""
    M_, V = 64, 1024
    w = jnp.eye(V, M_) * 5.0  # logit v = 5 * x[v] for v < M_
    x = jnp.zeros((3, M_)).at[0, 10].set(1.0).at[0, 40].set(1.0) \
        .at[1, 0].set(1.0).at[1, 63].set(1.0) \
        .at[2, 32].set(1.0).at[2, 33].set(1.0).at[2, 63].set(1.0)
    tau = jnp.zeros((3,))
    masked = jnp.ones((3,), bool)
    _, tok, _ = fused_step_pallas(x, w, tau, masked, tied=True,
                                  vocab_tile=128, interpret=True)
    _, tr, _ = fused_step_ref(x, w, tau, masked, tied=True)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(tr))
    assert np.asarray(tok).tolist() == [10, 0, 32]


def test_fused_step_threshold_semantics(rng):
    """``above`` is the paper's rule exactly: masked & (conf > tau) —
    unmasked rows never fire, conf == tau does not fire."""
    R, M_, V = 8, 128, 256
    x = jax.random.normal(rng, (R, M_), jnp.float32)
    w = jax.random.normal(jax.random.key(7), (V, M_), jnp.float32)
    conf, _, _ = fused_step_pallas(x, w, jnp.zeros((R,)),
                                   jnp.ones((R,), bool), tied=True,
                                   interpret=True)
    # tau exactly equal to conf: strict compare -> not above
    _, _, above_eq = fused_step_pallas(x, w, conf, jnp.ones((R,), bool),
                                       tied=True, interpret=True)
    assert not np.asarray(above_eq).any()
    # unmasked rows never fire even at tau = -inf
    _, _, above_um = fused_step_pallas(x, w, jnp.full((R,), -1.0),
                                       jnp.zeros((R,), bool), tied=True,
                                       interpret=True)
    assert not np.asarray(above_um).any()


def test_fused_step_ops_dispatch(rng, monkeypatch):
    """``ops.fused_step`` routes to the Pallas kernel when the TPU gate is
    on (recorded; interpret) and to the bit-identical jnp chain off-TPU."""
    R, M_, V = 4, 128, 256
    x = jax.random.normal(rng, (1, R, M_), jnp.float32)
    w = jax.random.normal(jax.random.key(3), (V, M_), jnp.float32)
    tau = jnp.full((1, R), 0.5)
    masked = jnp.ones((1, R), bool)
    off = ops.fused_step(x, w, tau, masked, tied=True)

    calls = []
    real = ops.fused_step_pallas

    def record(*a, **kw):
        calls.append(1)
        kw["interpret"] = True
        return real(*a, **kw)

    monkeypatch.setattr(ops, "fused_step_pallas", record)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    on = ops.fused_step(x, w, tau, masked, tied=True)
    assert calls
    for a, b in zip(off, on):
        assert a.shape == b.shape  # leading dims preserved
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# decode-loop bit-identity: step_fusion="fused" vs the unfused program
# ---------------------------------------------------------------------------

DCFG = DecodeConfig(max_new_tokens=16, block_size=4, policy="static",
                    threshold=0.9, page_size=4)
NB = DCFG.num_blocks
PROMPT_LEN = 8


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("llada-8b").reduced(num_layers=2, max_d_model=128,
                                         vocab_size=128)
    cfg = dataclasses.replace(cfg, mask_token_id=3)
    return cfg, M.init_params(jax.random.key(0), cfg)


@pytest.fixture(scope="module")
def prompts():
    return jax.random.randint(jax.random.key(1), (2, PROMPT_LEN), 4, 128,
                              jnp.int32)


def _pool(cfg, mode):
    max_len = PROMPT_LEN + DCFG.max_new_tokens \
        + (DCFG.block_size if mode == "dual" else 0)
    n_log = DCFG.pages_per_seq(max_len)
    pt = identity_page_table(2, max_len, DCFG.page_size)
    shape = (cfg.num_layers, 2 * n_log, DCFG.page_size,
             cfg.num_kv_heads, cfg.resolved_head_dim)
    dt = M.param_dtype(cfg)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt), pt


@pytest.mark.parametrize("mode,layout", [
    ("prefix", "dense"), ("dual", "dense"), ("none", "dense"),
    ("prefix", "paged"), ("dual", "paged"),
])
def test_generate_fused_bit_identity(small_model, prompts, mode, layout):
    """Monolithic decode with the fused epilogue is BIT-identical to the
    unfused program: same tokens, conf, seq_steps, nfe (the off-TPU fused
    chain lowers to the same HLO — the kernel's contract on TPU)."""
    cfg, params = small_model
    table = jnp.asarray(policies.static_table(DCFG))
    mask = jnp.asarray(3, jnp.int32)
    args = [params, prompts, table, mask, None, None]
    if layout == "paged":
        args += list(_pool(cfg, mode))
    base = make_generate_fn(cfg, DCFG, cache_mode=mode,
                            cache_layout=layout)(*args)
    fused = make_generate_fn(cfg, DCFG, cache_mode=mode,
                             cache_layout=layout,
                             step_fusion="fused")(*args)
    np.testing.assert_array_equal(np.asarray(base.tokens),
                                  np.asarray(fused.tokens))
    np.testing.assert_array_equal(np.asarray(base.conf),
                                  np.asarray(fused.conf))
    np.testing.assert_array_equal(np.asarray(base.seq_steps),
                                  np.asarray(fused.seq_steps))
    assert int(base.nfe) == int(fused.nfe) > 0


@pytest.mark.parametrize("mode,layout", [("prefix", "dense"),
                                         ("dual", "paged")])
def test_sliced_fused_bit_identity(small_model, prompts, mode, layout):
    """Sliced decode with step_fusion="fused" == the monolithic unfused
    oracle, bitwise, at slice_len 1 (the maximally-sliced loop)."""
    cfg, params = small_model
    table = jnp.asarray(policies.static_table(DCFG))
    mask = jnp.asarray(3, jnp.int32)
    args = [params, prompts, table, mask, None, None]
    pool_kw = {}
    if layout == "paged":
        pk, pv, pt = _pool(cfg, mode)
        args += [pk, pv, pt]
        pool_kw = dict(pool_k=pk, pool_v=pv, page_table=pt)
    base = make_generate_fn(cfg, DCFG, cache_mode=mode,
                            cache_layout=layout)(*args)
    carry = init_decode_carry(cfg, DCFG, batch=2, prompt_len=PROMPT_LEN,
                              mask_id=3, cache_mode=mode,
                              cache_layout=layout, **pool_kw)
    carry = admit_carry_rows(
        carry, [0, 1], np.asarray(prompts), np.asarray(table), 3,
        page_rows=np.asarray(pool_kw["page_table"])
        if layout == "paged" else None)
    adm = make_admit_fn(cfg, DCFG, cache_mode=mode, cache_layout=layout)
    carry = adm(params, carry, jnp.asarray([0, 1]))
    sf = make_slice_fn(cfg, DCFG, slice_len=1, cache_mode=mode,
                       cache_layout=layout, step_fusion="fused")
    while int(np.asarray(carry.cursor).min()) < NB:
        carry = sf(params, carry, mask, None, None)
    np.testing.assert_array_equal(np.asarray(base.tokens),
                                  np.asarray(carry.resp))
    np.testing.assert_array_equal(np.asarray(base.conf),
                                  np.asarray(carry.conf))
    np.testing.assert_array_equal(np.asarray(base.seq_steps),
                                  np.asarray(carry.seq_steps))
    assert int(base.nfe) == int(carry.nfe)


@pytest.mark.parametrize("quota", [1, 2])
def test_generate_quota_fused_bit_identity(small_model, prompts, quota):
    """The fused epilogue now carries the quota (fixed-step) baseline
    too: in-kernel per-row top-k over each block's masked confidences,
    bit-identical to the unfused stable-argsort rule — same tokens,
    conf, seq_steps, nfe."""
    cfg, params = small_model
    table = jnp.asarray(policies.static_table(DCFG))
    mask = jnp.asarray(3, jnp.int32)
    base = make_generate_fn(cfg, DCFG, quota=quota)(
        params, prompts, table, mask, None, None)
    fused = make_generate_fn(cfg, DCFG, quota=quota, step_fusion="fused")(
        params, prompts, table, mask, None, None)
    np.testing.assert_array_equal(np.asarray(base.tokens),
                                  np.asarray(fused.tokens))
    np.testing.assert_array_equal(np.asarray(base.conf),
                                  np.asarray(fused.conf))
    np.testing.assert_array_equal(np.asarray(base.seq_steps),
                                  np.asarray(fused.seq_steps))
    assert int(base.nfe) == int(fused.nfe) > 0
    # the sliced family accepts the combination too (it refused pre-int8)
    make_slice_fn(cfg, DCFG, slice_len=1, quota=quota, step_fusion="fused")


# ---------------------------------------------------------------------------
# µs/step roofline model invariants
# ---------------------------------------------------------------------------

def test_step_time_model_invariants():
    cfg = get_config("llada-8b")
    out = step_time_model(cfg, batch=8, ctx=4096, block_size=32)
    assert set(out) == set(STEP_VARIANTS) and len(out) == 8
    for layout in ("dense", "paged"):
        for rows in ("scalar", "per_row"):
            fu = out[f"{layout}/{rows}/fused"]
            un = out[f"{layout}/{rows}/unfused"]
            # 3-dispatch epilogue chain vs 1 (>= the 1.5x acceptance bar)
            assert un["dispatches"] - cfg.num_layers == 3
            assert fu["dispatches"] - cfg.num_layers == 1
            assert (un["dispatches"] - cfg.num_layers) \
                >= 1.5 * (fu["dispatches"] - cfg.num_layers)
            # ... and the logits' HBM round-trip
            assert un["hbm_bytes"] > fu["hbm_bytes"]
            assert un["us"] > fu["us"]
        # per-row tile skipping beats the batch-max scalar geometry
        assert out[f"{layout}/per_row/unfused"]["us"] \
            < out[f"{layout}/scalar/unfused"]["us"]
    for t in out.values():
        assert t["us"] > 0 and t["bound"] in ("compute", "memory",
                                              "dispatch")
