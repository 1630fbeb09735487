"""End-to-end OSDT behaviour (the paper's Algorithm 1 + serving)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import DecodeConfig, EngineConfig
from repro.config.registry import get_config
from repro.core.osdt import OSDTSession
from repro.core.signature import cosine_matrix, mean_offdiag_cosine
from repro.core.decoder import make_generate_fn, result_profile
from repro.data import tokenizer as tok
from repro.serving.engine import DiffusionEngine, Request


@pytest.fixture(scope="module")
def small_model():
    from repro.models.model import init_params
    cfg = get_config("llada-8b").reduced()
    return cfg, init_params(jax.random.key(0), cfg)


def test_osdt_session_two_phase(small_model):
    cfg, params = small_model
    dcfg = DecodeConfig(max_new_tokens=16, block_size=4, policy="osdt",
                        mode="block", metric="q1", cap=0.9, slack=0.1,
                        threshold=0.9)
    sess = OSDTSession(params, cfg, dcfg, mask_id=cfg.vocab_size - 1)
    p1 = jax.random.randint(jax.random.key(1), (1, 8), 1, cfg.vocab_size - 1)
    p2 = jax.random.randint(jax.random.key(2), (1, 8), 1, cfg.vocab_size - 1)
    assert not sess.calibrated
    sess.generate(p1)          # Phase 1
    assert sess.calibrated
    table = np.asarray(sess.table)
    assert (table <= 0.9 * 0.9 + 1e-6).all()
    sess.generate(p2)          # Phase 2
    assert sess.total_nfe > 0 and sess.total_tokens == 32


def test_signature_cosine(small_model):
    cfg, params = small_model
    dcfg = DecodeConfig(max_new_tokens=16, block_size=4, policy="static",
                        threshold=0.9)
    gen = make_generate_fn(cfg, dcfg)
    tab = jnp.full((4, 4), 0.9)
    profs = []
    for seed in range(3):
        p = jax.random.randint(jax.random.key(seed), (1, 8), 1,
                               cfg.vocab_size - 1)
        profs.append(result_profile(gen(params, p, tab,
                                        jnp.asarray(cfg.vocab_size - 1))))
    m = cosine_matrix(profs)
    assert m.shape == (3, 3)
    np.testing.assert_allclose(np.diag(m), 1.0, atol=1e-6)
    assert -1.0 <= mean_offdiag_cosine(profs) <= 1.0


def test_engine_batched_serving(small_model):
    cfg, params = small_model
    dcfg = DecodeConfig(max_new_tokens=8, block_size=4, policy="osdt",
                        mode="block", metric="q1", cap=0.9, slack=0.2)
    eng = DiffusionEngine(params, cfg, dcfg,
                          ecfg=EngineConfig(batch_size=2, prompt_len=16),
                          mask_id=tok.MASK_ID)
    reqs = [Request(i, "gsm8k-syn", f"Q: what is {i}+1?\nA:")
            for i in range(3)]
    reqs.append(Request(3, "gpqa-syn", "Q: pick A or B?\nA:"))
    out = eng.submit(reqs)
    assert [r.uid for r in out] == [0, 1, 2, 3]
    assert set(eng.sessions) == {"gsm8k-syn", "gpqa-syn"}
    assert eng.stats.nfe > 0
    assert eng.stats.tokens_per_nfe > 0


def test_policy_tables():
    from repro.core import policies
    dcfg = DecodeConfig(max_new_tokens=16, block_size=4, threshold=0.8,
                        factor=0.9)
    st = policies.static_table(dcfg)
    assert (st == 0.8).all()
    ft = policies.factor_table(dcfg)
    assert ft[0, 0] == pytest.approx(0.8)
    assert (np.diff(ft, axis=1) < 0).all()  # monotone decay over steps


def test_dual_cache_mode(small_model):
    """Fast-dLLM DualCache: suffix K/V refreshed per block. Checks NFE
    accounting (prefill + 1 refresh/block + steps, no commits) and that
    generation completes."""
    import numpy as np
    from repro.core.decoder import make_generate_fn
    cfg, params = small_model
    dcfg = DecodeConfig(max_new_tokens=16, block_size=4, policy="static",
                        threshold=2.0)  # sequential: steps = block_size
    p = jax.random.randint(jax.random.key(5), (1, 8), 1, cfg.vocab_size - 1)
    tab = jnp.full((4, 4), 2.0)
    res = make_generate_fn(cfg, dcfg, cache_mode="dual")(
        params, p, tab, jnp.asarray(cfg.vocab_size - 1, jnp.int32))
    nb, bs = 4, 4
    assert int(res.nfe) == 1 + nb + nb * bs  # prefill + refreshes + steps
    assert not bool(jnp.any(res.tokens == cfg.vocab_size - 1))
    assert (np.asarray(res.steps_per_block) == bs).all()


def test_online_ema_calibration(small_model):
    cfg, params = small_model
    dcfg = DecodeConfig(max_new_tokens=16, block_size=4, policy="osdt",
                        mode="block", metric="q1", cap=0.9, slack=0.1,
                        threshold=0.9)
    sess = OSDTSession(params, cfg, dcfg, mask_id=cfg.vocab_size - 1,
                       online_ema=0.3)
    p1 = jax.random.randint(jax.random.key(1), (1, 8), 1, cfg.vocab_size - 1)
    p2 = jax.random.randint(jax.random.key(2), (1, 8), 1, cfg.vocab_size - 1)
    sess.generate(p1)
    t1 = np.asarray(sess.table).copy()
    sess.generate(p2)
    t2 = np.asarray(sess.table)
    # table evolves but respects the cap*(1-slack) bound
    assert (t2 <= 0.9 * 0.9 + 1e-5).all()
    assert t1.shape == t2.shape
