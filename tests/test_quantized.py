"""Int8 weight-streaming decode path: quantizer bounds, dequant-in-
register kernel vs oracle, chunked-fallback bit-identity, quantized
fused epilogue, and e2e int8-vs-dequantized decode bit-identity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import DecodeConfig
from repro.config.registry import get_config
from repro.core import policies
from repro.core.decoder import _norm_slice_key, make_generate_fn
from repro.kernels import ops, ref
from repro.kernels.fused_step import quantized_fused_step_pallas
from repro.kernels.quantized_matmul import quantized_matmul_pallas
from repro.models import model as M
from repro.models.cache import identity_page_table
from repro.models.quantize import (QuantizedTensor, decode_weight_bytes,
                                   dequantize, is_quantized,
                                   max_abs_error_bound,
                                   quantize_decode_params, quantize_tensor)

pytestmark = pytest.mark.quant


# ---------------------------------------------------------------------------
# quantizer: error bound, scale layout, per-projection coverage
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,axis", [
    ((64, 96), -2),        # projection [in, out]: per output column
    ((96, 64), -1),        # tied table [V, d]: per vocab row
    ((3, 64, 96), -2),     # stacked layers ride scan with kept dims
])
def test_quantize_tensor_bound_and_layout(rng, shape, axis):
    w = jax.random.normal(rng, shape, jnp.float32) * 3.0
    qt = quantize_tensor(w, axis=axis)
    assert qt.q.dtype == jnp.int8 and qt.q.shape == w.shape
    assert qt.scale.ndim == w.ndim          # keepdims: rank preserved
    assert qt.scale.shape[axis] == 1
    err = jnp.abs(dequantize(qt) - w)
    assert bool(jnp.all(err <= max_abs_error_bound(qt) + 1e-7))


def test_quantize_tensor_zero_channel():
    """All-zero output channels get scale 1 — dequant never divides by 0
    and reproduces the zeros exactly."""
    w = jnp.zeros((16, 8)).at[:, 3].set(jnp.linspace(-1, 1, 16))
    qt = quantize_tensor(w, axis=-2)
    assert float(qt.scale[0, 0]) == 1.0
    np.testing.assert_array_equal(np.asarray(dequantize(qt)[:, 0]),
                                  np.zeros(16))


@pytest.mark.parametrize("tied", [True, False])
def test_quantize_decode_params_coverage(tied):
    cfg = get_config("llada-8b").reduced(num_layers=2, max_d_model=128,
                                         vocab_size=128)
    cfg = dataclasses.replace(cfg, tie_embeddings=tied)
    params = M.init_params(jax.random.key(0), cfg)
    qp = quantize_decode_params(params, cfg)
    assert is_quantized(qp) and not is_quantized(params)
    for k in ("wq", "wk", "wv", "wo"):
        assert isinstance(qp["layers"][k], QuantizedTensor), k
    for k in ("wi_gate", "wi_up", "wo"):
        assert isinstance(qp["layers"]["mlp"][k], QuantizedTensor), k
    # norms and the gather table stay in their source dtype
    assert qp["layers"]["ln1"].dtype == params["layers"]["ln1"].dtype
    np.testing.assert_array_equal(np.asarray(qp["embed"]),
                                  np.asarray(params["embed"]))
    if tied:
        assert isinstance(qp["head_q"], QuantizedTensor)
        assert qp["head_q"].scale.shape == (cfg.vocab_size, 1)
    else:
        assert isinstance(qp["head"], QuantizedTensor)
        assert qp["head"].scale.shape == (1, cfg.vocab_size)
    # int8 payload + f32 scales ≈ 1/4 the f32 footprint
    ratio = decode_weight_bytes(params, cfg) / decode_weight_bytes(qp, cfg)
    assert 3.0 < ratio <= 4.0


# ---------------------------------------------------------------------------
# kernel vs oracle; chunked XLA fallback bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,K,N", [
    (1, 128, 128),      # tile-exact single row
    (8, 256, 1024),     # multi N-tile
    (13, 200, 513),     # ragged everything: row/K/N padding
])
@pytest.mark.parametrize("transpose", [False, True])
def test_quantized_matmul_kernel_vs_oracle(rng, R, K, N, transpose):
    ks = jax.random.split(rng, 2)
    x = jax.random.normal(ks[0], (R, K), jnp.float32)
    w = jax.random.normal(ks[1], (N, K) if transpose else (K, N),
                          jnp.float32)
    qt = quantize_tensor(w, axis=-1 if transpose else -2)
    got = quantized_matmul_pallas(x, qt.q, qt.scale, transpose=transpose,
                                  interpret=True)
    want = ref.quantized_matmul_ref(x, qt.q, qt.scale, transpose=transpose)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("N,transpose", [(1024, False), (4096, True),
                                         (129, False)])
def test_quantized_matmul_xla_chunking_bit_identical(rng, N, transpose):
    """The off-TPU chunked dequant-matmul (``_chunks(N)``-way scan) is
    BITWISE the whole-dequant oracle — chunking only groups columns."""
    x = jax.random.normal(rng, (4, 7, 64), jnp.float32)
    w = jax.random.normal(jax.random.key(5),
                          (N, 64) if transpose else (64, N), jnp.float32)
    qt = quantize_tensor(w, axis=-1 if transpose else -2)
    got = ops.quantized_matmul(x, qt, transpose=transpose)
    want = ref.quantized_matmul_ref(x, qt.q, qt.scale, transpose=transpose)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("quota", [0, 2])
def test_quantized_fused_step_kernel_vs_oracle(rng, tied, quota):
    """The quantized fused epilogue (int8 lm-head tiles dequantized
    inside the logit stream) matches the dequantize-first oracle."""
    R, M_, V = 8, 128, 512
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (R, M_), jnp.float32)
    w = jax.random.normal(ks[1], (V, M_) if tied else (M_, V), jnp.float32)
    qt = quantize_tensor(w, axis=-1 if tied else -2)
    tau = jax.random.uniform(ks[2], (R,), jnp.float32)
    masked = jax.random.bernoulli(ks[3], 0.7, (R,))
    conf, tok, above = quantized_fused_step_pallas(
        x, qt.q, qt.scale, tau, masked, tied=tied, quota=quota,
        interpret=True)
    cr, tr, ar = ref.fused_step_ref(x, dequantize(qt), tau, masked,
                                    tied=tied, quota=quota)
    np.testing.assert_allclose(np.asarray(conf), np.asarray(cr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(tr))
    np.testing.assert_array_equal(np.asarray(above), np.asarray(ar))


# ---------------------------------------------------------------------------
# e2e decode: int8 program == dequantized-weights program, bitwise
# ---------------------------------------------------------------------------

DCFG = DecodeConfig(max_new_tokens=16, block_size=4, policy="static",
                    threshold=0.9, page_size=4)
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


def _dequant_tree(params):
    return jax.tree_util.tree_map(
        lambda t: dequantize(t) if isinstance(t, QuantizedTensor) else t,
        params, is_leaf=lambda t: isinstance(t, QuantizedTensor))


def _pool(cfg, mode):
    max_len = PROMPT_LEN + DCFG.max_new_tokens \
        + (DCFG.block_size if mode == "dual" else 0)
    n_log = DCFG.pages_per_seq(max_len)
    pt = identity_page_table(2, max_len, DCFG.page_size)
    shape = (cfg.num_layers, 2 * n_log, DCFG.page_size,
             cfg.num_kv_heads, cfg.resolved_head_dim)
    dt = M.param_dtype(cfg)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt), pt


@pytest.mark.parametrize("mode,layout", [("prefix", "dense"),
                                         ("dual", "paged")])
@pytest.mark.parametrize("fusion", ["unfused", "fused"])
def test_generate_int8_matches_dequantized(small_model, prompts, mode,
                                           layout, fusion):
    """Decoding with int8 params is BIT-identical to decoding with the
    same weights dequantized up front: the chunked fallback dequantizes
    before every contraction (accuracy contract), so the int8 program's
    numerics are exactly the dequantized program's — quantization error
    shows up only relative to the ORIGINAL weights, never between these
    two."""
    cfg, params = small_model
    qp = quantize_decode_params(params, cfg)
    table = jnp.asarray(policies.static_table(DCFG))
    mask = jnp.asarray(3, jnp.int32)
    args = [prompts, table, mask, None, None]
    if layout == "paged":
        args += list(_pool(cfg, mode))
    base = make_generate_fn(cfg, DCFG, cache_mode=mode, cache_layout=layout,
                            step_fusion=fusion)(_dequant_tree(qp), *args)
    quant = make_generate_fn(cfg, DCFG, cache_mode=mode,
                             cache_layout=layout, step_fusion=fusion,
                             weight_dtype="int8")(qp, *args)
    np.testing.assert_array_equal(np.asarray(base.tokens),
                                  np.asarray(quant.tokens))
    np.testing.assert_array_equal(np.asarray(base.conf),
                                  np.asarray(quant.conf))
    assert int(base.nfe) == int(quant.nfe) > 0


def test_sliced_int8_matches_dequantized(small_model, prompts):
    """The step-sliced int8 decode (slice_len 1, the maximally-sliced
    loop) is bitwise the monolithic dequantized oracle too. The
    token-match-vs-bf16 gate (>= 0.95, equal accuracy) is checked on the
    TRAINED bench model in ``benchmarks/quantized_decode.py`` — on a
    random-init model the near-uniform logits make match rates
    meaningless, while this bitwise contract is exact everywhere."""
    from repro.core.decoder import (admit_carry_rows, init_decode_carry,
                                    make_admit_fn, make_slice_fn)
    cfg, params = small_model
    qp = quantize_decode_params(params, cfg)
    table = jnp.asarray(policies.static_table(DCFG))
    mask = jnp.asarray(3, jnp.int32)
    base = make_generate_fn(cfg, DCFG)(
        _dequant_tree(qp), prompts, table, mask, None, None)
    carry = init_decode_carry(cfg, DCFG, batch=2, prompt_len=PROMPT_LEN,
                              mask_id=3)
    carry = admit_carry_rows(carry, [0, 1], np.asarray(prompts),
                             np.asarray(table), 3)
    adm = make_admit_fn(cfg, DCFG)
    carry = adm(qp, carry, jnp.asarray([0, 1]))
    sf = make_slice_fn(cfg, DCFG, slice_len=1, weight_dtype="int8")
    while int(np.asarray(carry.cursor).min()) < DCFG.num_blocks:
        carry = sf(qp, carry, mask, None, None)
    np.testing.assert_array_equal(np.asarray(base.tokens),
                                  np.asarray(carry.resp))
    np.testing.assert_array_equal(np.asarray(base.conf),
                                  np.asarray(carry.conf))
    assert int(base.nfe) == int(carry.nfe)


def test_weight_dtype_program_key(small_model):
    """``weight_dtype`` is part of the program identity: "" normalizes to
    the DecodeConfig's dtype (default bf16), int8 keys a distinct
    program, and unknown dtypes refuse loudly."""
    cfg, _ = small_model
    base = (cfg, DCFG, True, "prefix", "auto", "dense", 0, "step", "")
    kb = _norm_slice_key(*base, "")
    ki = _norm_slice_key(*base, "int8")
    assert kb[-1] == "bf16" and ki[-1] == "int8" and kb[:-1] == ki[:-1]
    dq = dataclasses.replace(DCFG, weight_dtype="int8")
    assert _norm_slice_key(cfg, dq, True, "prefix", "auto", "dense", 0,
                           "step", "", "")[-1] == "int8"
    with pytest.raises(AssertionError):
        _norm_slice_key(*base, "fp4")
