"""Block diffusion decoder (semi-autoregressive MDLM generation).

One compiled program per (cfg, dcfg, variant); the threshold table is a
runtime argument so static / factor / OSDT share the same executable — the
paper's "negligible overhead" property holds by construction.

Two variants:
  * ``use_cache=True``  — Fast-dLLM prefix KV-cache: prompt is prefilled
    (bidirectionally), each denoising step runs ``block_step`` over the
    active block only, and the block's K/V are committed after it completes
    (one extra forward per block, counted in NFE).
  * ``use_cache=False`` — vanilla LLaDA: every step is a full forward over
    [prompt ∥ response] with all future blocks still masked.

Unmasking rules per step (all shapes static; decisions are boolean masks):
  quota  > 0 : LLaDA fixed-step baseline — top-``quota`` masked positions.
  quota == 0 : threshold rule — unmask all masked positions with
               confidence > table[block, step]; if none clears it, the
               single most-confident masked position (Algorithm 1 l.19-21).

Always records the calibration signal (conf of masked positions of EVERY
live batch row per (block, step)) — ``[B, nb, steps_cap, block_size]`` is
tiny at serving block sizes and lets the scheduler calibrate several new
tasks inside one mixed batch (one recorded row each).
"""
from __future__ import annotations

from functools import lru_cache
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import DecodeConfig, ModelConfig
from repro.core.calibrate import CalibrationProfile
from repro.core.confidence import confidence
from repro.kernels import ops as kops
from repro.models import cache as cache_lib
from repro.models import model as M
from repro.models.quantize import WEIGHT_DTYPES

Array = jax.Array


class GenerateResult(NamedTuple):
    tokens: Array        # [B, max_new_tokens]
    nfe: Array           # [] int32 — model forwards executed
    conf: Array          # [B, nb, steps_cap, block_size] float32
    conf_valid: Array    # same, bool (False once a row retires/dies)
    steps_per_block: Array  # [nb] int32 — batch-max steps per block
    seq_steps: Array     # [B, nb] int32 — steps each row was live+masked
    live: Array          # [B] bool — row still live at exit (no EOS seen)
    blocks_drafted: Array   # [B] int32 — blocks speculatively drafted
    blocks_accepted: Array  # [B] int32 — drafted blocks that verified
    # confidence-drift telemetry (obs.drift): accumulated in-program so
    # the host drains them at slice boundaries only — no per-step sync
    thr_steps: Array = None     # [B, nb] i32 — steps where >=1 position
    #                             cleared tau outright (no fallback)
    margin_sum: Array = None    # [B, nb] f32 — sum (conf - tau) cleared
    margin_n: Array = None      # [B, nb] i32 — cleared positions


def _threshold_fallback(conf: Array, masked: Array, above: Array,
                        live: Optional[Array]) -> Array:
    """Algorithm 1 l.19-21: positions already above threshold, plus the
    single most-confident masked position for rows where none cleared it.
    ``above`` is the threshold rule's [B, bs] verdict — computed either
    host-side (``_unmask_choice``) or in-kernel (``ops.fused_step``); the
    cross-row argmax fallback is [B, bs]-sized and stays here. The
    fallback only fires for *live* rows — dead slots / EOS-finished rows
    must not be forced to denoise."""
    conf_m = jnp.where(masked, conf, -jnp.inf)
    best = jnp.argmax(conf_m, axis=-1)
    need_fb = (~jnp.any(above, axis=-1)) & jnp.any(masked, axis=-1)
    if live is not None:
        need_fb = need_fb & live
    fb = jax.nn.one_hot(best, conf.shape[-1], dtype=bool) & need_fb[:, None]
    return above | (fb & masked)


def _unmask_choice(conf: Array, toks: Array, block: Array, mask_id: Array,
                   tau: Array, quota: int,
                   live: Optional[Array] = None) -> Array:
    """Boolean [B, bs] of positions to unmask this step.

    ``tau`` is scalar or per-row [B] (per-slot threshold tables).
    """
    masked = block == mask_id
    conf_m = jnp.where(masked, conf, -jnp.inf)
    if quota > 0:
        order = jnp.argsort(jnp.argsort(-conf_m, axis=-1), axis=-1)
        return (order < quota) & masked
    above = (conf_m > jnp.reshape(tau, (-1, 1))) & masked
    return _threshold_fallback(conf, masked, above, live)


def make_generate_fn(cfg: ModelConfig, dcfg: DecodeConfig, *,
                     use_cache: bool = True, quota: int = 0,
                     use_kernel: bool = False, cache_mode: str = "",
                     attn_impl: str = "", cache_layout: str = "",
                     shared_prefix_len: int = 0, variant: str = "step",
                     step_fusion: str = "", weight_dtype: str = ""):
    """Build (or fetch) the jitted generate function.

    fn(params, prompt [B, P] int32, table, mask_id [],
       live [B] bool = None, eos_id [] = None) -> GenerateResult

    With the PAGED cache layout three trailing runtime args are added:
    fn(..., pool_k, pool_v, page_table) where pool_k/v
    [L, num_pages, page_size, Kh, D] is the engine-owned page pool and
    page_table [B, n_log] maps each row's logical pages onto it (-1 =
    unmapped; dead rows pin zero pages). The pool is read (and its
    updated copy used internally) but NOT returned — decode only ever
    writes pages that are private to this batch's rows, so the caller's
    pool keeps exactly its pre-call contents (shared-prefix pages
    survive by construction: copy-on-write boundaries are page-aligned).

    ``table`` is the threshold table — per-slot [B, nb, steps_cap]
    (continuous-batching: every row may carry a different task's
    calibrated table) or the legacy shared [nb, steps_cap], which is
    broadcast over the batch at trace time. Either way it stays a runtime
    argument: one compiled program serves every policy and task mix.

    ``live`` marks rows that should decode. Dead rows (pad rows of a
    batch) never trigger the argmax fallback, never keep the step loop
    alive, and have their masks flushed in one ride-along step — an
    all-dead block costs zero forwards. ``eos_id`` (pass ``None`` to
    disable) retires a row once a *completed* block of its response
    contains EOS: all later blocks are skipped for that row, and the
    per-block commit / dual refresh forwards are skipped entirely once
    every row is retired.

    ``cache_mode``: "prefix" (Fast-dLLM prefix cache, default when
    use_cache), "dual" (prefix + suffix: the response region's K/V are
    refreshed once per block so steps see the future masked blocks too —
    Fast-dLLM DualCache), or "none" (vanilla LLaDA full re-forward).

    ``attn_impl`` (default ``dcfg.attn_impl``) selects the block-step
    attention path — auto | dense | flash | kernel (KERNELS.md). The
    "none" cache mode runs full forwards and is unaffected.

    ``cache_layout`` (default ``dcfg.cache_layout``): "dense" keeps the
    per-row buffer slices; "paged" routes every cache access through the
    page-table indirection (SERVING.md "Paged KV"). ``shared_prefix_len``
    (paged only, a page multiple) marks the first ``Sp`` prompt positions
    as ALREADY PREFILLED in shared pool pages: prefill then encodes only
    ``prompt[:, Sp:]`` against them (Fast-dLLM prefix semantics — the
    remainder attends [shared pages ∥ itself]); with ``0`` the paged
    prefill is the exact bidirectional full-prompt forward and paged
    decode is token-identical to dense.

    ``variant``: "step" is the stepped loop above; "draft" adds
    speculative block drafting (SERVING.md "Speculative drafting") and a
    trailing runtime argument ``draft_mask [B, nb]`` bool — blocks the
    profile-derived signature predicts clear in <= 1 step. Before the
    block loop, ONE forward over the fully-masked response region drafts
    every flagged block's tokens at once and ONE verification forward
    re-scores them: a block is accepted only if every drafted token's
    probability in the revealed context clears the row's step-0
    threshold ``table[b, blk, 0]``. Accepted blocks enter the block loop
    already unmasked (zero denoising steps; their K/V still commit as
    usual), rejected blocks are demoted back to mask and decode through
    the normal stepped loop. ``draft_mask=None`` (or all-False) skips
    both forwards via ``lax.cond`` — the draft program then reproduces
    the stepped path's tokens exactly.

    ``step_fusion`` (default ``dcfg.step_fusion``): "unfused" runs the
    classic epilogue (head matmul, confidence pass, threshold select —
    3 dispatches + 3 HBM passes over [rows, vocab] logits per step);
    "fused" collapses it into the single ``ops.fused_step`` kernel on
    TPU (bit-identical jnp chain elsewhere). With ``quota > 0`` the
    kernel's final-tile select switches to the fixed-step baseline's
    per-row top-``quota`` (in-kernel pairwise ranking, one batch row's
    block per tile), bit-identical to the unfused quota rule.

    ``weight_dtype`` (default ``dcfg.weight_dtype``): "bf16" expects raw
    params (any storage dtype — bit-identity oracle); "int8" keys the
    program for params pre-quantized by
    ``models.quantize.quantize_decode_params`` (the scheduler does this
    once at load) — projections and the lm-head then stream int8 tiles
    through the dequant-in-register kernels.

    Memoized on the NORMALIZED variant key, so spelling-equivalent calls
    (e.g. ``use_cache=True`` vs ``cache_mode="prefix"``) share one jitted
    program — one trace/compile per (cfg, dcfg, variant) process-wide.
    """
    cache_mode, attn_impl, cache_layout, shared_prefix_len, step_fusion, \
        weight_dtype = _norm_slice_key(
            cfg, dcfg, use_cache, cache_mode, attn_impl, cache_layout,
            shared_prefix_len, variant, step_fusion, weight_dtype)
    assert not (variant == "draft" and quota > 0), \
        "drafting presupposes the threshold rule, not the quota baseline"
    return _make_generate_fn(cfg, dcfg, quota, use_kernel, cache_mode,
                             attn_impl, cache_layout, shared_prefix_len,
                             variant, step_fusion, weight_dtype)


@lru_cache(maxsize=None)
def _make_generate_fn(cfg: ModelConfig, dcfg: DecodeConfig, quota: int,
                      use_kernel: bool, cache_mode: str, attn_impl: str,
                      cache_layout: str = "dense",
                      shared_prefix_len: int = 0, variant: str = "step",
                      step_fusion: str = "unfused",
                      weight_dtype: str = "bf16"):
    # weight_dtype is pure program identity: routing is isinstance-based
    # (QuantizedTensor leaves), but int8 params trace to a different HLO,
    # so the memo key must separate them.
    assert cfg.supports_mdlm, f"{cfg.name}: diffusion decoding inapplicable"
    use_cache = cache_mode != "none"
    dual = cache_mode == "dual"
    paged = cache_layout == "paged"
    draft = variant == "draft"
    fused = step_fusion == "fused"
    ps, Sp = dcfg.page_size, shared_prefix_len
    N, bs = dcfg.max_new_tokens, dcfg.block_size
    nb, sc = dcfg.num_blocks, dcfg.steps_cap

    def gen(params, prompt, table, mask_id, live=None, eos_id=None,
            pool_k=None, pool_v=None, page_table=None, draft_mask=None):
        B, P = prompt.shape
        if table.ndim == 2:
            # legacy shared table: broadcast to the per-slot rank
            table = jnp.broadcast_to(table[None], (B,) + table.shape)
        live0 = (jnp.ones((B,), bool) if live is None
                 else jnp.asarray(live).astype(bool))
        track_eos = eos_id is not None
        resp = jnp.full((B, N), mask_id, jnp.int32)
        conf_rec = jnp.zeros((B, nb, sc, bs), jnp.float32)
        val_rec = jnp.zeros((B, nb, sc, bs), bool)
        steps_used = jnp.zeros((nb,), jnp.int32)
        seq_steps0 = jnp.zeros((B, nb), jnp.int32)
        thr0 = jnp.zeros((B, nb), jnp.int32)
        msum0 = jnp.zeros((B, nb), jnp.float32)
        mn0 = jnp.zeros((B, nb), jnp.int32)
        nfe = jnp.zeros((), jnp.int32)

        if use_cache:
            # dual cache reserves a scratch slot region for the in-flight
            # block beyond [prompt | response]
            max_len = P + N + (bs if dual else 0)
            if paged:
                assert pool_k is not None and page_table is not None, \
                    "paged layout: pass pool_k, pool_v, page_table"
                n_log = -(-max_len // ps)
                assert page_table.shape == (B, n_log), \
                    (page_table.shape, (B, n_log))
                assert Sp < P, (Sp, P)
                kv0 = {"kp": pool_k, "vp": pool_v,
                       "pt": page_table.astype(jnp.int32),
                       "pos": jnp.full((max_len,), -1, jnp.int32),
                       "length": jnp.zeros((), jnp.int32)}
                if Sp:
                    # shared pages already hold [0, Sp): mark them valid
                    # and encode only the per-row remainder against them
                    kv0["pos"] = kv0["pos"].at[:Sp].set(
                        jnp.arange(Sp, dtype=jnp.int32))
                    kv0["length"] = jnp.asarray(Sp, jnp.int32)
                    _, cache0 = M.block_step(
                        params, cfg, prompt[:, Sp:],
                        jnp.asarray(Sp, jnp.int32), {"attn": kv0},
                        write=True, attn_impl=attn_impl, page_size=ps)
                else:
                    _, cache0 = M.prefill(params, cfg, prompt,
                                          max_len=max_len, mode="full",
                                          cache={"attn": kv0},
                                          page_size=ps)
            else:
                _, cache0 = M.prefill(params, cfg, prompt, max_len=max_len,
                                      mode="full")
            nfe = nfe + 1
        else:
            cache0 = None

        drafted_ct = jnp.zeros((B,), jnp.int32)
        accepted_ct = jnp.zeros((B,), jnp.int32)
        if draft:
            # -- speculative block drafting (SERVING.md) ---------------
            # ONE forward over the fully-masked response region guesses
            # every flagged block's tokens; ONE verification forward
            # re-scores the guess against the per-slot step-0 thresholds.
            # Accepted blocks enter the block loop already unmasked (the
            # while loop sees no masked positions and runs zero steps);
            # rejected blocks fall back to the stepped rule untouched.
            dm = (jnp.zeros((B, nb), bool) if draft_mask is None
                  else jnp.asarray(draft_mask).astype(bool))
            dm = dm & live0[:, None]        # dead rows never draft
            pos_dm = jnp.repeat(dm, bs, axis=1)             # [B, N]
            tau0 = jnp.repeat(table[:, :, 0], bs, axis=1)   # [B, N]

            def region_logits(region):
                # logits of the whole response region in one forward
                if use_cache:
                    logits, _ = M.block_step(
                        params, cfg, region, jnp.asarray(P, jnp.int32),
                        cache0, attn_impl=attn_impl, page_size=ps,
                        row_live=live0 if paged else None)
                    return logits
                x = jnp.concatenate([prompt, region], axis=1)
                logits, _ = M.forward(params, cfg, x, mode="full")
                return logits[:, P:]

            def do_draft(args):
                resp, nfe = args
                _, toks1 = confidence(region_logits(resp),
                                      use_kernel=use_kernel)
                cand = jnp.where(pos_dm, toks1, resp)
                # re-score THE DRAFTED TOKENS in the revealed context:
                # P(drafted | drafted region) must clear the same step-0
                # tau the stepped rule would have applied (P(argmax) is
                # the wrong quantity here — the drafted token is already
                # chosen; what verification owes is its probability)
                logp2 = jax.nn.log_softmax(
                    region_logits(cand).astype(jnp.float32), axis=-1)
                sel = jnp.take_along_axis(
                    logp2, cand[..., None].astype(jnp.int32),
                    axis=-1)[..., 0]
                ok = jnp.exp(sel) > tau0
                blk_ok = jnp.all(ok.reshape(B, nb, bs), axis=-1) & dm
                keep = jnp.repeat(blk_ok, bs, axis=1)
                return jnp.where(keep, cand, resp), nfe + 2, blk_ok

            def no_draft(args):
                resp, nfe = args
                return resp, nfe, jnp.zeros((B, nb), bool)

            resp, nfe, accept_blk = jax.lax.cond(
                jnp.any(dm), do_draft, no_draft, (resp, nfe))
            drafted_ct = dm.sum(axis=1).astype(jnp.int32)
            accepted_ct = accept_blk.sum(axis=1).astype(jnp.int32)

        def block_body(b, carry):
            resp, cache, nfe, conf_rec, val_rec, steps_used, live, \
                seq_steps, thr_steps, margin_sum, margin_n = carry
            start = b * bs
            block0 = jax.lax.dynamic_slice(resp, (jnp.zeros((), jnp.int32),
                                                  start), (B, bs))
            block_start = P + start
            any_live = jnp.any(live)

            if dual:
                # refresh the whole response region's K/V (suffix cache):
                # one forward over [resp] against the prompt prefix,
                # committed at slot P without advancing the length —
                # skipped outright once no row is live
                def refresh(cache, nfe):
                    _, c = M.block_step(params, cfg, resp,
                                        jnp.asarray(P, jnp.int32), cache,
                                        write=True, advance=False,
                                        write_slot=P, attn_impl=attn_impl,
                                        page_size=ps,
                                        row_live=live if paged else None)
                    return c, nfe + 1

                cache, nfe = jax.lax.cond(
                    any_live, refresh, lambda c, n: (c, n), cache, nfe)

            def model_out(block, full_resp, head=True):
                # ``head=False``: the fused epilogue takes the final-norm'd
                # hidden and unembeds in-kernel (logits never touch HBM)
                if dual:
                    out, _ = M.block_step(
                        params, cfg, block, block_start, cache,
                        write_slot=P + N, exclude_start=start + P,
                        exclude_len=bs, attn_impl=attn_impl, page_size=ps,
                        row_live=live if paged else None, head=head)
                    return out
                if use_cache:
                    out, _ = M.block_step(params, cfg, block,
                                          block_start, cache,
                                          attn_impl=attn_impl,
                                          page_size=ps,
                                          row_live=live if paged
                                          else None, head=head)
                    return out
                x = jnp.concatenate([prompt, full_resp], axis=1)
                out, _ = M.forward(params, cfg, x, mode="full", head=head)
                return jax.lax.dynamic_slice(
                    out, (jnp.zeros((), jnp.int32), block_start,
                          jnp.zeros((), jnp.int32)),
                    (B, bs, out.shape[-1]))

            def cond_fn(st):
                block, step, *_ = st
                # only live rows keep the denoising loop alive
                return (step < sc) & jnp.any((block == mask_id)
                                             & live[:, None])

            def step_fn(st):
                block, step, resp, nfe, conf_rec, val_rec, seq_steps, \
                    thr_steps, margin_sum, margin_n = st
                masked = block == mask_id
                row_active = live & jnp.any(masked, axis=-1)
                tau = table[:, b, jnp.minimum(step, sc - 1)]  # [B]
                if fused:
                    xh = model_out(block, resp, head=False)
                    conf, toks, above = kops.fused_step(
                        xh, M.head_weights(params, cfg),
                        jnp.broadcast_to(tau[:, None], masked.shape),
                        masked, tied=cfg.tie_embeddings, quota=quota)
                    # quota: the in-kernel top-k IS the full rule (the
                    # fixed-step baseline has no argmax fallback)
                    unmask = above if quota else _threshold_fallback(
                        conf, masked, above, live)
                else:
                    logits = model_out(block, resp)
                    conf, toks = confidence(logits, use_kernel=use_kernel)
                    unmask = _unmask_choice(conf, toks, block, mask_id,
                                            tau, quota, live)
                # dead rows flush their masks in whatever step rides along
                unmask = unmask | (masked & ~live[:, None])
                new_block = jnp.where(unmask, toks, block)
                new_resp = jax.lax.dynamic_update_slice(
                    resp, new_block, (jnp.zeros((), jnp.int32), start))
                # calibration signal: EVERY live row (the scheduler picks
                # which rows become task profiles) — a retired/dead row's
                # ride-along flush step must not leak garbage confidences
                # into any task's table
                rec = masked & live[:, None]
                z0 = jnp.zeros((), jnp.int32)
                conf_rec = jax.lax.dynamic_update_slice(
                    conf_rec, jnp.where(rec, conf, 0.0)[:, None, None, :],
                    (z0, b, step, z0))
                val_rec = jax.lax.dynamic_update_slice(
                    val_rec, rec[:, None, None, :], (z0, b, step, z0))
                seq_steps = seq_steps.at[:, b].add(
                    row_active.astype(jnp.int32))
                # drift telemetry (obs.drift): which live masked positions
                # cleared tau outright, and by how much — same verdict the
                # threshold rule used, re-derived from (conf, tau) so the
                # fused and unfused programs accumulate identical values
                above_t = (jnp.where(masked, conf, -jnp.inf)
                           > tau[:, None]) & live[:, None]
                thr_steps = thr_steps.at[:, b].add(
                    jnp.any(above_t, axis=-1).astype(jnp.int32))
                margin_sum = margin_sum.at[:, b].add(
                    jnp.where(above_t, conf - tau[:, None], 0.0)
                    .sum(axis=-1))
                margin_n = margin_n.at[:, b].add(
                    above_t.sum(axis=-1).astype(jnp.int32))
                return (new_block, step + 1, new_resp, nfe + 1, conf_rec,
                        val_rec, seq_steps, thr_steps, margin_sum,
                        margin_n)

            block, steps, resp, nfe, conf_rec, val_rec, seq_steps, \
                thr_steps, margin_sum, margin_n = jax.lax.while_loop(
                    cond_fn, step_fn,
                    (block0, jnp.zeros((), jnp.int32), resp, nfe, conf_rec,
                     val_rec, seq_steps, thr_steps, margin_sum, margin_n))
            steps_used = steps_used.at[b].set(steps)

            if track_eos:
                # rows whose completed prefix contains EOS retire: all
                # later blocks are skipped for them
                done = jnp.arange(N, dtype=jnp.int32) < (b + 1) * bs
                seen = jnp.any((resp == eos_id) & done[None, :], axis=-1)
                live = live & ~seen

            if use_cache and not dual:
                # commit the finished block's K/V (Fast-dLLM prefix cache);
                # pointless — and skipped — once no row remains live
                def commit(cache, nfe):
                    _, c = M.block_step(params, cfg, block, block_start,
                                        cache, write=True,
                                        attn_impl=attn_impl, page_size=ps,
                                        row_live=live if paged else None)
                    return c, nfe + 1

                cache, nfe = jax.lax.cond(
                    jnp.any(live), commit, lambda c, n: (c, n), cache, nfe)
            return (resp, cache, nfe, conf_rec, val_rec, steps_used, live,
                    seq_steps, thr_steps, margin_sum, margin_n)

        carry = (resp, cache0, nfe, conf_rec, val_rec, steps_used, live0,
                 seq_steps0, thr0, msum0, mn0)
        resp, _, nfe, conf_rec, val_rec, steps_used, live_out, seq_steps, \
            thr_steps, margin_sum, margin_n = \
            jax.lax.fori_loop(0, nb, block_body, carry)
        return GenerateResult(resp, nfe, conf_rec, val_rec, steps_used,
                              seq_steps, live_out, drafted_ct, accepted_ct,
                              thr_steps, margin_sum, margin_n)

    return jax.jit(gen)


def result_profile(res: GenerateResult,
                   row: Optional[int] = None) -> CalibrationProfile:
    """Host-side view of one row's recorded confidences (Phase-1 output).

    ``row``: the calibration row's index — its recording and its own live
    step counts become the profile. ``None`` keeps the legacy single-task
    semantics: row 0's recording with the batch-max while-loop counts
    (``steps_per_block``) as ``steps``. Every row is recorded, so a mixed
    batch can yield several task profiles (one ``result_profile`` each).
    """
    r = 0 if row is None else row
    steps = res.steps_per_block if row is None else res.seq_steps[row]
    return CalibrationProfile(
        conf=np.asarray(res.conf)[r],
        valid=np.asarray(res.conf_valid)[r],
        steps=np.asarray(steps),
    )


# ---------------------------------------------------------------------------
# step-sliced decode (SERVING.md "Async admission")
#
# The monolithic program above stays untouched as the bit-identity oracle.
# The sliced family splits it into host-visible pieces: one compiled
# program runs ``slice_len`` block-iterations over an explicit carried
# ``DecodeCarry`` pytree, the host orchestrates the loop — retiring EOS
# rows, reclaiming their pages, and admitting queued requests into freed
# slots BETWEEN slices. Rows therefore carry their own block cursor (a
# freshly admitted row decodes block 0 while its neighbours are at block
# k): every block-offset quantity is per-row inside the slice program,
# and with uniform cursors the math collapses to exactly the monolithic
# program's values (tests/test_sliced_decode.py enforces token, seq_steps
# and nfe identity for slice_len 1 / 2 / nb).
# ---------------------------------------------------------------------------

class DecodeCarry(NamedTuple):
    """Decode state carried between compiled block-slices.

    Shapes are fixed per engine: ``B`` slots, ``P`` prompt slots, ``N``
    response slots (= nb * bs). ``cursor`` is PER-ROW — the next block
    each row denoises — which is what lets one batch mix rows admitted
    at different times. ``cache`` is the KV cache dict (dense or paged —
    the paged pool rides INSIDE the carry so it can be donated into the
    compiled program on TPU), or ``None`` for the cacheless mode.
    """
    resp: Array          # [B, N] int32 response tokens (mask = undecoded)
    prompt: Array        # [B, P] int32 (cacheless forwards + admission)
    table: Array         # [B, nb, sc] float32 per-slot threshold tables
    live: Array          # [B] bool — False: dead slot / EOS-retired
    cursor: Array        # [B] int32 — next block index, nb = done
    conf: Array          # [B, nb, sc, bs] calibration recording
    conf_valid: Array    # [B, nb, sc, bs] bool
    steps_used: Array    # [nb] int32 — batch-max steps per block
    seq_steps: Array     # [B, nb] int32 — per-row live denoising steps
    nfe: Array           # [] int32 — model forwards so far
    blocks_drafted: Array   # [B] int32
    blocks_accepted: Array  # [B] int32
    cache: Any           # KV cache dict ({"attn": ...}) or None
    # drift telemetry (obs.drift) — see GenerateResult; carried so the
    # host drains it at slice boundaries, zeroed per-row at admission
    thr_steps: Array = None     # [B, nb] int32
    margin_sum: Array = None    # [B, nb] float32
    margin_n: Array = None      # [B, nb] int32

    def result(self) -> GenerateResult:
        """The accumulated state in ``GenerateResult`` form, so
        ``result_profile`` (calibration ingest) works unchanged."""
        return GenerateResult(self.resp, self.nfe, self.conf,
                              self.conf_valid, self.steps_used,
                              self.seq_steps, self.live,
                              self.blocks_drafted, self.blocks_accepted,
                              self.thr_steps, self.margin_sum,
                              self.margin_n)


def _norm_slice_key(cfg: ModelConfig, dcfg: DecodeConfig, use_cache: bool,
                    cache_mode: str, attn_impl: str, cache_layout: str,
                    shared_prefix_len: int, variant: str,
                    step_fusion: str = "", weight_dtype: str = ""):
    """THE program-key normalization — ``make_generate_fn`` and the
    sliced family share it, so spelling-equivalent calls can never key
    the oracle and the sliced programs differently."""
    if not cache_mode:
        cache_mode = "prefix" if use_cache else "none"
    assert cache_mode in ("prefix", "dual", "none"), cache_mode
    if not attn_impl:
        attn_impl = dcfg.attn_impl
    assert attn_impl in ("auto", "dense", "flash", "kernel"), attn_impl
    if not cache_layout:
        cache_layout = dcfg.cache_layout or "dense"
    assert cache_layout in ("dense", "paged"), cache_layout
    assert variant in ("step", "draft"), variant
    if not step_fusion:
        step_fusion = dcfg.step_fusion or "unfused"
    assert step_fusion in ("unfused", "fused"), step_fusion
    if not weight_dtype:
        weight_dtype = dcfg.weight_dtype or "bf16"
    assert weight_dtype in WEIGHT_DTYPES, weight_dtype
    if cache_mode == "none":
        cache_layout = "dense"
    if cache_layout != "paged":
        shared_prefix_len = 0
    else:
        assert shared_prefix_len % dcfg.page_size == 0, \
            (shared_prefix_len, dcfg.page_size)
    return (cache_mode, attn_impl, cache_layout, shared_prefix_len,
            step_fusion, weight_dtype)


def _donate_default() -> bool:
    """Donate the carry into the compiled slice program only where the
    backend actually reuses donated buffers (TPU). On CPU jax ignores
    donation with a warning, so the fallback is simply not asking."""
    return jax.default_backend() == "tpu"


def carry_shardings(carry: DecodeCarry, mesh):
    """NamedSharding pytree for ``carry`` on the serving mesh — batch
    dims over ``data``, paged-pool pages over ``data``, kv-heads (or
    head_dim) over ``model``; see ``repro.sharding.rules.carry_specs``
    for the full layout. ``carry`` may be the real pytree or its
    ``eval_shape`` image."""
    from repro.sharding import rules
    return rules.to_named(rules.carry_specs(carry, mesh), mesh)


def shard_decode_carry(carry: DecodeCarry, mesh) -> DecodeCarry:
    """Place a carry on the serving mesh (identity when ``mesh`` is
    ``None``). This is the ONLY mesh hook the decode loop needs: the
    jitted slice/admit programs specialize on their inputs' shardings
    (computation-follows-data), so every program factory in this module
    stays mesh-free and the sharded runtime reuses the exact same
    compiled-program cache keys as the single-device one."""
    if mesh is None:
        return carry
    return jax.device_put(carry, carry_shardings(carry, mesh))


def init_decode_carry(cfg: ModelConfig, dcfg: DecodeConfig, *,
                      batch: int, prompt_len: int, mask_id: int,
                      cache_mode: str = "prefix", cache_layout: str = "",
                      shared_prefix_len: int = 0,
                      pool_k: Optional[Array] = None,
                      pool_v: Optional[Array] = None,
                      page_table: Optional[Array] = None,
                      mesh=None) -> DecodeCarry:
    """A fresh all-dead carry (every slot free). The paged layout takes
    the engine-owned pool and the initial ``[B, n_log]`` page table
    (dead rows all ``-1``); a non-zero ``shared_prefix_len`` expects the
    pool's shared pages to be prefilled already (scheduler ctor) and
    marks their slots valid exactly like the monolithic program. With a
    ``mesh`` the fresh carry is placed per ``carry_shardings`` before
    any program ever sees it, so the first slice compiles against the
    sharded layout directly."""
    cache_mode, _, cache_layout, Sp, _, _ = _norm_slice_key(
        cfg, dcfg, True, cache_mode, "auto", cache_layout,
        shared_prefix_len, "step")
    B, P = batch, prompt_len
    N, bs = dcfg.max_new_tokens, dcfg.block_size
    nb, sc = dcfg.num_blocks, dcfg.steps_cap
    dual = cache_mode == "dual"
    if cache_mode == "none":
        cache = None
    else:
        max_len = P + N + (bs if dual else 0)
        dtype = M.param_dtype(cfg)
        if cache_layout == "paged":
            assert pool_k is not None and page_table is not None, \
                "paged carry needs pool_k, pool_v, page_table"
            pos = jnp.full((max_len,), -1, jnp.int32)
            length = jnp.zeros((), jnp.int32)
            if Sp:
                pos = pos.at[:Sp].set(jnp.arange(Sp, dtype=jnp.int32))
                length = jnp.asarray(Sp, jnp.int32)
            cache = {"attn": {
                "kp": pool_k, "vp": pool_v,
                "pt": jnp.asarray(page_table, jnp.int32),
                "pos": pos, "length": length}}
        else:
            cache = cache_lib.init_cache(cfg, B, max_len, dtype)
    carry = DecodeCarry(
        resp=jnp.full((B, N), mask_id, jnp.int32),
        prompt=jnp.full((B, P), mask_id, jnp.int32),
        table=jnp.zeros((B, nb, sc), jnp.float32),
        live=jnp.zeros((B,), bool),
        cursor=jnp.full((B,), nb, jnp.int32),
        conf=jnp.zeros((B, nb, sc, bs), jnp.float32),
        conf_valid=jnp.zeros((B, nb, sc, bs), bool),
        steps_used=jnp.zeros((nb,), jnp.int32),
        seq_steps=jnp.zeros((B, nb), jnp.int32),
        nfe=jnp.zeros((), jnp.int32),
        blocks_drafted=jnp.zeros((B,), jnp.int32),
        blocks_accepted=jnp.zeros((B,), jnp.int32),
        cache=cache,
        thr_steps=jnp.zeros((B, nb), jnp.int32),
        margin_sum=jnp.zeros((B, nb), jnp.float32),
        margin_n=jnp.zeros((B, nb), jnp.int32))
    return shard_decode_carry(carry, mesh)


@lru_cache(maxsize=None)
def _admit_rows_prog(bucket: int, has_pages: bool, mark: bool):
    """The compiled admission-scatter program for a power-of-two row
    bucket. ``rows`` is padded to ``bucket`` with the out-of-range
    sentinel ``B`` — every ``.at[rows]`` scatter runs ``mode="drop"``,
    so pad entries touch nothing. One program per (bucket, has_pages,
    mark) triple -> an O(log B) family instead of one eager dispatch
    chain per admission count."""

    def prog(carry: DecodeCarry, rows, prompts, tables, lives, mask_id,
             page_rows):
        kw = dict(
            resp=carry.resp.at[rows].set(mask_id, mode="drop"),
            prompt=carry.prompt.at[rows].set(prompts, mode="drop"),
            table=carry.table.at[rows].set(tables, mode="drop"),
            live=carry.live.at[rows].set(lives, mode="drop"),
            cursor=carry.cursor.at[rows].set(0, mode="drop"),
            conf=carry.conf.at[rows].set(0.0, mode="drop"),
            conf_valid=carry.conf_valid.at[rows].set(False, mode="drop"),
            seq_steps=carry.seq_steps.at[rows].set(0, mode="drop"),
            blocks_drafted=carry.blocks_drafted.at[rows].set(
                0, mode="drop"),
            blocks_accepted=carry.blocks_accepted.at[rows].set(
                0, mode="drop"),
            thr_steps=carry.thr_steps.at[rows].set(0, mode="drop"),
            margin_sum=carry.margin_sum.at[rows].set(0.0, mode="drop"),
            margin_n=carry.margin_n.at[rows].set(0, mode="drop"))
        if has_pages or mark:
            kv = dict(carry.cache["attn"])
            if has_pages:
                kv["pt"] = kv["pt"].at[rows].set(page_rows, mode="drop")
            if mark:
                # radix-admission engines mark the prompt range valid
                # HERE so an all-full-hit boundary can skip the prefill
                # forward entirely; a non-skipped admit forward re-marks
                # the same values (idempotent)
                P = carry.prompt.shape[1]
                kv["pos"] = kv["pos"].at[:P].set(
                    jnp.arange(P, dtype=jnp.int32))
                kv["length"] = jnp.maximum(kv["length"],
                                           jnp.asarray(P, jnp.int32))
            kw["cache"] = dict(carry.cache, attn=kv)
        return carry._replace(**kw)

    return jax.jit(prog)


def bucket_rows(rows: Sequence[int], batch: int) -> np.ndarray:
    """``rows`` padded to the power-of-two bucket ``1 << (n-1).bit_length()``
    with the out-of-range index ``batch``: the row vector both admission
    programs (``admit_carry_rows``'s scatter, ``make_admit_fn``'s
    prefill) take, so each compiles once per bucket."""
    n = len(rows)
    r = np.full((1 << (n - 1).bit_length(),), batch, np.int32)
    r[:n] = list(rows)
    return r


def admit_carry_rows(carry: DecodeCarry, rows: Sequence[int],
                     prompts: np.ndarray, tables: np.ndarray,
                     mask_id: int, *,
                     page_rows: Optional[np.ndarray] = None,
                     live: Optional[Sequence[bool]] = None,
                     mark_prompt_pos: bool = False) -> DecodeCarry:
    """Host-side slot (re)initialisation at admission: place each row's
    prompt / table (/ page-table row), reset its response to masks, its
    cursor to block 0, and zero its accumulators. ``live`` marks which
    of the rows carry a real request (dead pad slots admit ``False``).
    The KV prefill itself is the compiled ``make_admit_fn`` program.

    The scatters are jitted per power-of-two admission-count bucket
    (pad rows carry an out-of-range index and drop): the program family
    is O(log B), and a 1-row mid-generation admission stops re-tracing
    the whole update chain eagerly (~700 ms per slice boundary on CPU
    with the old per-count masked selects).

    ``mark_prompt_pos`` (radix prefix cache): also mark the shared
    ``pos`` row's prompt range valid and bump ``length`` to the prompt
    length, so a boundary whose every admitted row is a FULL radix hit
    needs no prefill forward at all."""
    if not len(rows):
        return carry
    r = bucket_rows(rows, carry.live.shape[0])
    n, bucket = len(rows), len(r)
    P = carry.prompt.shape[1]
    nb, sc = carry.table.shape[1], carry.table.shape[2]
    pr = np.zeros((bucket, P), np.int32)
    pr[:n] = np.asarray(prompts, np.int32)
    tb = np.zeros((bucket, nb, sc), np.float32)
    tb[:n] = np.asarray(tables, np.float32)
    lv = np.zeros((bucket,), bool)
    lv[:n] = True if live is None else list(live)
    has_pages = page_rows is not None
    pg = None
    if has_pages:
        n_log = carry.cache["attn"]["pt"].shape[1]
        pg = np.full((bucket, n_log), -1, np.int32)
        pg[:n] = np.asarray(page_rows, np.int32)
    if mark_prompt_pos:
        assert carry.cache is not None and "pt" in carry.cache["attn"], \
            "mark_prompt_pos is a paged-carry (radix admission) feature"
    prog = _admit_rows_prog(bucket, has_pages, bool(mark_prompt_pos))
    return prog(carry, jnp.asarray(r), jnp.asarray(pr), jnp.asarray(tb),
                jnp.asarray(lv), jnp.asarray(mask_id, jnp.int32),
                jnp.asarray(pg) if has_pages else None)


def retire_carry_rows(carry: DecodeCarry, rows: Sequence[int],
                      num_blocks: int) -> DecodeCarry:
    """Host-side slot release: mark rows dead and (paged) unmap their
    page-table entries so pages freed back to the allocator can be
    handed to the next admission without the old row still reading or
    writing them."""
    if not len(rows):
        return carry
    sel = np.zeros((carry.live.shape[0],), bool)
    sel[list(rows)] = True
    m = jnp.asarray(sel)
    kw = dict(live=jnp.where(m, False, carry.live),
              cursor=jnp.where(m, num_blocks, carry.cursor))
    if carry.cache is not None and "pt" in carry.cache["attn"]:
        kv = dict(carry.cache["attn"])
        kv["pt"] = jnp.where(m[:, None], -1, kv["pt"])
        kw["cache"] = dict(carry.cache, attn=kv)
    return carry._replace(**kw)


def make_admit_fn(cfg: ModelConfig, dcfg: DecodeConfig, *,
                  cache_mode: str = "prefix", attn_impl: str = "",
                  cache_layout: str = "", shared_prefix_len: int = 0,
                  donate: Optional[bool] = None):
    """Build (or fetch) the compiled admission program.

    fn(params, carry, rows [k] i32, prefix_len [k] i32 = None) -> carry

    ``rows`` is the ``bucket_rows`` vector: the slots admitted at this
    boundary, padded to a power-of-two ``k`` with the out-of-range index
    ``B``. ONE forward prefills just those ``k`` rows of
    ``carry.prompt`` (gathered; a pad entry prefills a dummy row that
    maps no page) and merges their K/V into the carried cache: paged
    writes go through the gathered page-table rows (pad rows are all
    ``-1`` and drop), dense writes scatter back per row. Every other row
    keeps its buffers bit-exactly. The program compiles once per bucket
    (O(log B) programs, as ``admit_carry_rows``); with every row
    admitted, ``k = B`` and the forward is the monolithic program's one
    prefill. Costs one forward (+1 nfe) per call — the host batches all
    of a slice boundary's admissions into one call. The cacheless mode
    has no admission program (nothing to prefill).

    ``prefix_len`` (paged only, mutually exclusive with the static
    ``shared_prefix_len``): per-entry radix-cache hit lengths in tokens,
    aligned with ``rows`` (page-aligned, 0 = full miss). Hit positions
    read their K/V from the row's already-mapped shared pages instead of
    the fresh projections, and the write-back page table unmaps the hit
    pages so the shared runs stay immutable. Passing a zero vector is
    bit-exact with omitting the argument (the jit specializes on its
    presence).
    """
    cache_mode, attn_impl, cache_layout, Sp, _, _ = _norm_slice_key(
        cfg, dcfg, True, cache_mode, attn_impl, cache_layout,
        shared_prefix_len, "step")
    assert cache_mode != "none", "cacheless decode has nothing to admit"
    return _make_admit_fn(cfg, dcfg, cache_mode, attn_impl, cache_layout,
                          Sp, _donate_default() if donate is None
                          else bool(donate))


@lru_cache(maxsize=None)
def _make_admit_fn(cfg: ModelConfig, dcfg: DecodeConfig, cache_mode: str,
                   attn_impl: str, cache_layout: str,
                   shared_prefix_len: int, donate: bool):
    assert cfg.supports_mdlm, f"{cfg.name}: diffusion decoding inapplicable"
    paged = cache_layout == "paged"
    ps, Sp = dcfg.page_size, shared_prefix_len
    N, bs = dcfg.max_new_tokens, dcfg.block_size
    dual = cache_mode == "dual"

    def admit(params, carry: DecodeCarry, rows, prefix_len=None):
        P = carry.prompt.shape[1]
        max_len = P + N + (bs if dual else 0)
        kv = carry.cache["attn"]
        rows = jnp.asarray(rows, jnp.int32)
        k = rows.shape[0]
        prompt = jnp.take(carry.prompt, rows, axis=0, mode="fill",
                          fill_value=0)
        if paged:
            pt = jnp.take(kv["pt"], rows, axis=0, mode="fill",
                          fill_value=-1)
            if Sp:
                assert prefix_len is None, \
                    "per-row prefix_len replaces the static shared prefix"
                # the shared pages already hold [0, Sp): encode only the
                # per-row remainder against them (same call shape as the
                # monolithic Sp prefill; write slot is explicit because
                # the carried length tracks the batch-max extent, not Sp)
                _, c1 = M.block_step(
                    params, cfg, prompt[:, Sp:],
                    jnp.asarray(Sp, jnp.int32),
                    {"attn": dict(kv, pt=pt)}, write=True,
                    advance=False, write_slot=jnp.asarray(Sp, jnp.int32),
                    attn_impl=attn_impl, page_size=ps,
                    row_limit=jnp.full((k,), Sp, jnp.int32))
                kv1 = c1["attn"]
            elif prefix_len is not None:
                # radix-hit admission: each row's first prefix_len[r]
                # positions are already resident in shared tree pages —
                # the forward substitutes their cached K/V per layer and
                # writes back ONLY the novel suffix (matched pages are
                # unmapped in the write table, so scatters to them drop
                # and the shared pages stay immutable). Rows with
                # prefix_len == 0 take the identical [P, P] attention and
                # all-fresh selects, so a full miss is bit-exact with the
                # plain-prefill branch below.
                pfx = prefix_len.astype(jnp.int32)
                n_log = kv["pt"].shape[1]
                drop = jnp.arange(n_log, dtype=jnp.int32)[None, :] \
                    < (pfx[:, None] // ps)
                pt_write = jnp.where(drop, -1, pt)
                _, c1 = M.prefill(params, cfg, prompt,
                                  max_len=max_len, mode="full",
                                  cache={"attn": dict(kv, pt=pt)},
                                  page_size=ps, prefix_len=pfx,
                                  write_page_table=pt_write)
                kv1 = c1["attn"]
            else:
                _, c1 = M.prefill(params, cfg, prompt,
                                  max_len=max_len, mode="full",
                                  cache={"attn": dict(kv, pt=pt)},
                                  page_size=ps)
                kv1 = c1["attn"]
            new_kv = dict(kv, kp=kv1["kp"], vp=kv1["vp"],
                          pos=jnp.maximum(kv["pos"], kv1["pos"]),
                          length=jnp.maximum(kv["length"],
                                             jnp.asarray(P, jnp.int32)))
        else:
            assert prefix_len is None, \
                "radix prefix hits require the paged layout"
            _, fresh = M.prefill(params, cfg, prompt,
                                 max_len=max_len, mode="full")
            fkv = fresh["attn"]
            new_kv = dict(kv, pos=jnp.maximum(kv["pos"], fkv["pos"]),
                          length=jnp.maximum(kv["length"], fkv["length"]))
            for name in ("k", "v"):
                new_kv[name] = kv[name].at[:, rows, :P].set(
                    fkv[name][:, :, :P].astype(kv[name].dtype),
                    mode="drop")
        return carry._replace(cache=dict(carry.cache, attn=new_kv),
                              nfe=carry.nfe + 1)

    return jax.jit(admit, donate_argnums=(1,) if donate else ())


def make_slice_fn(cfg: ModelConfig, dcfg: DecodeConfig, *,
                  slice_len: int = 1, quota: int = 0,
                  use_kernel: bool = False, cache_mode: str = "prefix",
                  attn_impl: str = "", cache_layout: str = "",
                  shared_prefix_len: int = 0, variant: str = "step",
                  step_fusion: str = "", weight_dtype: str = "",
                  donate: Optional[bool] = None):
    """Build (or fetch) the compiled block-slice program.

    fn(params, carry, mask_id [], eos_id [] = None,
       draft_mask [B, nb] = None) -> carry

    Runs ``slice_len`` block-iterations of the decode loop and returns
    the updated :class:`DecodeCarry`. Each iteration denoises, for every
    row, the row's OWN ``cursor`` block — per-row positions, write
    slots, exclusion ranges and valid extents — then advances the
    cursors, so one batch freely mixes rows admitted at different times.
    With uniform cursors (same admitted set) the math reproduces the
    monolithic ``make_generate_fn`` program bit-exactly: driving slices
    until every cursor reaches ``nb`` yields identical tokens,
    ``seq_steps``, ``conf`` recordings and ``nfe``.

    ``variant="draft"``: the slice ADDITIONALLY runs the draft+verify
    forwards over the blocks flagged in ``draft_mask`` before its block
    iterations (skipped via ``lax.cond`` when the mask is empty). The
    host passes a row's plan exactly once — on the first slice after its
    admission (``Drafter.plan_remaining``) — so re-planned drafts for
    mid-generation admissions score against the already-committed
    context of THEIR OWN row, and rows mid-decode are unaffected.

    ``donate`` (default: auto) donates the carry into the program so the
    paged KV pool is updated in place instead of being copied per slice;
    auto enables it on TPU only — CPU ignores donation, and the fallback
    is to keep the functional copy (satellite: pool donation).

    ``step_fusion`` mirrors ``make_generate_fn`` — "fused" collapses each
    step's epilogue (head matmul + confidence + threshold) into the one
    ``ops.fused_step`` kernel; ``quota > 0`` runs the in-kernel top-k
    select (bit-identical to the unfused quota baseline).
    ``weight_dtype`` mirrors ``make_generate_fn`` too — "int8" keys the
    program for pre-quantized params.

    Memoized like ``make_generate_fn``: one compiled program per
    (cfg, dcfg, variant, slice_len) process-wide.
    """
    cache_mode, attn_impl, cache_layout, Sp, step_fusion, weight_dtype = \
        _norm_slice_key(cfg, dcfg, True, cache_mode, attn_impl,
                        cache_layout, shared_prefix_len, variant,
                        step_fusion, weight_dtype)
    assert slice_len >= 1, slice_len
    assert not (variant == "draft" and quota > 0), \
        "drafting presupposes the threshold rule, not the quota baseline"
    return _make_slice_fn(cfg, dcfg, int(slice_len), quota, use_kernel,
                          cache_mode, attn_impl, cache_layout, Sp, variant,
                          step_fusion, weight_dtype,
                          _donate_default() if donate is None
                          else bool(donate))


@lru_cache(maxsize=None)
def _make_slice_fn(cfg: ModelConfig, dcfg: DecodeConfig, slice_len: int,
                   quota: int, use_kernel: bool, cache_mode: str,
                   attn_impl: str, cache_layout: str,
                   shared_prefix_len: int, variant: str, step_fusion: str,
                   weight_dtype: str, donate: bool):
    assert cfg.supports_mdlm, f"{cfg.name}: diffusion decoding inapplicable"
    use_cache = cache_mode != "none"
    dual = cache_mode == "dual"
    paged = cache_layout == "paged"
    draft = variant == "draft"
    fused = step_fusion == "fused"
    ps = dcfg.page_size
    N, bs = dcfg.max_new_tokens, dcfg.block_size
    nb, sc = dcfg.num_blocks, dcfg.steps_cap

    def slice_fn(params, carry: DecodeCarry, mask_id, eos_id=None,
                 draft_mask=None):
        resp, prompt, table = carry.resp, carry.prompt, carry.table
        B, P = prompt.shape

        def row_extent(live, cursor):
            """Per-row committed-cache extent [B]: what each row may
            attend beyond its own fresh block. Mirrors the monolithic
            row_live wiring — paged masks dead/retired rows to 0 (their
            still-mapped pages stop being touched), dense keeps the
            extent (the oracle passes no mask there)."""
            ext = jnp.minimum(cursor, nb) * bs
            if dual:
                # the refreshed suffix is valid for every working row
                ext = jnp.broadcast_to(jnp.asarray(N, jnp.int32),
                                       ext.shape)
            if paged:
                return jnp.where(live, P + ext, 0)
            return P + ext

        track_eos = eos_id is not None
        cache = carry.cache
        nfe = carry.nfe
        live0, cursor0 = carry.live, carry.cursor
        drafted_ct, accepted_ct = carry.blocks_drafted, carry.blocks_accepted
        rows = jnp.arange(B, dtype=jnp.int32)
        max_len = P + N + (bs if dual else 0)

        if draft:
            dm = (jnp.zeros((B, nb), bool) if draft_mask is None
                  else jnp.asarray(draft_mask).astype(bool))
            dm = dm & live0[:, None]
            # re-planned drafts only cover a row's REMAINING blocks
            dm = dm & (jnp.arange(nb, dtype=jnp.int32)[None]
                       >= cursor0[:, None])
            pos_dm = jnp.repeat(dm, bs, axis=1)
            tau0 = jnp.repeat(table[:, :, 0], bs, axis=1)
            draft_lim = row_extent(live0, cursor0)

            def region_logits(region):
                if use_cache:
                    # write_slot pins the region's pre-write at P — the
                    # carried length tracks the batch-max extent, which
                    # exceeds P once any row is past block 0
                    logits, _ = M.block_step(
                        params, cfg, region, jnp.asarray(P, jnp.int32),
                        cache, write_slot=jnp.asarray(P, jnp.int32),
                        attn_impl=attn_impl, page_size=ps,
                        row_limit=draft_lim)
                    return logits
                x = jnp.concatenate([prompt, region], axis=1)
                logits, _ = M.forward(params, cfg, x, mode="full")
                return logits[:, P:]

            def do_draft(args):
                resp, nfe = args
                _, toks1 = confidence(region_logits(resp),
                                      use_kernel=use_kernel)
                cand = jnp.where(pos_dm, toks1, resp)
                logp2 = jax.nn.log_softmax(
                    region_logits(cand).astype(jnp.float32), axis=-1)
                sel = jnp.take_along_axis(
                    logp2, cand[..., None].astype(jnp.int32),
                    axis=-1)[..., 0]
                ok = jnp.exp(sel) > tau0
                blk_ok = jnp.all(ok.reshape(B, nb, bs), axis=-1) & dm
                keep = jnp.repeat(blk_ok, bs, axis=1)
                return jnp.where(keep, cand, resp), nfe + 2, blk_ok

            def no_draft(args):
                resp, nfe = args
                return resp, nfe, jnp.zeros((B, nb), bool)

            resp, nfe, accept_blk = jax.lax.cond(
                jnp.any(dm), do_draft, no_draft, (resp, nfe))
            drafted_ct = drafted_ct + dm.sum(axis=1).astype(jnp.int32)
            accepted_ct = accepted_ct \
                + accept_blk.sum(axis=1).astype(jnp.int32)

        def iter_body(_, st):
            resp, cache, nfe, conf_rec, val_rec, steps_used, live, \
                seq_steps, cursor, thr_steps, margin_sum, margin_n = st
            cur_c = jnp.minimum(cursor, nb - 1)       # [B] gather-safe
            todo = cursor < nb                        # [B]
            start = cur_c * bs                        # [B]
            col = start[:, None] + jnp.arange(bs, dtype=jnp.int32)
            block0 = jnp.take_along_axis(resp, col, axis=1)
            block_start = P + start                   # [B]
            rec_blk = jnp.where(todo, cur_c, nb)      # drop finished rows
            any_work = jnp.any(live & todo)

            if dual:
                def refresh(cache, nfe):
                    _, c = M.block_step(params, cfg, resp,
                                        jnp.asarray(P, jnp.int32), cache,
                                        write=True, advance=False,
                                        write_slot=jnp.asarray(P,
                                                               jnp.int32),
                                        attn_impl=attn_impl, page_size=ps,
                                        row_live=live if paged else None)
                    return c, nfe + 1

                cache, nfe = jax.lax.cond(
                    any_work, refresh, lambda c, n: (c, n), cache, nfe)

            def model_out(block, full_resp, live_now, head=True):
                # ``head=False``: the fused epilogue takes the final-norm'd
                # hidden and unembeds in-kernel (logits never touch HBM)
                if dual:
                    out, _ = M.block_step(
                        params, cfg, block, block_start, cache,
                        write_slot=jnp.asarray(P + N, jnp.int32),
                        exclude_start=block_start, exclude_len=bs,
                        attn_impl=attn_impl, page_size=ps,
                        row_live=live_now if paged else None, head=head)
                    return out
                if use_cache:
                    # write_slot = each row's OWN block slots: the
                    # monolithic oracle's slot (= the shared length)
                    # only equals the block position in lockstep
                    out, _ = M.block_step(
                        params, cfg, block, block_start, cache,
                        write_slot=block_start, attn_impl=attn_impl,
                        page_size=ps,
                        row_limit=row_extent(live_now, cursor), head=head)
                    return out
                x = jnp.concatenate([prompt, full_resp], axis=1)
                out, _ = M.forward(params, cfg, x, mode="full", head=head)
                pick = (P + col)[..., None]           # [B, bs, 1]
                return jnp.take_along_axis(
                    out, jnp.broadcast_to(
                        pick, (B, bs, out.shape[-1])), axis=1)

            def cond_fn(st):
                block, step, *_ = st
                return (step < sc) & jnp.any((block == mask_id)
                                             & live[:, None])

            def step_fn(st):
                block, step, resp, nfe, conf_rec, val_rec, seq_steps, \
                    thr_steps, margin_sum, margin_n = st
                masked = block == mask_id
                row_active = live & jnp.any(masked, axis=-1)
                tau = table[rows, cur_c, jnp.minimum(step, sc - 1)]  # [B]
                if fused:
                    xh = model_out(block, resp, live, head=False)
                    conf, toks, above = kops.fused_step(
                        xh, M.head_weights(params, cfg),
                        jnp.broadcast_to(tau[:, None], masked.shape),
                        masked, tied=cfg.tie_embeddings, quota=quota)
                    # quota: the in-kernel top-k IS the full rule (the
                    # fixed-step baseline has no argmax fallback)
                    unmask = above if quota else _threshold_fallback(
                        conf, masked, above, live)
                else:
                    logits = model_out(block, resp, live)
                    conf, toks = confidence(logits, use_kernel=use_kernel)
                    unmask = _unmask_choice(conf, toks, block, mask_id,
                                            tau, quota, live)
                unmask = unmask | (masked & ~live[:, None])
                new_block = jnp.where(unmask, toks, block)
                new_resp = resp.at[rows[:, None], col].set(new_block)
                rec = masked & live[:, None]
                conf_rec = conf_rec.at[rows, rec_blk, step].set(
                    jnp.where(rec, conf, 0.0), mode="drop")
                val_rec = val_rec.at[rows, rec_blk, step].set(
                    rec, mode="drop")
                seq_steps = seq_steps.at[rows, rec_blk].add(
                    row_active.astype(jnp.int32), mode="drop")
                # drift telemetry — the sliced twin of the monolithic
                # accumulators (per-row rec_blk scatter, finished rows
                # drop), so slice-driven decode drains identical values
                above_t = (jnp.where(masked, conf, -jnp.inf)
                           > tau[:, None]) & live[:, None]
                thr_steps = thr_steps.at[rows, rec_blk].add(
                    jnp.any(above_t, axis=-1).astype(jnp.int32),
                    mode="drop")
                margin_sum = margin_sum.at[rows, rec_blk].add(
                    jnp.where(above_t, conf - tau[:, None], 0.0)
                    .sum(axis=-1), mode="drop")
                margin_n = margin_n.at[rows, rec_blk].add(
                    above_t.sum(axis=-1).astype(jnp.int32), mode="drop")
                return (new_block, step + 1, new_resp, nfe + 1, conf_rec,
                        val_rec, seq_steps, thr_steps, margin_sum,
                        margin_n)

            block, steps, resp, nfe, conf_rec, val_rec, seq_steps, \
                thr_steps, margin_sum, margin_n = jax.lax.while_loop(
                    cond_fn, step_fn,
                    (block0, jnp.zeros((), jnp.int32), resp, nfe, conf_rec,
                     val_rec, seq_steps, thr_steps, margin_sum, margin_n))
            steps_used = steps_used.at[rec_blk].max(steps, mode="drop")

            if track_eos:
                done = jnp.arange(N, dtype=jnp.int32)[None] \
                    < ((cur_c + 1) * bs)[:, None]
                seen = jnp.any((resp == eos_id) & done, axis=-1)
                live = live & ~seen

            if use_cache and not dual:
                def commit(cache, nfe):
                    wslot = jnp.where(todo, block_start, max_len)
                    _, c = M.block_step(
                        params, cfg, block, block_start, cache,
                        write=True, advance=False, write_slot=wslot,
                        attn_impl=attn_impl, page_size=ps,
                        row_limit=row_extent(live, cursor))
                    kv = c["attn"]
                    ext = P + bs * jnp.max(jnp.where(todo, cur_c + 1, 0))
                    kv = dict(kv, length=jnp.maximum(kv["length"], ext))
                    return dict(c, attn=kv), nfe + 1

                cache, nfe = jax.lax.cond(
                    jnp.any(live & todo), commit, lambda c, n: (c, n),
                    cache, nfe)
            cursor = jnp.minimum(cursor + 1, nb)
            return (resp, cache, nfe, conf_rec, val_rec, steps_used, live,
                    seq_steps, cursor, thr_steps, margin_sum, margin_n)

        st = (resp, cache, nfe, carry.conf, carry.conf_valid,
              carry.steps_used, live0, carry.seq_steps, cursor0,
              carry.thr_steps, carry.margin_sum, carry.margin_n)
        resp, cache, nfe, conf_rec, val_rec, steps_used, live, seq_steps, \
            cursor, thr_steps, margin_sum, margin_n = \
            jax.lax.fori_loop(0, slice_len, iter_body, st)
        return carry._replace(
            resp=resp, cache=cache, nfe=nfe, conf=conf_rec,
            conf_valid=val_rec, steps_used=steps_used, live=live,
            seq_steps=seq_steps, cursor=cursor,
            blocks_drafted=drafted_ct, blocks_accepted=accepted_ct,
            thr_steps=thr_steps, margin_sum=margin_sum, margin_n=margin_n)

    return jax.jit(slice_fn, donate_argnums=(1,) if donate else ())


# ---------------------------------------------------------------------------
# AR decoding (SSM / hybrid archs — OSDT inapplicable, DESIGN.md §4)
# ---------------------------------------------------------------------------

def make_ar_generate_fn(cfg: ModelConfig, *, max_new_tokens: int,
                        window: int = 0, attn_impl: str = "auto"):
    """Greedy AR generation: fn(params, prompt [B, P]) -> tokens [B, N]."""
    assert attn_impl in ("auto", "dense", "flash", "kernel"), attn_impl

    def gen(params, prompt):
        B, P = prompt.shape
        max_len = P + max_new_tokens
        logits, cache = M.prefill(params, cfg, prompt, max_len=max_len,
                                  window=window)
        first = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)

        def step(carry, _):
            tok, cache = carry
            logits, cache = M.decode_step(params, cfg, tok, cache,
                                          window=window,
                                          attn_impl=attn_impl)
            nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            return (nxt, cache), tok

        (_, _), toks = jax.lax.scan(step, (first, cache), None,
                                    length=max_new_tokens)
        return jnp.moveaxis(toks[:, :, 0], 0, 1)

    return jax.jit(gen)
