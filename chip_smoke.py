#!/usr/bin/env python3
"""Smoke run of the diffusion serving runtime on TPU at llada-8b widths.

Drives the main serving path once, through the library entry points a
user calls: ``DiffusionEngine`` -> ``Scheduler`` with the step-sliced
runtime (``slice_len=1``), the paged KV cache, the Pallas block-attention
kernel (``attn_impl="kernel"``), the fused step epilogue
(``step_fusion="fused"``) and OSDT thresholds. The model is
``configs/llada_8b.py`` at its published widths (d_model 4096, 32 x 128
MHA heads, d_ff 12288, vocab 126464, bf16) with only the depth cut, and
seeded random weights drawn on the device.

Checks, each fatal:
  * JAX's backend is TPU; nothing here runs on the CPU or in interpret
    mode, and the repo's ``src/`` must sit next to this file;
  * the compiled slice program holds Pallas kernels (``tpu_custom_call``)
    for paged block attention and the fused step;
  * one denoising step's confidences on the live decode carry agree
    between that Pallas path and the XLA path (``attn_impl="auto"``,
    unfused epilogue) within ``LOG_CONF_TOL``;
  * every emitted token id is inside the vocabulary and every recorded
    confidence is finite; every request is answered and the task's OSDT
    table is calibrated.

Random weights give near-uniform confidences, so almost every block runs
to ``steps_cap`` steps: the tokens/s printed is a smoke number, not a
benchmark. The last line of standard output is the JSON result.

  python3 chip_smoke.py                # one chip
  python3 chip_smoke.py --four-chips   # mesh data=4 and data=2 x model=2,
                                       # each against one device
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

# One denoising step's confidence, Pallas path vs XLA path, compared as
# |log conf_pallas - log conf_xla|. log conf = max logit - logsumexp, and
# with random weights the logits have magnitude ~1-5: one bf16 rounding
# of a logit is up to 2^-8 * 5 = 0.02, and the two paths round the
# activations differently in every one of the model's layers. 0.1 allows
# a few such roundings; a wrong mask, head or layout moves it by O(1).
LOG_CONF_TOL = 0.1

SEQ = dict(prompt_len=256, max_new_tokens=128, block_size=32, batch=4,
           requests=8)
TASK = "smoke"
LAYERS_ONE_CHIP = 16
LAYERS_FOUR_CHIPS = 8


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


class CompileClock:
    """Seconds XLA spent compiling, and persistent-cache hits/misses,
    from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.misses = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def __str__(self):
        return (f"{self.seconds:.1f} s compiling, persistent cache "
                f"{self.hits} hits / {self.misses} misses")


def describe(cfg) -> None:
    """Print the widths and the depth cut with its reason."""
    from repro.config.registry import get_config
    full = get_config(cfg.name)
    layer_bytes = 2 * (cfg.param_count() - dataclasses.replace(
        cfg, num_layers=0).param_count()) // cfg.num_layers
    print(f"model: {cfg.name} d_model={cfg.d_model} heads="
          f"{cfg.num_heads}x{cfg.resolved_head_dim} kv_heads="
          f"{cfg.num_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"dtype={cfg.dtype}")
    print(f"depth: {cfg.num_layers} of {full.num_layers} layers; bf16 "
          f"weights {cfg.param_count() * 2 / 1e9:.2f} GB vs "
          f"{full.param_count() * 2 / 1e9:.2f} GB at full depth "
          f"({layer_bytes / 1e9:.2f} GB per layer), which does not fit "
          f"a 16 GB chip")


def decode_configs(attn_impl="kernel", step_fusion="fused", **ecfg_kw):
    from repro.config.base import DecodeConfig, EngineConfig
    dcfg = DecodeConfig(max_new_tokens=SEQ["max_new_tokens"],
                        block_size=SEQ["block_size"], policy="osdt",
                        cache_layout="paged", page_size=16,
                        attn_impl=attn_impl, step_fusion=step_fusion)
    ecfg = EngineConfig(batch_size=SEQ["batch"],
                        prompt_len=SEQ["prompt_len"], slice_len=1,
                        **ecfg_kw)
    return dcfg, ecfg


def requests(seed: int):
    """Seeded printable prompts that fill most of the prompt window."""
    import numpy as np

    from repro.serving.engine import Request
    rng = np.random.default_rng(seed)
    n = SEQ["prompt_len"] - 8
    return [Request(i, TASK, bytes(rng.integers(32, 127, n,
                                                dtype=np.uint8)).decode())
            for i in range(SEQ["requests"])]


def step_confidences(params, carry, *, cfg, dcfg, pallas: bool):
    """Confidences and argmax ids of the next denoising step of every
    row's current block, read from a live carry without changing it —
    the slice program's own block-step call, on either path."""
    import jax.numpy as jnp

    from repro.core.confidence import confidence_ref
    from repro.data import tokenizer as tok
    from repro.kernels import ops as kops
    from repro.models import model as M
    P = carry.prompt.shape[1]
    bs, nb = dcfg.block_size, dcfg.num_blocks
    cur = jnp.minimum(carry.cursor, nb - 1)
    col = (cur * bs)[:, None] + jnp.arange(bs, dtype=jnp.int32)
    block = jnp.take_along_axis(carry.resp, col, axis=1)
    start = P + cur * bs
    limit = jnp.where(carry.live,
                      P + jnp.minimum(carry.cursor, nb) * bs, 0)
    kw = dict(write_slot=start, page_size=dcfg.page_size, row_limit=limit)
    if pallas:
        h, _ = M.block_step(params, cfg, block, start, carry.cache,
                            attn_impl="kernel", head=False, **kw)
        conf, ids, _ = kops.fused_step(
            h, M.head_weights(params, cfg),
            jnp.zeros(block.shape, jnp.float32), block == tok.MASK_ID,
            tied=cfg.tie_embeddings)
    else:
        logits, _ = M.block_step(params, cfg, block, start, carry.cache,
                                 attn_impl="auto", **kw)
        conf, ids = confidence_ref(logits)
    return conf, ids


def check_slice_hlo(engine, cfg, dcfg) -> None:
    """The compiled slice program must hold the Pallas kernels it asked
    for: a silent fall-back to XLA fails here."""
    import jax.numpy as jnp

    from repro.core.decoder import make_slice_fn
    from repro.data import tokenizer as tok
    sched = engine.scheduler
    fn = make_slice_fn(cfg, dcfg, slice_len=sched.slice_len,
                       cache_mode=engine.ecfg.resolved_cache_mode())
    hlo = fn.lower(sched.params, sched.carry,
                   jnp.asarray(tok.MASK_ID, jnp.int32), tok.EOS_ID,
                   None).compile().as_text()
    calls = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    for name in ("paged_block_attention", "fused_step"):
        if not any(name in ln for ln in calls):
            fail(f"compiled slice program has no tpu_custom_call for "
                 f"{name} ({len(calls)} Pallas calls in all)")
    print(f"hlo: slice program holds {len(calls)} tpu_custom_call ops, "
          f"incl. paged_block_attention and fused_step")


def check_step_agreement(engine, cfg, dcfg) -> None:
    """Pallas path vs XLA path on the same carry, one denoising step."""
    import functools

    import jax
    import numpy as np
    sched = engine.scheduler
    carry = sched.carry
    got = {}
    for pallas in (True, False):
        fn = jax.jit(functools.partial(step_confidences, cfg=cfg,
                                       dcfg=dcfg, pallas=pallas))
        conf, ids = fn(sched.params, carry)
        got[pallas] = (np.asarray(conf), np.asarray(ids))
    rows = np.asarray(carry.live) & (np.asarray(carry.cursor)
                                     < dcfg.num_blocks)
    if not rows.any():
        fail("no live row to compare the two paths on")
    ck, ik = (a[rows] for a in got[True])
    cx, ix = (a[rows] for a in got[False])
    if not (np.isfinite(ck).all() and np.isfinite(cx).all()
            and (ck > 0).all() and (cx > 0).all()):
        fail("non-finite or non-positive step confidences")
    dlog = np.abs(np.log(ck) - np.log(cx))
    print(f"pallas vs xla: {ck.size} positions of {int(rows.sum())} rows, "
          f"max |dlog conf| {dlog.max():.4f} (tol {LOG_CONF_TOL}), mean "
          f"{dlog.mean():.4f}; conf mean {cx.mean():.3e}; argmax ids "
          f"agree at {np.mean(ik == ix):.1%} of positions")
    if dlog.max() > LOG_CONF_TOL:
        fail(f"Pallas and XLA step confidences differ by "
             f"{dlog.max():.4f} in log space (> {LOG_CONF_TOL})")


def serve(engine, reqs, *, on_first_slice=None):
    """Submit and drain through ``slice_step``; returns (responses,
    seconds of serving, compilation included, the first-slice checks
    excluded). Every slice boundary checks the recorded confidences."""
    import numpy as np
    sched = engine.scheduler
    sched.submit(reqs)
    out, t0, t_checks = [], time.perf_counter(), 0.0
    first = True
    while sched.queue or any(s.state == "active" for s in sched.slots):
        got = sched.slice_step()
        out.extend(got)
        carry = sched.carry
        if carry is not None:
            conf = np.asarray(carry.conf)[np.asarray(carry.conf_valid)]
            if not np.isfinite(conf).all():
                fail("non-finite confidence recorded by the slice program")
            if first and on_first_slice is not None:
                t1 = time.perf_counter()
                on_first_slice()
                t_checks += time.perf_counter() - t1
        first = False
        if not got and not any(s.state == "active" for s in sched.slots):
            break
    wall = time.perf_counter() - t0 - t_checks
    return sorted(out, key=lambda r: r.uid), wall


def check_responses(out, reqs, cfg) -> None:
    if sorted(r.uid for r in out) != [r.uid for r in reqs]:
        fail(f"answered {len(out)} of {len(reqs)} requests")
    for r in out:
        bad = [t for t in r.token_ids if not 0 <= t < cfg.vocab_size]
        if bad:
            fail(f"request {r.uid}: token ids outside the vocabulary: "
                 f"{bad[:5]}")


def one_chip(layers: int, seed: int, clock: CompileClock) -> None:
    import jax

    from repro.launch.serve import random_model
    from repro.serving.engine import DiffusionEngine
    t0 = time.perf_counter()
    cfg, params = random_model("llada-8b", layers=layers, seed=seed)
    jax.block_until_ready(params)
    describe(cfg)
    print(f"weights: seeded random (seed {seed}), drawn on device in "
          f"{time.perf_counter() - t0:.1f} s")
    dcfg, ecfg = decode_configs()
    engine = DiffusionEngine(params, cfg, dcfg, ecfg=ecfg)
    reqs = requests(seed)

    def first_slice():
        print(f"first slice: {clock}")
        check_slice_hlo(engine, cfg, dcfg)
        check_step_agreement(engine, cfg, dcfg)

    out, wall = serve(engine, reqs, on_first_slice=first_slice)
    check_responses(out, reqs, cfg)
    st = engine.stats
    view = engine.sessions[TASK]
    if not view.calibrated:
        fail(f"task {TASK!r} was never calibrated")
    tab = view.table
    print(f"osdt: task {TASK!r} calibrated from its first request; table "
          f"{tab.shape} in [{tab.min():.3e}, {tab.max():.3e}] served the "
          f"requests admitted after it")
    print(f"served: {st.requests} requests, {st.tokens} tokens delivered, "
          f"NFE {st.nfe} (prefill {st.prefill_nfe}), {st.slices} slices; "
          f"denoising steps per request {[r.nfe for r in out]} "
          f"(steps_cap {dcfg.steps_cap} x {dcfg.num_blocks} blocks)")
    print("  random weights give near-uniform confidences (~1e-4): the "
          "static 0.9 threshold of the requests admitted before "
          "calibration never passes, so they unmask one token per step; "
          "the table calibrated from those confidences passes most "
          "positions at once")
    print(f"smoke wall (NOT a benchmark): {wall:.2f} s serving incl. "
          f"compile, {st.tokens / wall:.1f} tokens/s; slice dispatches "
          f"{st.wall_s:.2f} s, {st.tokens / st.wall_s:.1f} tokens/s")
    print(f"compile: {clock}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"hbm: peak_bytes_in_use {stats.get('peak_bytes_in_use', 0)} "
          f"of bytes_limit {stats.get('bytes_limit', 0)}")


def four_chips(layers: int, seed: int, clock: CompileClock) -> None:
    import jax

    from repro.launch.serve import random_model
    from repro.serving.engine import DiffusionEngine
    if len(jax.devices()) < 4:
        fail(f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    cfg, params = random_model("llada-8b", layers=layers, seed=seed)
    describe(cfg)
    print(f"depth {layers}: the one-device weights and a mesh copy of "
          f"them share device 0")
    reqs = requests(seed)
    runs = {}
    # the mesh path runs XLA attention and the unfused epilogue: a Pallas
    # kernel inside a mesh-sharded program needs a shard_map the runtime
    # does not build yet (the TPU compiler refuses to partition it), so
    # the one-device run it is compared with takes the same path
    for dp, mp in ((1, 1), (4, 1), (2, 2)):
        dcfg, ecfg = decode_configs("auto", "unfused", data_parallel=dp,
                                    model_parallel=mp)
        engine = DiffusionEngine(params, cfg, dcfg, ecfg=ecfg)
        out, wall = serve(engine, [dataclasses.replace(r) for r in reqs])
        check_responses(out, reqs, cfg)
        runs[dp, mp] = out
        print(f"data={dp} model={mp}: {engine.stats.tokens} tokens, NFE "
              f"{engine.stats.nfe}, smoke wall {wall:.2f} s "
              f"(NOT a benchmark); {clock}")
        del engine
        gc.collect()
    base = runs[1, 1]
    same = [a.token_ids == b.token_ids for a, b in zip(base, runs[4, 1])]
    if not all(same):
        fail(f"data=4 tokens differ from one device for requests "
             f"{[r.uid for r, s in zip(base, same) if not s]}")
    print(f"data=4: tokens identical to one device for all {len(base)} "
          f"requests")
    tp = runs[2, 2]
    n_same = sum(a.token_ids == b.token_ids for a, b in zip(base, tp))
    pos = [x == y for a, b in zip(base, tp)
           for x, y in zip(a.token_ids, b.token_ids)]
    print(f"data=2 x model=2: tokens identical for {n_same} of "
          f"{len(base)} requests, {sum(pos)} of {len(pos)} positions "
          f"(tensor-parallel sums reorder; random-weight confidences are "
          f"near ties)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh path (data=4, data=2 x "
                         "model=2) and the one-device run it is compared "
                         "with")
    ap.add_argument("--layers", type=int, default=0,
                    help=f"depth cut (default {LAYERS_ONE_CHIP}, "
                         f"{LAYERS_FOUR_CHIPS} with --four-chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        fail(f"JAX finds no TPU (backend {backend!r}); this smoke run "
             f"never falls back to the CPU")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repo's src/ is not next to this script: {e}")
    devs = jax.devices()
    print(f"device: {devs[0].device_kind} x {len(devs)} "
          f"(platform {devs[0].platform}), jax {jax.__version__}")
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    if args.four_chips:
        four_chips(args.layers or LAYERS_FOUR_CHIPS, args.seed, clock)
    else:
        one_chip(args.layers or LAYERS_ONE_CHIP, args.seed, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
