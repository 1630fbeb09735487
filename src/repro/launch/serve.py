"""Serving launcher: batched OSDT diffusion serving.

Serves either the trained bench checkpoint or, with ``--arch``, a
registered configuration at its published widths with seeded random
weights (``--layers`` cuts the depth to fit the device):

  PYTHONPATH=src python -m repro.launch.serve --ckpt experiments/bench_model.msgpack \\
      --policy osdt --n 16
  PYTHONPATH=src python -m repro.launch.serve --arch llada-8b --layers 16 \\
      --prompt-len 256 --max-new 128 --block 32 --slice-len 1 \\
      --cache-layout paged --attn-impl kernel --step-fusion fused
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.checkpoint.checkpoint import restore
from repro.config.base import DecodeConfig, EngineConfig
from repro.config.registry import get_config, list_archs
from repro.data import tokenizer as tok
from repro.data.tasks import TASKS
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving.engine import DiffusionEngine, Request


def random_model(arch: str, *, layers: int = 0, seed: int = 0):
    """``(cfg, params)``: the registered ``arch`` at its published widths,
    its depth cut to ``layers`` when non-zero (widths never change), with
    seeded random weights. The draw runs under ``jax.jit`` on the default
    device, where XLA fuses the float32 normal draw into the cast to the
    parameter dtype, so no float32 copy of a stacked weight is held."""
    cfg = get_config(arch)
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    cfg = dataclasses.replace(cfg, mask_token_id=tok.MASK_ID)
    params = jax.jit(lambda s: M.init_params(jax.random.key(s), cfg))(seed)
    return cfg, params


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", default="experiments/bench_model.msgpack")
    ap.add_argument("--arch", default="", choices=[""] + list_archs(),
                    help="serve this registered configuration at its "
                         "published widths with seeded random weights "
                         "instead of the bench checkpoint")
    ap.add_argument("--layers", type=int, default=0,
                    help="with --arch: cut the depth to this many layers "
                         "(0 = the configuration's own depth)")
    ap.add_argument("--seed", type=int, default=0,
                    help="with --arch: random-weight seed")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--policy", default="osdt",
                    choices=["static", "factor", "osdt"])
    ap.add_argument("--task", default="gsm8k-syn", choices=list(TASKS))
    ap.add_argument("--n", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--block", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--cache-mode", default="prefix",
                    choices=["prefix", "dual", "none"])
    ap.add_argument("--cache-layout", default="dense",
                    choices=["dense", "paged"],
                    help="paged: page-pool KV with per-slot page tables — "
                         "dead slots pin zero pages (SERVING.md)")
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "dense", "flash", "kernel"],
                    help="block-step attention path; kernel = the Pallas "
                         "block-attention kernels on TPU (KERNELS.md)")
    ap.add_argument("--step-fusion", default="unfused",
                    choices=["unfused", "fused"],
                    help="fused: one kernel for lm head + confidence + "
                         "threshold select (KERNELS.md 'fused step')")
    ap.add_argument("--page-size", type=int, default=16,
                    help="cache slots per page (paged layout)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool capacity; 0 = auto-size for the batch")
    ap.add_argument("--shared-prefix", default="",
                    help="system prompt prefilled once into refcounted "
                         "shared pages and mapped into every slot")
    ap.add_argument("--store", default="",
                    help="npz path persisting per-task calibration across "
                         "restarts (SERVING.md)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative block drafting: one-shot-draft the "
                         "blocks each task's calibrated signature predicts "
                         "easy, verify, and skip their denoising steps "
                         "(SERVING.md 'Speculative drafting')")
    ap.add_argument("--draft-max-steps", type=int, default=1,
                    help="draft blocks predicted to clear in <= this many "
                         "steps (spec decode)")
    ap.add_argument("--slice-len", type=int, default=1,
                    help="blocks decoded per compiled slice; queued "
                         "requests admit into freed slots at every slice "
                         "boundary, MID-generation (SERVING.md 'Async "
                         "admission')")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prefix cache: reuse page-aligned "
                         "prompt-prefix KV across requests and tenants, "
                         "prefilling only each row's novel remainder "
                         "(needs --cache-layout paged; SERVING.md 'Radix "
                         "prefix cache')")
    ap.add_argument("--prefix-cache-pages", type=int, default=0,
                    help="page budget the tree may pin (0 = bounded by "
                         "the pool; LRU-evicted under pressure)")
    ap.add_argument("--prefix-cache-watermark", type=float, default=0.0,
                    help="fraction of the pool eviction keeps free "
                         "beyond each admission's immediate need")
    ap.add_argument("--data-parallel", type=int, default=1,
                    help="shard the slot pool (and paged page pool) over "
                         "this many devices on the mesh's 'data' axis — "
                         "needs batch divisible (SERVING.md 'Sharded "
                         "serving')")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="tensor-parallel decode over the mesh's 'model' "
                         "axis via the 'serve' weight specs (dims that "
                         "don't divide replicate)")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome/Perfetto trace_event JSON of the "
                         "run here (enables the ring-buffer tracer; "
                         "SERVING.md 'Observability')")
    ap.add_argument("--trace-capacity", type=int, default=1 << 16,
                    help="trace ring size in events (oldest evicted)")
    ap.add_argument("--metrics", default="",
                    help="write a Prometheus text-exposition snapshot of "
                         "the engine's metrics registry here ('-' = stdout)")
    ap.add_argument("--metrics-json", default="",
                    help="write the full JSON snapshot (metrics + drift) "
                         "here")
    ap.add_argument("--drift", action="store_true",
                    help="confidence-drift telemetry: score each retiring "
                         "row's live trajectory against the task's stored "
                         "calibration profile and flag staleness")
    args = ap.parse_args()

    print(f"# compile cache: {enable_compile_cache()}")
    if args.arch:
        cfg, params = random_model(args.arch, layers=args.layers,
                                   seed=args.seed)
        print(f"# {cfg.name}: {cfg.num_layers} layers, d_model "
              f"{cfg.d_model}, random weights (seed {args.seed})")
    else:
        from benchmarks.common import bench_config
        cfg = bench_config()
        shape = jax.eval_shape(lambda: M.init_params(jax.random.key(0),
                                                     cfg))
        params, meta = restore(args.ckpt, shape)
        print(f"# loaded {args.ckpt} (meta={meta})")

    dcfg = DecodeConfig(max_new_tokens=args.max_new, block_size=args.block,
                        policy=args.policy, threshold=0.9, mode="block",
                        metric="q1", cap=0.9, slack=0.1,
                        attn_impl=args.attn_impl,
                        cache_layout=args.cache_layout,
                        page_size=args.page_size,
                        step_fusion=args.step_fusion)
    ecfg = EngineConfig(batch_size=args.batch, prompt_len=args.prompt_len,
                        cache_mode=args.cache_mode, store_path=args.store,
                        num_pages=args.num_pages,
                        shared_prefix=args.shared_prefix,
                        spec_decode=args.spec_decode,
                        draft_max_steps=args.draft_max_steps,
                        slice_len=args.slice_len,
                        data_parallel=args.data_parallel,
                        model_parallel=args.model_parallel,
                        prefix_cache=args.prefix_cache,
                        prefix_cache_pages=args.prefix_cache_pages,
                        prefix_cache_watermark=args.prefix_cache_watermark,
                        trace=bool(args.trace_out),
                        trace_capacity=args.trace_capacity,
                        drift_telemetry=args.drift)
    engine = DiffusionEngine(params, cfg, dcfg, ecfg=ecfg)
    rng = np.random.default_rng(0)
    samples = TASKS[args.task].make(rng, args.n)
    reqs = [Request(i, args.task, s.prompt) for i, s in enumerate(samples)]
    out = engine.submit(reqs)
    hits = sum(TASKS[args.task].score(r.text, s)
               for r, s in zip(out, samples))
    st = engine.stats
    print(f"# {st.requests} requests  acc={hits / len(samples):.2f}  "
          f"tokens/s={st.tokens_per_s:.1f}  NFE={st.nfe}  "
          f"tokens/NFE={st.tokens_per_nfe:.2f}")
    if st.page_capacity:
        print(f"# pages: capacity={st.page_capacity} "
              f"peak={st.pages_peak} ({st.page_util:.0%}) "
              f"shared={st.pages_shared} freed={st.pages_freed}")
    if st.blocks_drafted:
        print(f"# drafting: {st.blocks_drafted} drafted "
              f"{st.blocks_accepted} accepted "
              f"({st.draft_accept_rate:.0%}) planned at "
              f"{st.draft_batches} boundaries")
    if st.prefix_hits or st.prefix_misses or st.prefix_inserts:
        print(f"# prefix cache: {st.prefix_hits} hits "
              f"{st.prefix_misses} misses "
              f"({st.prefix_hit_rate:.0%} hit rate), "
              f"{st.prefix_hit_pages} pages reused "
              f"({st.prefill_tokens_saved} prompt tokens), "
              f"{st.prefix_inserts} inserts {st.prefix_evictions} "
              f"evictions, prefill NFE={st.prefill_nfe}")
    q = [r.queue_s for r in out]
    ttfb = [r.ttfb_s for r in out]
    print(f"# {st.slices} slices, {st.mid_admits} mid-generation admits, "
          f"queue p95 {np.percentile(q, 95) * 1e3:.1f}ms, ttfb p95 "
          f"{np.percentile(ttfb, 95) * 1e3:.1f}ms")
    obs = engine.obs
    if args.drift and obs.drift is not None:
        for task, row in sorted(obs.drift.snapshot().items()):
            print(f"# drift[{task}]: cosine={row['cosine']:.4f} "
                  f"score={row['drift']:.4f} stale={row['stale']} "
                  f"obs={row['observations']} "
                  f"fallback={row['fallback_frac']:.2f} "
                  f"margin={row['margin_mean']:.3f}")
    if args.trace_out:
        obs.save_trace(args.trace_out)
        print(f"# trace: {len(obs.tracer.events())} events -> "
              f"{args.trace_out}"
              + (f" ({obs.tracer.dropped} dropped)"
                 if obs.tracer.dropped else ""))
    if args.metrics:
        text = obs.prometheus()
        if args.metrics == "-":
            print(text, end="")
        else:
            with open(args.metrics, "w") as f:
                f.write(text)
            print(f"# metrics: {args.metrics}")
    if args.metrics_json:
        import json
        with open(args.metrics_json, "w") as f:
            json.dump(obs.snapshot(), f, indent=1, sort_keys=True)
        print(f"# metrics json: {args.metrics_json}")
    for r in out[:3]:
        print(f"  [{r.uid}] {r.text!r}")


if __name__ == "__main__":
    main()
