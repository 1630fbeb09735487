"""Continuous-batching serving with per-slot OSDT tables (SERVING.md).

    PYTHONPATH=src:. python examples/serve_osdt.py [--paged] [--spec] [--prefix]

Simulates a mixed request stream across three tasks. The engine keeps ONE
calibration store and ONE compiled slice program (one block per slice);
every task calibrates on its first admitted request (all rows record
profiles, so several new tasks calibrate at once) and every batch mixes
tasks freely: the per-slot threshold table is gathered at runtime.
Requests admit into freed slots mid-generation, and rows retire at EOS,
so short answers stop costing denoising steps; the per-request
``ttfb_s`` / queue waits are measured at slice boundaries (SERVING.md
"Async admission"). With ``--paged`` the KV cache is a page pool: a
shared system prompt is prefilled once into refcounted pages, free slots
pin zero pages, and retirement reclaims pages at once. With ``--spec``
the engine decodes through the draft-and-verify program: blocks a task's
calibrated signature predicts easy are one-shot drafted and, when
verification accepts them, skip their denoising steps. Prints per-task
accuracy + throughput accounting, the per-request queue/decode split,
page occupancy, and draft acceptance. With ``--prefix`` (implies
--paged) each task's requests carry a
per-tenant system prompt in ``Request.prefix`` and the engine runs the
radix-tree prefix cache: repeat tenants reuse the tree's prefix pages
and prefill only their novel remainder (SERVING.md "Radix prefix
cache").
"""
import sys

import numpy as np

from benchmarks import common
from repro.config.base import DecodeConfig, EngineConfig
from repro.data.tasks import TASKS
from repro.serving.engine import DiffusionEngine, Request


def main() -> None:
    prefix = "--prefix" in sys.argv
    paged = "--paged" in sys.argv or prefix
    spec = "--spec" in sys.argv
    cfg, params = common.get_model()
    dcfg = DecodeConfig(max_new_tokens=32, block_size=8, policy="osdt",
                        mode="block", metric="q1", cap=0.8, slack=0.15,
                        threshold=0.9,
                        cache_layout="paged" if paged else "dense",
                        page_size=8)
    ecfg = EngineConfig(batch_size=4, prompt_len=64, cache_mode="prefix",
                        eos_early_exit=True,
                        shared_prefix="answer briefly. "
                        if paged and not prefix else "",
                        spec_decode=spec, prefix_cache=prefix)
    engine = DiffusionEngine(params, cfg, dcfg, ecfg=ecfg)

    rng = np.random.default_rng(3)
    stream, gold = [], {}
    uid = 0
    for task in TASKS:
        for s in TASKS[task].make(rng, 8):
            # per-tenant system prompt: under --prefix each task's
            # requests share one radix chain and repeat admissions
            # reuse its pages
            stream.append(Request(uid, task, s.prompt,
                                  prefix=f"[{task}] answer briefly. "
                                  if prefix else ""))
            gold[uid] = (task, s)
            uid += 1
    rng.shuffle(stream)

    responses = engine.submit(stream)
    by_task = {}
    for r in responses:
        task, s = gold[r.uid]
        by_task.setdefault(task, []).append(TASKS[task].score(r.text, s))
    for task, hits in sorted(by_task.items()):
        view = engine.sessions[task]
        print(f"{task:14s} acc={np.mean(hits):.2f}  calibrated={view.calibrated}"
              f"  tau[0,0]={float(np.asarray(view.table)[0, 0]):.3f}")
    st = engine.stats
    q = [r.queue_s for r in responses]
    d = [r.decode_s for r in responses]
    steps = [r.nfe for r in responses]
    print(f"TOTAL: {st.requests} reqs / {st.slices} slices "
          f"({st.mid_admits} mid-gen admits)  {st.tokens} tokens delivered "
          f"(+{st.tokens_dropped} truncated)  NFE={st.nfe}  "
          f"tokens/NFE={st.tokens_per_nfe:.2f}  tokens/s={st.tokens_per_s:.1f}")
    print(f"per-request: queue {np.mean(q)*1e3:.1f}ms avg / "
          f"{np.max(q)*1e3:.1f}ms max, decode {np.mean(d)*1e3:.1f}ms avg, "
          f"row steps {np.mean(steps):.1f} avg / {np.max(steps)} max")
    if st.page_capacity:
        print(f"pages: capacity={st.page_capacity} peak={st.pages_peak} "
              f"({st.page_util:.0%}) shared={st.pages_shared} "
              f"freed={st.pages_freed}")
    if st.blocks_drafted:
        print(f"drafting: {st.blocks_drafted} drafted "
              f"{st.blocks_accepted} accepted "
              f"({st.draft_accept_rate:.0%}) planned at "
              f"{st.draft_batches} boundaries")
    if st.prefix_hits or st.prefix_misses:
        print(f"prefix cache: {st.prefix_hits} hits {st.prefix_misses} "
              f"misses ({st.prefix_hit_rate:.0%}), "
              f"{st.prefill_tokens_saved} prompt tokens saved, "
              f"{st.prefix_inserts} inserts {st.prefix_evictions} "
              f"evictions, prefill NFE={st.prefill_nfe}")
    ttfb = [r.ttfb_s for r in responses]
    print(f"ttfb {np.mean(ttfb)*1e3:.1f}ms avg / "
          f"{np.max(ttfb)*1e3:.1f}ms max")


if __name__ == "__main__":
    main()
