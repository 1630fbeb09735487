"""Observability overhead gate: tracing + drift telemetry must be ≈ free.

Same weights, same pre-calibrated per-task tables, same request stream —
the only variable is ``EngineConfig.trace`` / ``drift_telemetry``. The
obs-off engine is the plain sliced runtime; the obs-on engine records
every span (admit / slice / retire / promote), accumulates the
carry-resident confidence telemetry, and scores every retiring row
against the stored calibration profile. The gate asserts both halves of
the "always compiled, off by default" contract:

  * delivered text is IDENTICAL with tracing on (the telemetry
    accumulators ride the carry but never feed back into decoding), and
  * obs-on tokens/s is within ``REPRO_OBS_MAX_OVERHEAD`` (default 5%) of
    obs-off, best-of-``REPS`` walls on both sides, each wall covering
    ``ROUNDS`` back-to-back submits of the stream — single-submit walls
    are tens of ms at toy size and scheduler jitter alone exceeds the
    gate.

Artifacts: ``experiments/obs_trace.json`` (Chrome/Perfetto
trace_event JSON, schema-validated here) and
``experiments/obs_metrics.prom`` (Prometheus text exposition).

  REPRO_OBS_BENCH_REQS=8 PYTHONPATH=src:. python -m benchmarks.run obs
"""
from __future__ import annotations

import os
import time
from typing import List

from benchmarks import common
from repro.config.base import DecodeConfig, EngineConfig
from repro.core.osdt import CalibrationStore
from repro.obs.trace import validate_trace
from repro.serving.engine import DiffusionEngine

N_REQS = int(os.environ.get("REPRO_OBS_BENCH_REQS", "16"))
MAX_OVERHEAD = float(os.environ.get("REPRO_OBS_MAX_OVERHEAD", "0.05"))
REPS = 3
ROUNDS = 3
BATCH = 4
BLOCK = 4
RESP = 32
SLICE = 1
TASKS_USED = ("gsm8k-syn", "humaneval-syn")


def _dcfg() -> DecodeConfig:
    return common.default_dcfg(max_new_tokens=RESP, block_size=BLOCK)


def _ecfg(obs: bool) -> EngineConfig:
    return EngineConfig(batch_size=BATCH, prompt_len=common.PROMPT_LEN,
                        slice_len=SLICE, eos_early_exit=True,
                        trace=obs, drift_telemetry=obs)


def _engine(params, cfg, store, obs: bool) -> DiffusionEngine:
    dcfg = _dcfg()
    eng = DiffusionEngine(params, cfg, dcfg, ecfg=_ecfg(obs),
                          store=CalibrationStore(dcfg))
    eng.store.profiles.update(store.profiles)
    eng.store.tables.update(store.tables)
    return eng


def run(csv_rows: List[str], verbose: bool = True) -> None:
    cfg, params = common.get_model(verbose=verbose)

    # one-shot calibration shared by every engine (the paper's tables)
    dcfg = _dcfg()
    calib = DiffusionEngine(params, cfg, dcfg, ecfg=_ecfg(False),
                            store=CalibrationStore(dcfg))
    reqs, gold = common.request_stream(N_REQS, TASKS_USED, seed=31)
    calib.submit(reqs[:len(TASKS_USED)])
    store = calib.store

    # warm the compiled program family once per side (identical programs
    # — telemetry is always compiled in — but pay the trace cost outside
    # the timed reps), then interleave best-of-REPS timed runs
    for obs in (False, True):
        warm = _engine(params, cfg, store, obs)
        warm.submit(list(reqs[:BATCH]))
    walls = {False: [], True: []}
    texts = {}
    engines = {}
    for rep in range(REPS):
        for obs in (False, True):
            eng = _engine(params, cfg, store, obs)
            t0 = time.perf_counter()
            for _ in range(ROUNDS):
                out = eng.submit(list(reqs))
            walls[obs].append(time.perf_counter() - t0)
            texts[obs] = {r.uid: r.text for r in out}
            engines[obs] = eng
    tokens = engines[True].stats.tokens  # ROUNDS submits' worth
    assert tokens == engines[False].stats.tokens
    assert texts[True] == texts[False], \
        "tracing must not change decode output"
    tps_off = tokens / min(walls[False])
    tps_on = tokens / min(walls[True])
    overhead = max(0.0, 1.0 - tps_on / tps_off)
    assert overhead <= MAX_OVERHEAD, \
        (f"observability overhead {overhead:.1%} exceeds the "
         f"{MAX_OVERHEAD:.0%} gate (off={tps_off:.1f} on={tps_on:.1f} "
         f"tokens/s)")

    eng = engines[True]
    obs = eng.obs

    # artifacts: schema-valid Perfetto trace + Prometheus snapshot
    trace_path = common.ROOT / "experiments" / "obs_trace.json"
    obs.save_trace(trace_path)
    counts = validate_trace(obs.tracer.export())
    prom_path = common.ROOT / "experiments" / "obs_metrics.prom"
    prom = obs.prometheus()
    prom_path.write_text(prom)
    assert "repro_engine_tokens" in prom and "repro_drift_cosine" in prom

    rows = [(f"obs/overhead/tracing,"
             f"{min(walls[True]) / max(tokens, 1) * 1e6:.2f},"
             f"tok_per_s_off={tps_off:.1f};tok_per_s_on={tps_on:.1f};"
             f"overhead={overhead:.4f};gate={MAX_OVERHEAD:.2f};"
             f"same_text=1"),
            (f"obs/trace/events,{len(obs.tracer.events())},"
             f"spans={counts['spans']};async={counts['async']};"
             f"instants={counts['instants']};"
             f"dropped={obs.tracer.dropped}")]
    for task, d in sorted(obs.drift.snapshot().items()):
        rows.append(f"obs/drift/{task},{d['cosine']:.4f},"
                    f"drift={d['drift']:.4f};stale={int(d['stale'])};"
                    f"obs={d['observations']};"
                    f"fallback={d['fallback_frac']:.3f};"
                    f"margin={d['margin_mean']:.3f}")
    for row in rows:
        csv_rows.append(row)
        if verbose:
            print(row)


if __name__ == "__main__":
    run([])
