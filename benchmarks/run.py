"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows, tees them to
experiments/bench_results.csv, and writes each bench's rows to
``experiments/BENCH_<name>.json`` (machine-readable per-bench artifact).
See DESIGN.md §7 for the experiment index.

  python -m benchmarks.run            # everything
  python -m benchmarks.run table1     # one benchmark
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import List

from benchmarks import (block_attn, cache_modes, fig1_confidence,
                        fig2_cosine, fig3_5_sweep, fused_step, kernels_bench,
                        quantized_decode, table1_compare)
from repro.launch.compile_cache import enable_compile_cache

BENCHES = {
    "fig1": fig1_confidence.run,
    "fig2": fig2_cosine.run,
    "table1": table1_compare.run,
    "fig3_5": fig3_5_sweep.run,
    "cache_modes": cache_modes.run,
    "kernels": kernels_bench.run,
    "block_attn": block_attn.run,
    "fused_step": fused_step.run,
    "quant": quantized_decode.run,
}


def _provenance() -> dict:
    """Environment stamp for every bench artifact: *which* code, runtime,
    machine, and bench-model produced these numbers. A row that can't be
    traced to its producer can't be compared across PRs."""
    import socket
    import subprocess

    import jax

    from benchmarks.common import CKPT
    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True, timeout=10,
                             cwd=Path(__file__).resolve().parent
                             ).stdout.strip() or "unknown"
    except OSError:
        sha = "unknown"
    steps = None
    if CKPT.exists():
        try:
            from repro.checkpoint.checkpoint import peek_meta
            steps = peek_meta(str(CKPT)).get("steps")
        except Exception:
            steps = None
    return {"git_sha": sha, "jax": jax.__version__,
            "backend": jax.default_backend(),
            "host": socket.gethostname(),
            "bench_model_train_steps": steps}


def _prov_row(bench: str, prov: dict) -> str:
    kv = ";".join(f"{k}={v}" for k, v in sorted(prov.items()))
    return f"provenance/{bench},0,{kv}"


def _merge(out: Path, rows: List[str]) -> List[str]:
    """Replace same-name rows in the existing csv, keep the rest — a
    partial run must not clobber previously recorded benchmarks."""
    fresh = {r.split(",", 1)[0]: r for r in rows}
    merged: List[str] = []
    if out.exists():
        for line in out.read_text().splitlines()[1:]:
            name = line.split(",", 1)[0]
            if line.strip() and name not in fresh:
                merged.append(line)
    merged.extend(rows)
    return merged


def _bench_json(exp_dir: Path, name: str, rows: List[str],
                prov: dict) -> None:
    """experiments/BENCH_<name>.json: the bench's rows as records —
    the per-bench artifact CI and notebooks consume without parsing the
    merged csv."""
    recs = []
    for r in rows:
        parts = r.split(",", 2)
        recs.append({"name": parts[0],
                     "us_per_call": parts[1] if len(parts) > 1 else "",
                     "derived": parts[2] if len(parts) > 2 else ""})
    (exp_dir / f"BENCH_{name}.json").write_text(
        json.dumps({"bench": name, "provenance": prov, "rows": recs},
                   indent=1) + "\n")


def main() -> None:
    enable_compile_cache()
    which = sys.argv[1:] or list(BENCHES)
    rows: List[str] = []
    exp_dir = Path(__file__).resolve().parents[1] / "experiments"
    exp_dir.mkdir(parents=True, exist_ok=True)
    print("name,us_per_call,derived")
    for name in which:
        n0 = len(rows)
        BENCHES[name](rows, verbose=True)
        # stamp AFTER the bench ran: common.get_model may have just
        # (re)trained the bench checkpoint this stamp describes
        prov = _provenance()
        _bench_json(exp_dir, name, rows[n0:], prov)
        rows.append(_prov_row(name, prov))
        print(rows[-1])
    out = exp_dir / "bench_results.csv"
    merged = _merge(out, rows)
    out.write_text("name,us_per_call,derived\n" + "\n".join(merged) + "\n")
    print(f"# wrote {len(rows)} rows ({len(merged)} total) -> {out}")


if __name__ == "__main__":
    main()
