"""Render EXPERIMENTS.md §Dry-run / §Roofline / §Step tables.

  PYTHONPATH=src python -m repro.roofline.report [--mesh 16x16]
  PYTHONPATH=src python -m repro.roofline.report --section step

§Dry-run and §Roofline read the dry-run JSONs; §Step reads
``experiments/bench_results.csv`` (the ``roofline/step_us_model/*`` rows
written by ``benchmarks/fused_step.py`` next to the measured epilogue
timings) and renders the µs-per-denoising-step model per decode variant.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
DRYRUN = ROOT / "experiments" / "dryrun"
BENCH_CSV = ROOT / "experiments" / "bench_results.csv"

SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
ARCHS = [
    "mamba2-130m", "qwen3-moe-235b-a22b", "deepseek-67b", "qwen1.5-0.5b",
    "qwen1.5-110b", "zamba2-1.2b", "llama4-maverick-400b-a17b",
    "internvl2-76b", "smollm-135m", "musicgen-large",
]


def load(mesh_tag: str) -> dict:
    out = {}
    for f in DRYRUN.glob(f"*__{mesh_tag}*.json"):
        rec = json.loads(f.read_text())
        key = (rec["arch"], rec["shape"], rec.get("variant"))
        out[key] = rec
    return out


def fmt_bytes(b: float) -> str:
    return f"{b / 2**30:.2f}"


def fmt_s(s: float) -> str:
    if s >= 1:
        return f"{s:.2f}s"
    return f"{s * 1e3:.2f}ms"


def dryrun_table(recs: dict, mesh_tag: str) -> str:
    lines = [
        f"### Mesh {mesh_tag}",
        "",
        "| arch | shape | step | compile | HBM/dev GiB | fits 16G | "
        "collective MiB/step | µbatches |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for arch in ARCHS:
        for shape in SHAPES:
            rec = recs.get((arch, shape, None)) or recs.get(
                (arch, shape, {"train_4k": "train", "prefill_32k": "prefill",
                               "decode_32k": "decode",
                               "long_500k": "decode"}[shape]))
            if rec is None:
                lines.append(f"| {arch} | {shape} | — | MISSING | | | | |")
                continue
            coll = sum(rec["collectives"].values())
            lines.append(
                f"| {arch} | {shape} | {rec['variant']} | "
                f"{rec['compile_s']:.1f}s | "
                f"{fmt_bytes(rec['memory']['footprint_bytes_per_dev'])} | "
                f"{'yes' if rec['memory']['fits_16g_hbm'] else 'NO'} | "
                f"{coll / 2**20:.0f} | "
                f"{rec.get('grad_accum_microbatches', 1)} |")
    return "\n".join(lines)


def roofline_table(recs: dict, mesh_tag: str) -> str:
    lines = [
        f"### Mesh {mesh_tag}",
        "",
        "| arch | shape | compute | memory | collective | bound | "
        "MODEL_FLOPs/HLO | note |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for arch in ARCHS:
        for shape in SHAPES:
            rec = None
            for k, v in recs.items():
                if k[0] == arch and k[1] == shape:
                    rec = v
                    break
            if rec is None:
                continue
            t = rec["roofline"]
            ratio = rec["useful_flop_ratio"]
            note = _note(rec)
            lines.append(
                f"| {arch} | {shape} | {fmt_s(t['compute_s'])} | "
                f"{fmt_s(t['memory_s'])} | {fmt_s(t['collective_s'])} | "
                f"{t['dominant'].replace('_s', '')} | {ratio:.2f} | {note} |")
    return "\n".join(lines)


def _note(rec: dict) -> str:
    dom = rec["roofline"]["dominant"]
    if dom == "collective_s":
        biggest = max(rec["collectives"], key=rec["collectives"].get)
        return f"cut {biggest} volume (bf16 collectives / wider DP)"
    if dom == "memory_s":
        return "raise arithmetic intensity (fuse, larger tiles, quantize KV)"
    return "good: MXU-bound; overlap collectives to hold it"


def _bench_rows() -> dict:
    """bench_results.csv -> {name: (value, derived)}."""
    out = {}
    if not BENCH_CSV.exists():
        return out
    for line in BENCH_CSV.read_text().splitlines()[1:]:
        if not line.strip():
            continue
        name, value, derived = (line.split(",", 2) + ["", ""])[:3]
        out[name] = (value, derived)
    return out


def step_table() -> str:
    """µs-per-denoising-step model per decode variant, next to the
    measured epilogue chain (``benchmarks/fused_step.py``)."""
    rows = _bench_rows()
    lines = [
        "### µs / denoising step (model: llada-8b, B=8, ctx=4k, bs=32, "
        "tpu-v5e)",
        "",
        "| layout | rows | epilogue | model µs/step | bound | dispatches |",
        "|---|---|---|---|---|---|",
    ]
    prefix = "roofline/step_us_model/"
    found = False
    for name in sorted(rows):
        if not name.startswith(prefix):
            continue
        found = True
        layout, geom, fusion = name[len(prefix):].split("/")
        us, derived = rows[name]
        bound, _, disp = derived.partition("_bound_d")
        lines.append(f"| {layout} | {geom} | {fusion} | {us} | {bound} | "
                     f"{disp} |")
    if not found:
        return ("(no roofline/step_us_model rows — run "
                "`python -m benchmarks.run fused_step` first)")
    qprefix = "roofline/step_us_model_int8/"
    qnames = [n for n in sorted(rows) if n.startswith(qprefix)]
    if qnames:
        lines += ["", "int8 weight streaming "
                  "(`benchmarks/quantized_decode.py`):", "",
                  "| layout | rows | epilogue | model µs/step | bound | "
                  "dispatches |", "|---|---|---|---|---|---|"]
        for name in qnames:
            layout, geom, fusion = name[len(qprefix):].split("/")
            us, derived = rows[name]
            bound, _, disp = derived.partition("_bound_d")
            lines.append(f"| {layout} | {geom} | {fusion} | {us} | "
                         f"{bound} | {disp} |")
    lines += ["", "tensor-parallel decode (analytic, paged/per_row/fused; "
              "`step_time_model(tp=...)` — Megatron all-reduce pair per "
              "layer + vocab-sharded head over ICI, SERVING.md 'Sharded "
              "serving'):", "",
              "| tp | model µs/step | ici µs | bound | speedup |",
              "|---|---|---|---|---|"]
    from repro.config.registry import get_config
    from repro.roofline.analytic import step_time_model
    _cfg = get_config("llada-8b")
    base = None
    for tp in (1, 2, 4, 8):
        v = step_time_model(_cfg, batch=8, ctx=4096, block_size=32,
                            tp=tp)["paged/per_row/fused"]
        base = base or v["us"]
        lines.append(f"| {tp} | {v['us']:.1f} | {v['ici_us']:.1f} | "
                     f"{v['bound']} | {base / v['us']:.2f}x |")
    lines += ["", "measured epilogue (CPU container; real kernel timing "
              "needs a TPU):", ""]
    for key in ("fused_step/unfused_epilogue", "fused_step/fused_epilogue",
                "fused_step/dispatches_unfused",
                "fused_step/dispatches_fused",
                "fused_step/logit_hbm_passes_unfused",
                "fused_step/logit_hbm_passes_fused"):
        for name in sorted(rows):
            if name == key or name.startswith(key + "/"):
                us, derived = rows[name]
                lines.append(f"* `{name}` = {us} ({derived})")
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--section",
                    choices=["dryrun", "roofline", "step", "both", "all"],
                    default="both")
    args = ap.parse_args()
    if args.section == "step":
        print(step_table())
        return
    recs = load(args.mesh)
    if args.section in ("dryrun", "both", "all"):
        print(dryrun_table(recs, args.mesh))
        print()
    if args.section in ("roofline", "both", "all"):
        print(roofline_table(recs, args.mesh))
    if args.section == "all":
        print()
        print(step_table())


if __name__ == "__main__":
    main()
