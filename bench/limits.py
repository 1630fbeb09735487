#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, in one process.

  python3 bench/limits.py --workload <cell> --seeds 11-22 \\
      --control-seeds 31-33 [--fault token_altered,wrong_position \\
      --fault-seeds 41-43] --seconds 15 [--out readings.json]

Runs the cell on each sound seed, with the lower-precision control (the
reference computed in int8 in the program's place) on each control
seed, and with each named fault of ``faults.py`` planted on each fault
seed: each a short window at the cell's own load, then the comparison
with the reference. Prints every number of every run and, per number,
the lower reading (largest over sound seeds), the upper reading of the
control (smallest over control seeds) and of each fault. The limits in
``bench/limits/<cell>`` are set between them; see ``bench/check.py`` and
PERF.md.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import faults  # noqa: E402
import run  # noqa: E402


def seeds(spec: str) -> list:
    out = []
    for part in spec.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default="",
                    help="comma-separated names from faults.NAMES")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = run.Cell.find(args.workload, bench)
    jax = run.use_cache()
    if jax.devices()[0].platform != "tpu":
        raise run.Refused("limits are read on the chip")
    sys.path.insert(0, str(run.ROOT / "src"))
    dev = jax.devices()[0]
    plan = [("sound", seeds(args.seeds), ""),
            ("control", seeds(args.control_seeds), "")]
    plan += [(f, seeds(args.fault_seeds), f)
             for f in args.fault.split(",") if f]
    readings = {kind: {} for kind, _, _ in plan}
    for kind, ss, fault in plan:
        for s in ss:
            try:
                if fault:
                    with faults.planted(fault) as hook:
                        r = run.execute(cell, s, args.seconds, False,
                                        device=dev, engine_hook=hook)
                else:
                    r = run.execute(cell, s, args.seconds, False,
                                    control="ref-int8" if kind == "control"
                                    else "", device=dev)
                nums = {k: v["value"] for k, v in r["checks"].items()}
                nums.update(r["readings"])
            except Exception as e:  # a control that crashes has failed
                run.log(traceback.format_exc())
                run.log(f"{kind} seed {s}: {type(e).__name__}: {e}")
                nums = {"error": f"{type(e).__name__}: {e}"}
            readings[kind][s] = nums
            print(json.dumps({"kind": kind, "seed": s, **nums}), flush=True)
    summary = {}
    for k in ("token_gap", "token_gap_mean", "conf_dlog", "conf_mse",
              "order_gap"):
        got = {kind: [v[k] for v in readings[kind].values() if k in v]
               for kind in readings}
        summary[k] = {"lower": max(got["sound"], default=None)}
        summary[k].update({f"upper_{kind}": min(v, default=None)
                           for kind, v in got.items() if kind != "sound"})
    print(json.dumps({"summary": summary}))
    if args.out:
        Path(args.out).write_text(json.dumps(
            {"readings": readings, "summary": summary}, indent=1))


if __name__ == "__main__":
    main()
