#!/usr/bin/env python3
"""Sweep the arrival rate of an open-loop cell to find its knee.

  python3 bench/sweep.py --workload llada8b-longdoc-open --seed 7 \\
      --rates 0.5,0.6,0.7,0.8,0.9,1.0 --seconds 51

Sets the cell up once, then offers Poisson load at each rate in turn for
``--seconds`` (the system drained to idle between rates) and prints, per
rate, the time to first block (p50 and p90) of the first and of the
second half of the requests due in the window (halves of equal count, in
order of due time), the latency p90, and the queue left at the close.
The knee is the highest rate at which the second half's ttfb p90 does
not grow past the first half's; a cell runs at a fixed fraction of it,
written into its traffic file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = run.Cell.find(args.workload, bench)
    if cell.mix.loop != "open":
        raise run.Refused("a sweep needs an open-loop cell")
    jax = run.use_cache()
    if jax.devices()[0].platform != "tpu":
        raise run.Refused("the knee is found on the chip")
    sys.path.insert(0, str(run.ROOT / "src"))
    clock = run.CompileClock()
    r = run.Run(cell, args.seed, args.seconds, False)
    r.setup(clock)
    rows = []
    for i, rate in enumerate(float(x) for x in args.rates.split(",")):
        while r._in_system():
            r._slice()
        cell.mix.rate = rate
        gen = cell.mix.requests(args.seed, stream=2 + i)
        t0 = time.perf_counter()
        end = t0 + args.seconds
        measured, pending = [], None
        while True:
            now = time.perf_counter()
            while True:
                if pending is None:
                    pending = next(gen)
                due = t0 + pending.due
                if due > now:
                    break
                if due < end:
                    measured.append(pending)
                r._submit(pending, at=due)
                pending = None
            if now >= end:
                break
            if r._in_system():
                r._slice()
            else:
                time.sleep(max(min(t0 + pending.due, end) - now, 0.0))
        queued = len(r.sched.queue)
        drain_end = time.perf_counter() + 60.0
        while any(q.uid not in r.responses for q in measured) and \
                time.perf_counter() < drain_end:
            r._slice()
        half = len(measured) // 2

        def stats(sel):
            ttfb = [(r.admit_t[q.uid] - t0 - q.due) * 1e3 for q in sel
                    if q.uid in r.admit_t]
            lat = [(r.done_t[q.uid] - t0 - q.due) * 1e3 for q in sel
                   if q.uid in r.done_t]
            return (len(sel), run.pct(ttfb, 50) if ttfb else None,
                    run.pct(ttfb, 90) if ttfb else None,
                    run.pct(lat, 90) if lat else None)

        a = stats(measured[:half])
        b = stats(measured[half:2 * half])
        row = {"rate": rate, "requests": len(measured),
               "queued_at_close": queued,
               "first_half": {"n": a[0], "ttfb_p50_ms": a[1],
                              "ttfb_p90_ms": a[2], "latency_p90_ms": a[3]},
               "second_half": {"n": b[0], "ttfb_p50_ms": b[1],
                               "ttfb_p90_ms": b[2], "latency_p90_ms": b[3]}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"sweep": rows}))


if __name__ == "__main__":
    main()
