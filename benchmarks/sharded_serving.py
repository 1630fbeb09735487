"""Mesh-sharded serving: tokens/s scaling over the ``data`` axis.

Same weights, same request stream, same paged sliced runtime — the only
variable is ``EngineConfig.data_parallel``.

A device belongs to one process at a time, so where and how each width
runs depends on the backend:

* real devices (``JAX_PLATFORMS`` not ``cpu``): ONE process loads the
  bench checkpoint and runs every width that fits ``jax.devices()`` in
  turn;
* fake CPU devices (``JAX_PLATFORMS=cpu``): the device count
  (``XLA_FLAGS=--xla_force_host_platform_device_count=N``) must be set
  before JAX starts, so each width runs in a CHILD process. This parent
  never starts JAX itself; the children load (the first one trains, if
  it is missing) the bench checkpoint.

Scaling gates (>= 1.6x at data=2, >= 2.5x at data=4 vs data=1) are
asserted only when the backend genuinely parallelizes shards onto
distinct hardware (non-CPU). Fake CPU devices timeshare one host — there
the curve is RECORDED un-gated (``experiments/bench_results.csv`` +
``experiments/BENCH_mesh.json``) so a real-accelerator run can diff it.

  REPRO_MESH_BENCH_REQS=8 JAX_PLATFORMS=cpu PYTHONPATH=src:. \\
      python -m benchmarks.run mesh
"""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import textwrap
import time
from typing import List

from benchmarks import common

N_REQS = int(os.environ.get("REPRO_MESH_BENCH_REQS", "16"))
BATCH = 4
DATA = (1, 2, 4)

_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_MESH_BENCH_DP"])
    import json
    from benchmarks import common, sharded_serving
    cfg, params = common.get_model(verbose=False)
    print(json.dumps(sharded_serving.measure(
        int(os.environ["REPRO_MESH_BENCH_DP"]), cfg, params)))
""")


def measure(dp: int, cfg, params) -> dict:
    """Serve the stream at ``data_parallel=dp`` (after a warm-up engine
    that pays trace/compile for the program family)."""
    import jax

    from repro.config.base import EngineConfig
    from repro.serving.scheduler import Scheduler
    dcfg = common.default_dcfg(cache_layout="paged")

    def sched():
        return Scheduler(params, cfg, dcfg,
                         ecfg=EngineConfig(batch_size=BATCH,
                                           prompt_len=common.PROMPT_LEN,
                                           slice_len=1, data_parallel=dp))

    reqs, _ = common.request_stream(N_REQS + BATCH, ("gsm8k-syn",), seed=7)
    warm = sched()
    warm.submit(reqs[N_REQS:])
    warm.run()
    s = sched()
    s.submit(reqs[:N_REQS])
    t0 = time.perf_counter()
    out = s.run()
    wall = time.perf_counter() - t0
    st = s.stats
    return {
        "dp": dp, "devices": jax.device_count(),
        "backend": jax.default_backend(), "requests": len(out),
        "tokens": st.tokens, "nfe": st.nfe, "wall_s": wall,
        "tokens_per_s": st.tokens / max(wall, 1e-9),
        "texts_fp": hashlib.sha1(json.dumps(
            sorted((r.uid, r.text) for r in out)).encode()).hexdigest()}


def _child(dp: int) -> dict:
    env = dict(os.environ, REPRO_MESH_BENCH_DP=str(dp))
    env.pop("XLA_FLAGS", None)  # the child sets its own, pre-jax
    out = subprocess.run([sys.executable, "-c", _CHILD],
                         capture_output=True, text=True, timeout=1800,
                         env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(csv_rows: List[str], verbose: bool = True) -> None:
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        results = {dp: _child(dp) for dp in DATA}
    else:
        import jax
        cfg, params = common.get_model(verbose=verbose)
        results = {dp: measure(dp, cfg, params) for dp in DATA
                   if dp <= jax.device_count()}
    base = results[1]["tokens_per_s"]
    parallel_hw = results[1]["backend"] != "cpu"
    for dp, r in results.items():
        speedup = r["tokens_per_s"] / max(base, 1e-9)
        row = (f"sharded/data{dp},"
               f"{r['wall_s'] / max(r['tokens'], 1) * 1e6:.2f},"
               f"tok={r['tokens']};tok_per_s={r['tokens_per_s']:.1f};"
               f"nfe={r['nfe']};speedup={speedup:.2f};"
               f"devices={r['devices']};backend={r['backend']};"
               f"gated={int(parallel_hw)}")
        csv_rows.append(row)
        if verbose:
            print(row)
    # responses must not depend on the shard count (data-axis sharding
    # is bitwise) — a throughput number over different texts is noise
    assert len({r["texts_fp"] for r in results.values()}) == 1, \
        "sharded runs diverged: responses differ across data_parallel"
    if parallel_hw:
        for dp, bound in ((2, 1.6), (4, 2.5)):
            if dp in results:
                sp = results[dp]["tokens_per_s"] / base
                assert sp >= bound, f"data={dp} speedup {sp:.2f} < {bound}"
    elif verbose:
        print("# cpu fake-device mesh: shards timeshare one host — "
              "scaling gates recorded, not asserted")


if __name__ == "__main__":
    run([])
