#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator it is started on.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). The run draws the weights from the
seed on the device, builds the serving engine on the paged, step-sliced,
Pallas path, warms up every shape the cell's traffic uses until every
slot is busy, and then measures for ``--seconds``. ``--trace 0`` reports
the cell's end-to-end metrics; ``--trace 1`` runs the same window under
the profiler and reports the cell's per-layer metrics, each computed by
its reader ``bench/metrics/<metric>.py``. Every run then checks what the
timed path produced against the plain reference (``bench/check.py``).

Standard error carries the account of the run; the last line of
standard output is one JSON object. Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import os
import time


def _process_age() -> float:
    """Seconds since this process started, from /proc."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(up - start / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


T_PROCESS = time.perf_counter() - _process_age()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import check  # noqa: E402
import counts  # noqa: E402
import peaks  # noqa: E402
from traffic import MASK_ID, Mix, describe  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
DRAIN_S = 60.0   # a due request may still finish this long after the close


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Refused(SystemExit):
    """Exit without a result (non-zero)."""

    def __init__(self, msg: str):
        log(f"bench: {msg}")
        super().__init__(2)


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------

class Cell:
    """A workload entry with its configuration, mix and metric readers."""

    def __init__(self, name: str, cfg: dict, mix: Mix, per_layer: list,
                 limits: dict, chips: int = 1):
        self.name, self.cfg, self.mix = name, cfg, mix
        self.per_layer, self.limits, self.chips = per_layer, limits, chips

    @classmethod
    def find(cls, name: str, bench: dict) -> "Cell":
        wl = {w["name"]: w for w in bench["workloads"]}.get(name)
        if wl is None:
            raise Refused(f"no workload {name!r} in BENCHMARK.json")
        cfgs = {c["name"]: c for c in bench["configs"]}
        cfg = json.loads((ROOT / cfgs[wl["config"]]["file"]).read_text())
        mix = Mix.load(HERE / "traffic" / f"{wl['traffic']}.json")
        per_layer = [m for m in bench["per_layer"]
                     if name in m.get("workloads", [name])]
        return cls(name, cfg, mix, per_layer, check.load_limits(HERE, name),
                   int(wl["chips"]))


def model_config(cfg: dict):
    """The system's ModelConfig for a configuration file."""
    import dataclasses

    from repro.config.base import ModelConfig
    names = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg.items() if k in names}
    return ModelConfig(**kw, mask_token_id=MASK_ID)


class CompileClock:
    """Seconds XLA spent compiling, and persistent-cache hits/misses,
    from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.misses, self.compiles = 0.0, 0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def __str__(self):
        return (f"{self.seconds:.2f} s compiling ({self.compiles} programs), "
                f"persistent cache {self.hits} hits / {self.misses} misses")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class Run:
    """Set-up, window and check of one cell under one seed.

    ``control="ref-int8"`` compares the comparison's control, the
    reference computed in int8 in the program's place; benchmark runs
    never do. ``engine_hook`` breaks the timed path underneath
    (``faults.py``)."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 control: str = "", engine_hook=None):
        self.cell, self.mix, self.seed = cell, cell.mix, int(seed)
        self.seconds, self.trace = float(seconds), bool(trace)
        self.control, self.engine_hook = control, engine_hook
        self.parts: dict = {}
        self.slices: list = []        # per slice boundary, see _slice
        self.reqs: dict = {}          # uid -> Req
        self.admit_t: dict = {}       # uid -> end of its admitting call
        self.done_t: dict = {}        # uid -> end of its retiring call
        self.responses: dict = {}     # uid -> Response
        self.grabbed: dict = {}       # uid -> device rows of its record
        self.blocks_of: dict = {}     # uid -> blocks decoded so far
        self.submit_late: list = []   # submit time - due time (open loop)

    # -- set-up -------------------------------------------------------------
    def setup(self, clock: CompileClock) -> None:
        import jax

        from repro.config.base import DecodeConfig, EngineConfig
        from repro.serving.engine import DiffusionEngine
        mix, cfg = self.mix, self.cell.cfg
        t = time.perf_counter()
        self.parts["init_s"] = t - T_PROCESS
        ref = check.load_reference(HERE, cfg["reference"])
        c0 = clock.seconds
        params = ref.draw_params(cfg, self.seed, MASK_ID)
        jax.block_until_ready(params)
        t1 = time.perf_counter()
        self.parts["weights_s"] = t1 - t
        self.parts["weights_compile_s"] = clock.seconds - c0
        self.mcfg = model_config(cfg)
        serve = cfg.get("serve", {})
        dcfg = DecodeConfig(max_new_tokens=mix.max_new_tokens,
                            block_size=mix.block_size, policy="osdt",
                            cache_layout="paged", page_size=mix.page_size,
                            attn_impl=serve.get("attn_impl", "kernel"),
                            step_fusion=serve.get("step_fusion", "fused"))
        self.doc = mix.shared_doc(self.seed)
        ecfg = EngineConfig(batch_size=mix.batch_size,
                            prompt_len=mix.prompt_len, cache_mode="prefix",
                            slice_len=1, eos_early_exit=False,
                            shared_prefix=self.doc)
        c0 = clock.seconds
        self.engine = DiffusionEngine(params, self.mcfg, dcfg, ecfg=ecfg)
        del params
        self.sched = self.engine.scheduler
        _sync()
        t2 = time.perf_counter()
        self.parts["shared_prefill_s"] = t2 - t1
        self.parts["shared_prefill_compile_s"] = clock.seconds - c0
        for task, tab in mix.tables().items():
            self.engine.store.tables[task] = tab
        if self.engine_hook is not None:
            self.engine_hook(self)
        self.gen = mix.requests(self.seed, stream=1)
        c0 = clock.seconds
        self._grab_fn = jax.jit(lambda r, c, v, s, rows: (
            r[rows], c[rows], v[rows], s[rows]))
        if mix.loop == "closed":
            self._warm_closed()
        else:
            self._warm_open()
        self._warm_grab()
        t3 = time.perf_counter()
        self.parts["warmup_s"] = t3 - t2
        self.parts["warmup_compile_s"] = clock.seconds - c0

    def _submit(self, req, at=None):
        from repro.serving.engine import Request
        self.reqs[req.uid] = req
        self.sched.submit([Request(req.uid, req.task, req.prompt)], at=at)

    def _in_system(self) -> int:
        return len(self.sched.queue) + sum(
            s.state == "active" for s in self.sched.slots)

    def _warm_closed(self) -> None:
        """Admit ``batch / blocks`` requests at each of ``blocks``
        boundaries, so that rows retire and are refilled a few at every
        boundary (the steady state of a stream), until every slot is
        busy; then fill the queue to the mix's outstanding count."""
        mix = self.mix
        per = max(mix.batch_size // mix.num_blocks, 1)
        while not self.slices or len(self.slices[-1]["rows"]) \
                < mix.batch_size:
            for _ in range(per):
                self._submit(next(self.gen))
            self._slice()
        while self._in_system() < mix.outstanding:
            self._submit(next(self.gen))

    def _warm_open(self) -> None:
        """Compile every admission size an open loop meets, into a fresh
        carry (after an idle spell) and into a running one: bursts of the
        batch, 1, 2, 4, ... requests, each drained to idle, then 1, 2, 4,
        ... at consecutive boundaries until a slice decodes every slot."""
        B = self.mix.batch_size
        for n in [B] + [1 << i for i in range((B - 1).bit_length())]:
            for _ in range(n):
                self._submit(next(self.gen))
            while self._in_system():
                self._slice()
        n, first = 1, len(self.slices)
        while len(self.slices) == first or \
                len(self.slices[-1]["rows"]) < B:
            for _ in range(min(n, B - self._in_system())):
                self._submit(next(self.gen))
            self._slice()
            n = min(2 * n, B // 2)

    def _warm_grab(self) -> None:
        carry = self.sched.carry
        if carry is None:
            return
        k = 1
        while k <= self.mix.batch_size:
            self._grab_rows(carry, [0] * k)
            k *= 2

    # -- one slice boundary ------------------------------------------------
    def _grab_rows(self, carry, rows):
        import jax.numpy as jnp
        n = 1 << (len(rows) - 1).bit_length()
        idx = np.zeros((n,), np.int32)
        idx[:len(rows)] = rows
        return self._grab_fn(carry.resp, carry.conf, carry.conf_valid,
                             carry.seq_steps, jnp.asarray(idx))

    def _slice(self) -> list:
        """One ``slice_step``, with the rows it decoded recorded."""
        import jax
        sched = self.sched
        before = {s.index: s.rs.req.uid for s in sched.slots
                  if s.state == "active"}
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("slice_step"):
            out = sched.slice_step()
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("responses"):
            after = {s.rs.req.uid for s in sched.slots if s.state == "active"}
            done = [r.uid for r in out]
            admitted = [u for u in after if u not in before.values()]
            if any(u not in before.values() for u in done):
                raise RuntimeError("a request finished in the boundary "
                                   "that admitted it")
            rows = []
            for u in sorted(after | set(done)):
                b = self.blocks_of.get(u, 0)
                rows.append((u, b))
                self.blocks_of[u] = b + 1
            for u in admitted:
                self.admit_t[u] = t1
            slot_of = {u: i for i, u in before.items()}
            if done and sched.carry is not None:
                got = self._grab_rows(sched.carry,
                                      [slot_of[u] for u in done])
                for i, u in enumerate(done):
                    self.grabbed[u] = (got, i)
            for r in out:
                self.responses[r.uid] = r
                self.done_t[r.uid] = t1
            self.slices.append({"t0": t0, "t1": t1, "rows": rows,
                                "admitted": admitted, "done": done})
        return out

    # -- the window ----------------------------------------------------------
    def window(self, clock: CompileClock) -> None:
        import jax
        self.compiles0 = clock.compiles
        self.gen = self.mix.requests(self.seed)
        self.stats0 = self.sched.stats.as_dict()
        self.n_slice0 = len(self.slices)
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
        self.t0 = time.perf_counter()
        self.parts["setup_s"] = self.t0 - T_PROCESS
        if self.mix.loop == "closed":
            self._window_closed()
        else:
            self._window_open()
        self.compiles_in_window = clock.compiles - self.compiles0

    def _stop_trace(self) -> None:
        import jax
        if self.trace and not getattr(self, "_traced", False):
            self.t_trace_end = time.perf_counter()
            jax.profiler.stop_trace()
            self._traced = True

    def _window_closed(self) -> None:
        end = self.t0 + self.seconds
        while True:
            with jax_annotation("generator"):
                while self._in_system() < self.mix.outstanding:
                    self._submit(next(self.gen))
            self._slice()
            if self.slices[-1]["t1"] >= end:
                break
        self.t1 = self.slices[-1]["t1"]
        self.stats1 = self.sched.stats.as_dict()
        self._stop_trace()

    def _window_open(self) -> None:
        mix, t0 = self.mix, self.t0
        end = t0 + self.seconds
        pending = None
        self.measured = []
        while True:
            now = time.perf_counter()
            with jax_annotation("generator"):
                while True:
                    if pending is None:
                        pending = next(self.gen)
                    due = t0 + pending.due
                    if due > now:
                        break
                    if due < end:
                        self.measured.append(pending.uid)
                    self.submit_late.append(now - due)
                    self._submit(pending, at=due)
                    pending = None
            if now >= end and not hasattr(self, "t1"):
                self.t1 = now
                self.stats1 = self.sched.stats.as_dict()
                self._stop_trace()
            if now >= end and all(u in self.responses for u in self.measured):
                break
            if now >= end + DRAIN_S:
                break
            if self._in_system():
                self._slice()
            else:
                with jax_annotation("idle_wait"):
                    time.sleep(max(min(t0 + pending.due, end + DRAIN_S)
                                   - time.perf_counter(), 0.0))
        if not hasattr(self, "t1"):
            self.t1 = time.perf_counter()
            self.stats1 = self.sched.stats.as_dict()
            self._stop_trace()

    # -- after the window ----------------------------------------------------
    def memory_peak(self) -> int:
        import jax
        st = jax.devices()[0].memory_stats() or {}
        return int(st.get("peak_bytes_in_use", 0))

    def release(self) -> dict:
        """Pull the sampled records to the host and free the engine."""
        host = {}
        pulled = {}
        for u, (got, i) in self.grabbed.items():
            if id(got) not in host:
                host[id(got)] = tuple(np.asarray(a) for a in got)
            pulled[u] = tuple(a[i] for a in host[id(got)])
        del self.grabbed
        self.engine = self.sched = None
        gc.collect()
        return pulled


def _sync() -> None:
    """Wait for the device: a later computation on the one device stream
    completes after every earlier one."""
    import jax.numpy as jnp
    (jnp.zeros(()) + 1).block_until_ready()


class jax_annotation:
    """A host span on the profiler's clock."""

    def __init__(self, name: str):
        import jax
        self._a = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._a.__enter__()

    def __exit__(self, *exc):
        self._a.__exit__(*exc)


def pct(values, q: float) -> float:
    """The ``q``-th percentile (0-100) of ``values``, linear between
    order statistics."""
    return float(np.percentile(np.asarray(values, float), q))


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------

def end_to_end(run: Run) -> dict:
    mix = run.mix
    out = {"setup_s": (run.parts["setup_s"], "s")}
    if mix.loop == "closed":
        blocks = sum(len(s["rows"]) for s in run.slices[run.n_slice0:])
        out["tokens_per_s"] = (blocks * mix.block_size / (run.t1 - run.t0),
                               "tokens/s")
        log(f"window: {run.t1 - run.t0:.3f} s, "
            f"{len(run.slices) - run.n_slice0} slices, {blocks} row-blocks "
            f"({blocks * mix.block_size} tokens)")
    else:
        done = [u for u in run.measured if u in run.responses]
        ttfb = [(run.admit_t[u] - (run.t0 + run.reqs[u].due)) * 1e3
                for u in done]
        lat = [(run.done_t[u] - (run.t0 + run.reqs[u].due)) * 1e3
               for u in done]
        if done:
            out["ttfb_p90_ms"] = (pct(ttfb, 90), "ms")
            out["latency_p90_ms"] = (pct(lat, 90), "ms")
        late = run.submit_late or [0.0]
        log(f"window: {run.seconds:.0f} s, {len(run.measured)} requests due, "
            f"{len(done)} answered; samples: ttfb_p90_ms n={len(ttfb)}, "
            f"latency_p90_ms n={len(lat)}; ttfb p50 "
            f"{pct(ttfb, 50) if ttfb else 0:.1f} ms, latency p50 "
            f"{pct(lat, 50) if lat else 0:.1f} ms; generator lateness p50 "
            f"{pct(late, 50) * 1e3:.2f} ms max {max(late) * 1e3:.2f} ms")
    return out


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(run: Run, red, pk: dict) -> dict:
    ctx = counts.Context(run, red, pk)
    out = {}
    for m in run.cell.per_layer:
        v = load_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = (v, m["unit"])
    for line in ctx.notes:
        log(line)
    return out


def finished(run: Run, pulled: dict) -> list:
    """``(req, record)`` of every request the window finished."""
    if run.mix.loop == "closed":
        pool = [u for u in pulled if run.t0 <= run.done_t[u] <= run.t1]
    else:
        pool = [u for u in run.measured if u in pulled]
    out = []
    for u in sorted(pool):
        resp, conf, valid, steps = pulled[u]
        out.append((run.reqs[u], check.Record(
            resp=resp, conf=conf, valid=valid, steps=steps,
            served=tuple(run.responses[u].token_ids))))
    return out


def correctness(run: Run, pulled: dict) -> tuple:
    """Exact checks on every finished request; the reference's numbers on
    a sample of them drawn from the seed."""
    mix, lim = run.mix, run.cell.limits
    done = finished(run, pulled)
    rng = np.random.default_rng([run.seed, 3])
    picked = [done[i] for i in sorted(rng.choice(
        len(done), size=min(int(lim["sample"]), len(done)),
        replace=False).tolist())] if done else []
    nums = {"step_mismatch": 0, "served_mismatch": 0}
    for req, rec in done:
        a, b = check.structural(mix, req, rec)
        nums["step_mismatch"] += a
        nums["served_mismatch"] += b
    if mix.loop == "open":
        nums["unanswered"] = sum(u not in run.responses for u in run.measured)
    else:
        nums["unanswered"] = 0
    t = time.perf_counter()
    if picked:
        ref = check.load_reference(HERE, run.cell.cfg["reference"])
        fn = check.control if run.control == "ref-int8" else check.compare
        nums.update(fn(ref, run.cell.cfg, run.seed, mix, run.doc, picked))
    else:
        nums.update({k: float("inf") for k in (
            "token_gap", "token_gap_mean", "conf_dlog", "conf_mse",
            "order_gap")}, tokens=0)
    log(f"reference: {len(picked)} sampled requests, {nums['tokens']} "
        f"served tokens compared in {time.perf_counter() - t:.1f} s; "
        "readings: " + ", ".join(f"{k} {v:.6g}" for k, v in nums.items()))
    ok, rows = check.verdict(nums, lim["limits"])
    return ok, rows, nums


def use_cache():
    """Keep JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache`` and cache every program, so that only a checkout's
    first run compiles. Returns the ``jax`` module."""
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no LRU eviction: its bookkeeping fails writes on some hosts
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = Cell.find(args.workload, bench)

    jax = use_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX finds no TPU (platform {devs[0].platform!r}); "
                      f"the benchmark never falls back to the CPU")
    if len(devs) < cell.chips:
        raise Refused(f"the cell asks for {cell.chips} chips, JAX sees "
                      f"{len(devs)}")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise Refused(f"the system under test (src/repro) is missing: {e}")
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     device=devs[0],
                     n_devices=len(devs))
    print(json.dumps(result))


def execute(cell: Cell, seed: int, seconds: float, trace: bool, *,
            control: str = "", device=None, n_devices: int = 1,
            engine_hook=None) -> dict:
    """One run of ``cell``: the contract's result object."""
    import jax
    device = device or jax.devices()[0]
    pk = peaks.for_kind(device.device_kind) if device.platform == "tpu" \
        else None
    log(f"device: {device.device_kind} x {n_devices} "
        f"(platform {device.platform}), jax {jax.__version__}")
    log(f"cell: {cell.name}; config {cell.cfg['name']} ({cell.cfg['num_layers']}"
        f" of {cell.cfg['published']['num_layers']} layers, d_model "
        f"{cell.cfg['d_model']}, {cell.cfg['num_heads']}x"
        f"{cell.cfg['head_dim']} heads, {cell.cfg['num_kv_heads']} kv heads, "
        f"d_ff {cell.cfg['d_ff']}, vocab {cell.cfg['vocab_size']})")
    log(f"traffic: {describe(cell.mix)}")
    clock = CompileClock()
    run = Run(cell, seed, seconds, trace, control=control,
              engine_hook=engine_hook)
    run.setup(clock)
    run.window(clock)
    p = run.parts
    log(f"setup: {p['setup_s']:.3f} s = process and JAX init "
        f"{p['init_s']:.3f} + weight draw {p['weights_s']:.3f} (compile "
        f"{p['weights_compile_s']:.2f}) + engine and "
        f"shared prefill {p['shared_prefill_s']:.3f} (compile "
        f"{p['shared_prefill_compile_s']:.2f}) + warm-up {p['warmup_s']:.3f} "
        f"(compile {p['warmup_compile_s']:.2f}) + the rest; {clock}")
    log(f"compiles inside the window: {run.compiles_in_window}")
    mem = run.memory_peak()
    red = None
    if trace:
        import devtrace
        try:
            red = devtrace.reduce_dir(TRACE_DIR)
        except (OSError, ValueError) as e:
            log(f"trace: no reduction ({e})")
    metrics = per_layer(run, red, pk) if trace else end_to_end(run)
    pulled = run.release()
    ok, rows, nums = correctness(run, pulled)
    if run.compiles_in_window:
        log(f"warning: {run.compiles_in_window} programs compiled inside "
            f"the window")
    checks = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    for k, v, lim in rows:
        log(f"check {k}: {v} (limit {lim})")
    if run.mix.loop == "open":
        attempted = len(run.measured)
        failed = nums["unanswered"]
    else:
        attempted = len({u for s in run.slices[run.n_slice0:]
                         for u, _ in s["rows"]})
        failed = 0
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": n_devices, "memory_peak_bytes": mem}
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": {k: {"value": v, "unit": u}
                          for k, (v, u) in metrics.items()},
              "device": dev}
    if red is not None:
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    result["readings"] = {k: v for k, v in nums.items() if k not in checks}
    result["checks"] = checks
    return result


if __name__ == "__main__":
    main()
