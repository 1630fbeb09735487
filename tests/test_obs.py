"""Observability (SERVING.md "Observability").

The contracts enforced here:

* **Tracer** — bounded ring (oldest evicted, drop count surfaced),
  Perfetto ``trace_event`` export, and ``validate_trace`` actually
  rejecting unbalanced / mis-nested span trees.
* **Metrics** — typed counter/gauge/histogram registry with Prometheus
  text exposition and JSON snapshots; ``EngineStats`` is a live view
  over engine gauges.
* **Drift** — an exact same-traffic replay scores cosine ≈ 1 (drift
  ≈ 0, paper O2); a mismatched profile trips the staleness flag;
  fallback/margin accumulators aggregate what the carry recorded.
* **Off = free** — a tracing+drift engine delivers byte-identical
  text and identical token/NFE accounting to the default engine.
* **Trace integrity** — one balanced span tree per submitted request,
  across mid-generation admission AND the requeues of a failed slice or
  admission prefill.
* **Profiler spans** — every slice boundary's ``sched.*`` phases reach
  an active ``jax.profiler`` session, nested and in order, whether or
  not the ring is on; the ring, when on, holds the same spans.
"""
import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import DecodeConfig, EngineConfig
from repro.config.registry import get_config
from repro.core.calibrate import CalibrationProfile
from repro.core.decoder import make_generate_fn, result_profile
from repro.core.osdt import CalibrationStore
from repro.data import tokenizer as tok
from repro.obs.drift import DriftMonitor
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer, validate_trace
from repro.serving.scheduler import Request, Scheduler

pytestmark = pytest.mark.obs

DCFG = DecodeConfig(max_new_tokens=16, block_size=4, policy="osdt",
                    mode="block", metric="q1", cap=0.9, slack=0.1,
                    threshold=0.9, page_size=4)
PROMPT_LEN = 16


@pytest.fixture(scope="module")
def small_model():
    from repro.models.model import init_params
    cfg = get_config("llada-8b").reduced()
    return cfg, init_params(jax.random.key(0), cfg)


def _requests(task, n, base=0):
    return [Request(base + i, task, f"{task} question {i}?")
            for i in range(n)]


def _static_profile(cfg, params, task, store, base=0):
    gen = make_generate_fn(cfg, DCFG)
    ids = [tok.encode(r.prompt, bos=True)[-PROMPT_LEN:]
           for r in _requests(task, 4, base)]
    prompt = jnp.asarray(tok.batch_prompts(ids, PROMPT_LEN))
    return result_profile(gen(params, prompt, jnp.asarray(store.static),
                              jnp.asarray(tok.MASK_ID, jnp.int32)))


@pytest.fixture(scope="module")
def calibrated_store(small_model):
    cfg, params = small_model
    store = CalibrationStore(DCFG)
    for task in ("alpha", "beta"):
        store.ingest(task, _static_profile(cfg, params, task, store))
    return store


def _sched(cfg, params, store, **ecfg_kw):
    kw = dict(batch_size=2, prompt_len=PROMPT_LEN)
    kw.update(ecfg_kw)
    dcfg_kw = kw.pop("dcfg_kw", {})
    dcfg = dataclasses.replace(DCFG, **dcfg_kw) if dcfg_kw else DCFG
    fresh = CalibrationStore(dcfg)
    fresh.profiles.update(store.profiles)
    fresh.tables.update(store.tables)
    return Scheduler(params, cfg, dcfg, ecfg=EngineConfig(**kw),
                     store=fresh)


def _drain(s):
    out = []
    while s.queue or any(sl.state == "active" for sl in s.slots):
        out.extend(s.slice_step())
    return out


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_ring_drops_oldest():
    t = Tracer(capacity=4)
    for i in range(10):
        t.instant(f"e{i}")
    assert t.dropped == 6
    names = [e[1] for e in t.events()]
    assert names == ["e6", "e7", "e8", "e9"]   # oldest evicted first
    doc = t.export()
    assert doc["otherData"]["dropped"] == 6
    assert doc["displayTimeUnit"] == "ms"


def test_disabled_tracer_is_falsy_and_silent():
    t = Tracer(enabled=False)
    assert not t
    t.begin("a")
    t.end("a")
    with t.span("sched.x", rows=1) as sp:
        sp.set_metadata(pages=2)
    t.instant("x")
    t.abegin("r", 1)
    t.aend("r", 1)
    assert t.events() == [] and t.dropped == 0


def test_tracer_export_and_validate():
    t = Tracer()
    t.track(0, "engine")
    t.track(16, "slot 0")
    t.begin("batch", tid=0, rows_live=2)
    t.begin("prefill", tid=0)
    t.end("prefill", tid=0)
    t.end("batch", tid=0, nfe=7)
    t.abegin("request", 42, task="alpha")
    t.instant("calibrate", tid=0, task="alpha")
    t.counter("pages_in_use", 3)
    t.aend("request", 42)
    doc = t.export()
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} == {"engine", "slot 0"}
    counts = validate_trace(doc)
    assert counts == {"spans": 2, "async": 1, "instants": 1}
    json.dumps(doc)   # serializable as-is


@pytest.mark.parametrize("mutate", [
    lambda t: t.begin("open"),                       # unclosed span
    lambda t: t.end("never_opened"),                 # E without B
    lambda t: (t.begin("a"), t.end("b")),            # close mismatch
    lambda t: t.aend("request", 9),                  # e without b
    lambda t: t.abegin("request", 9),                # unclosed async
])
def test_validate_trace_rejects_imbalance(mutate):
    t = Tracer()
    mutate(t)
    with pytest.raises(AssertionError):
        validate_trace(t.export())


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_registry_prometheus_exposition():
    r = MetricsRegistry()
    r.counter("reqs", "served requests").inc(3)
    r.gauge("pool", "pages").set(7.5, layout="paged")
    h = r.histogram("wait", "queue wait", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = r.prometheus()
    assert "# HELP repro_reqs served requests" in text
    assert "# TYPE repro_reqs counter" in text
    assert "repro_reqs 3" in text
    assert 'repro_pool{layout="paged"} 7.5' in text
    assert 'repro_wait_bucket{le="0.1"} 1' in text
    assert 'repro_wait_bucket{le="1.0"} 2' in text
    assert 'repro_wait_bucket{le="+Inf"} 3' in text
    assert "repro_wait_count 3" in text
    snap = r.snapshot()
    assert snap["repro_reqs"]["values"]["_"] == 3.0
    assert snap["repro_reqs"]["kind"] == "counter"
    json.dumps(snap)


def test_registry_rejects_kind_and_sign_errors():
    r = MetricsRegistry()
    r.counter("x", "a counter")
    with pytest.raises(AssertionError):
        r.gauge("x", "now a gauge?")
    with pytest.raises(AssertionError):
        r.counter("x", "").inc(-1)


def test_engine_stats_is_registry_view(small_model, calibrated_store):
    cfg, params = small_model
    s = _sched(cfg, params, calibrated_store)
    s.submit(_requests("alpha", 2))
    s.run()
    st = s.stats
    assert st.requests == 2 and st.tokens > 0
    snap = s.obs.registry.snapshot()
    assert snap["repro_engine_requests"]["values"]["_"] == 2.0
    assert snap["repro_engine_tokens"]["values"]["_"] == float(st.tokens)
    assert "repro_engine_nfe" in s.obs.prometheus()


# ---------------------------------------------------------------------------
# drift
# ---------------------------------------------------------------------------

def test_drift_same_task_replay_is_zero(small_model, calibrated_store):
    """The acceptance demo: replaying the exact traffic the profile was
    recorded from scores cosine >= 0.99 (drift ~ 0) and never trips."""
    cfg, params = small_model
    mon = DriftMonitor(calibrated_store)
    replay = _static_profile(cfg, params, "alpha", calibrated_store)
    for _ in range(3):
        cos = mon.observe("alpha", replay)
        assert cos == pytest.approx(1.0, abs=1e-6)
    assert mon.cosine("alpha") >= 0.99
    assert mon.drift("alpha") <= 0.01
    assert not mon.stale("alpha")


def test_drift_mismatched_task_trips_stale(calibrated_store):
    ref = calibrated_store.profiles["alpha"]
    # a signature concentrated on the complementary (block, step) cells:
    # near-orthogonal to the stored one, as a stale/mis-routed task is
    conf = np.where(ref.conf > 0, 0.0, 1.0).astype(np.float32)
    rogue = CalibrationProfile(conf=conf, valid=np.ones_like(ref.valid),
                               steps=ref.steps)
    mon = DriftMonitor(calibrated_store, threshold=0.95, min_obs=2)
    assert mon.observe("alpha", rogue) is not None
    assert not mon.stale("alpha")          # min_obs not reached yet
    mon.observe("alpha", rogue)
    assert mon.stale("alpha")
    assert mon.cosine("alpha") < 0.95
    assert mon.snapshot()["alpha"]["stale"] is True


def test_drift_unscorable_rows_are_skipped(calibrated_store):
    ref = calibrated_store.profiles["alpha"]
    mon = DriftMonitor(calibrated_store)
    # unknown task: accumulates health counters, scores nothing
    assert mon.observe("nope", ref, seq_steps=np.asarray([4])) is None
    assert mon.cosine("nope") == 1.0 and not mon.stale("nope")
    # empty recording (EOS before anything was recorded)
    empty = CalibrationProfile(conf=np.zeros_like(ref.conf),
                               valid=np.zeros_like(ref.valid),
                               steps=np.zeros_like(ref.steps))
    assert mon.observe("alpha", empty) is None


def test_drift_fallback_and_margin_accumulate(calibrated_store):
    mon = DriftMonitor(calibrated_store)
    mon.observe("nope", calibrated_store.profiles["alpha"],
                thr_steps=np.asarray([3, 1]), seq_steps=np.asarray([4, 4]),
                margin_sum=np.asarray([0.5, 0.3]),
                margin_n=np.asarray([2, 2]))
    assert mon.fallback_frac("nope") == pytest.approx(1 - 4 / 8)
    assert mon.margin_mean("nope") == pytest.approx(0.2)


def test_engine_drift_telemetry_end_to_end(small_model, calibrated_store):
    """Live rows under the calibrated budget score against the
    support-projected stored profile: high cosine, no staleness, and the
    carry-drained counters land in the snapshot and Prometheus text."""
    cfg, params = small_model
    s = _sched(cfg, params, calibrated_store, drift_telemetry=True,
               drift_threshold=0.9)
    s.submit(_requests("alpha", 4))
    s.run()
    d = s.obs.drift
    assert d is not None
    td = d._t["alpha"]
    assert td.obs == 4 and td.steps > 0
    assert d.cosine("alpha") > 0.9
    assert not d.stale("alpha")
    snap = d.snapshot()["alpha"]
    assert 0.0 <= snap["fallback_frac"] <= 1.0
    text = s.obs.prometheus()
    assert 'repro_drift_cosine{task="alpha"}' in text
    assert 'repro_drift_stale{task="alpha"} 0' in text


# ---------------------------------------------------------------------------
# off = free (bit-identity)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("slice_len", [1, 2])
def test_obs_off_output_identity(small_model, calibrated_store, slice_len):
    """The default engine and a tracing+drift engine deliver identical
    text and identical token/NFE/step accounting — telemetry rides the
    carry but never feeds back into decoding."""
    cfg, params = small_model
    kw = dict(batch_size=2, slice_len=slice_len,
              dcfg_kw=dict(cache_layout="paged"))
    reqs = _requests("alpha", 2) + _requests("beta", 2, 10)
    off = _sched(cfg, params, calibrated_store, **kw)
    off.submit(list(reqs))
    ref = {r.uid: r for r in off.run()}
    on = _sched(cfg, params, calibrated_store, trace=True,
                drift_telemetry=True, **kw)
    on.submit(list(reqs))
    got = {r.uid: r for r in on.run()}
    assert got.keys() == ref.keys()
    for uid in ref:
        assert got[uid].text == ref[uid].text, uid
        assert got[uid].nfe == ref[uid].nfe
    for f in ("requests", "tokens", "nfe", "seq_steps", "slices",
              "mid_admits", "pages_freed", "prefill_nfe", "prefill_rows"):
        assert getattr(on.stats, f) == getattr(off.stats, f), f


# ---------------------------------------------------------------------------
# trace integrity (balanced span tree per request)
# ---------------------------------------------------------------------------

def _async_balance(tracer):
    """(cat,id,name) -> open-count over the surviving events."""
    bal = {}
    for ph, name, tid, ts, args, eid, cat in tracer.events():
        if ph == "b":
            bal[(cat, eid, name)] = bal.get((cat, eid, name), 0) + 1
        elif ph == "e":
            bal[(cat, eid, name)] = bal.get((cat, eid, name), 0) - 1
    return bal


def test_trace_covers_request_lifecycle(small_model, calibrated_store):
    cfg, params = small_model
    s = _sched(cfg, params, calibrated_store, trace=True,
               dcfg_kw=dict(cache_layout="paged"))
    s.submit(_requests("alpha", 1))
    s.slice_step()
    s.submit(_requests("beta", 1, 50))   # mid-generation admission
    _drain(s)
    assert s.stats.mid_admits == 1
    doc = s.obs.tracer.export()
    counts = validate_trace(doc)
    assert counts["spans"] > 0 and counts["async"] > 0
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"request", "queued", "serve", "sched.boundary", "sched.admit",
            "sched.admit_prefill", "sched.slice", "sched.wait",
            "sched.retire", "sched.pull", "sched.release"} <= names
    bal = _async_balance(s.obs.tracer)
    for uid in (0, 50):
        assert bal.get(("request", uid, "request"), 0) == 0, uid
        assert bal.get(("request", uid, "queued"), 0) == 0, uid
    # every serve span names the uid it served
    serves = [e for e in doc["traceEvents"]
              if e["ph"] == "B" and e["name"] == "serve"]
    assert {e["args"]["uid"] for e in serves} == {0, 50}
    assert any(e["args"].get("mid") for e in serves)


@pytest.mark.parametrize("phase,span", [("slice", "sched.slice"),
                                        ("admit", "sched.admit_prefill")])
def test_trace_balanced_across_failed_slice(small_model, calibrated_store,
                                            phase, span):
    """An injected failure of a boundary program — the slice, or the
    admission prefill — requeues its rows: their serve spans close
    (requeued=True), queued spans reopen, and the retried run still
    exports a balanced, schema-valid trace."""
    cfg, params = small_model
    s = _sched(cfg, params, calibrated_store, trace=True,
               dcfg_kw=dict(cache_layout="paged"))
    name = f"_{phase}_fn"
    real = getattr(s, name)
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError(f"injected {phase} failure")
        return real(*a, **kw)

    setattr(s, name, flaky)
    s.submit(_requests("alpha", 2))
    with pytest.raises(RuntimeError):
        s.slice_step()
    assert s.pending() == 2            # both rows back in the queue
    out = s.run()
    assert sorted(r.uid for r in out) == [0, 1]
    doc = s.obs.tracer.export()
    validate_trace(doc)
    bal = _async_balance(s.obs.tracer)
    for uid in (0, 1):
        assert bal.get(("request", uid, "request"), 0) == 0
        assert bal.get(("request", uid, "queued"), 0) == 0
    names = [e["name"] for e in doc["traceEvents"]]
    assert f"{phase}_failed" in names
    # the failed program's own span closes as an error
    assert any(e["name"] == span and e["ph"] == "E"
               and (e.get("args") or {}).get("error")
               for e in doc["traceEvents"])
    # the failed boundary's serve spans carry the requeue marker
    assert any(e["name"] == "serve" and e["ph"] == "E"
               and (e.get("args") or {}).get("requeued")
               for e in doc["traceEvents"])


def test_trace_save_roundtrip(tmp_path, small_model, calibrated_store):
    cfg, params = small_model
    s = _sched(cfg, params, calibrated_store, trace=True)
    s.submit(_requests("alpha", 1))
    s.run()
    path = tmp_path / "trace.json"
    s.obs.save_trace(path)
    doc = json.loads(path.read_text())
    validate_trace(doc)
    assert doc["otherData"]["dropped"] == 0


# ---------------------------------------------------------------------------
# profiler spans (the sched.* taxonomy on the device trace's clock)
# ---------------------------------------------------------------------------

# the benchmark harness's own span names (bench/devtrace.py): its traced
# window and idle-gap labels are built from them, so no program span may
# take one
HARNESS_SPANS = ("generator", "slice_step", "responses", "idle_wait")


def _profiled(tmp_path, s, reqs, later=()):
    """Drive ``s`` under a profiler session (``later`` is submitted after
    the first boundary, so it admits mid-generation); returns the
    responses and the host ``sched.*`` events as (start, end, name,
    args), sorted by start."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        s.submit(list(reqs))
        out = s.slice_step()
        s.submit(list(later))
        out += _drain(s)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    evs, names = [], set()
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                names.add(e.name)
                if e.name.startswith("sched."):
                    evs.append((e.start_ns, e.end_ns, e.name,
                                {k: v for k, v in e.stats}))
    evs.sort(key=lambda e: (e[0], -e[1]))
    return out, evs, names


def _within(evs, outer):
    s0, e0 = outer[0], outer[1]
    return [e for e in evs if e is not outer and s0 <= e[0] and e[1] <= e0]


def _ring_spans(tracer):
    """(name, count) of the ring's closed engine-track spans."""
    got = {}
    for ph, name, tid, ts, args, eid, cat in tracer.events():
        if ph == "E" and name.startswith("sched."):
            got[name] = got.get(name, 0) + 1
    return got


@pytest.mark.parametrize("ring", [False, True])
def test_profiler_records_boundary_phases(tmp_path, small_model,
                                          calibrated_store, ring):
    """Every ``sched.boundary`` holds its admission (when it admitted),
    the slice dispatch, the wait and the retirement, nested and in that
    order; one boundary per slice; the calibrating row's ingest shows;
    no program span takes a harness span's name. With the ring on it
    holds the same spans (and validates); with it off it stays empty
    while the profiler still records."""
    cfg, params = small_model
    s = _sched(cfg, params, calibrated_store, trace=ring,
               dcfg_kw=dict(cache_layout="paged"))
    out, evs, names = _profiled(
        tmp_path, s, _requests("alpha", 1) + _requests("gamma", 1, 20),
        later=_requests("beta", 1, 50))
    assert sorted(r.uid for r in out) == [0, 20, 50]
    assert not names & set(HARNESS_SPANS)
    bounds = [e for e in evs if e[2] == "sched.boundary"]
    assert len(bounds) == s.stats.slices == sum(
        e[2] == "sched.slice" for e in evs)
    admitted = retired = 0
    for b in bounds:
        inner = _within(evs, b)
        order = [e[2] for e in inner
                 if e[2] in ("sched.admit", "sched.slice", "sched.wait",
                             "sched.retire")]
        assert order == ["sched.admit", "sched.slice", "sched.wait",
                         "sched.retire"], order
        args = b[3]
        assert args["live"] >= 1 and args["slice"] == bounds.index(b)
        admitted += args["admitted"]
        retired += args["retired"]
        if args["admitted"]:
            assert "sched.admit_prefill" in [e[2] for e in inner]
        for r in (e for e in inner if e[2] == "sched.retire"):
            phases = [e[2] for e in _within(evs, r)]
            assert phases[0] == "sched.pull"
            assert ("sched.release" in phases) == (r[3]["rows"] > 0)
    assert admitted == retired == 3
    ingest = [e for e in evs if e[2] == "sched.ingest"]
    assert [e[3]["task"] for e in ingest] == ["gamma"]
    counts = {}
    for e in evs:
        counts[e[2]] = counts.get(e[2], 0) + 1
    if ring:
        validate_trace(s.obs.tracer.export())
        assert _ring_spans(s.obs.tracer) == counts
    else:
        assert s.obs.tracer.events() == []


def test_profiler_records_seed_and_plan(tmp_path, small_model,
                                        calibrated_store):
    """The radix cache's seeding and the drafter's planning get their
    own spans, with host-held counts as arguments."""
    cfg, params = small_model
    s = _sched(cfg, params, calibrated_store, prefix_cache=True,
               spec_decode=True, shared_prefix="a shared system prompt.",
               prompt_len=32, dcfg_kw=dict(cache_layout="paged"))
    _, evs, _ = _profiled(tmp_path, s, _requests("alpha", 2))
    seeds = [e for e in evs if e[2] == "sched.seed"]
    assert seeds and sum(e[3]["segments"] for e in seeds) >= 1
    plans = [e for e in evs if e[2] == "sched.plan"]
    assert plans and all(e[3]["draft_blocks"] >= 0 for e in plans)
    bounds = [e for e in evs if e[2] == "sched.boundary"]
    for e in seeds + plans:
        assert any(b[0] <= e[0] and e[1] <= b[1] for b in bounds)


@pytest.mark.parametrize("slice_len", [1, 2])
def test_response_block_times(small_model, calibrated_store, slice_len):
    """Sliced decode stamps every block: ``num_blocks`` times from
    submit, the first of them the ttfb, rising from one slice to the
    next; the blocks one slice completed share its time."""
    cfg, params = small_model
    s = _sched(cfg, params, calibrated_store, slice_len=slice_len,
               dcfg_kw=dict(cache_layout="paged"))
    s.submit(_requests("alpha", 2) + _requests("beta", 1, 10))
    out = s.run()
    assert len(out) == 3
    for r in out:
        assert r.ttfb_s > 0 and r.block_s[0] == r.ttfb_s
        assert len(r.block_s) == DCFG.num_blocks
        per_slice = r.block_s[::slice_len]
        assert all(b > a for a, b in zip(per_slice, per_slice[1:]))
        for i in range(0, DCFG.num_blocks, slice_len):
            assert set(r.block_s[i:i + slice_len]) == {r.block_s[i]}
        # the slices the row decoded in lie between its admission and
        # its last block
        assert r.block_s[-1] >= r.queue_s + r.decode_s
