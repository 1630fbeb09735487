"""Self-test of the trace reduction on a small recorded trace.

``trace_small.pbtxt`` is a cut of a ``--trace 1`` run on one TPU v5e
(``llada8b-longdoc-open``): the harness's host spans and the device's
``XLA Ops`` and ``XLA Modules`` lines over its first milliseconds, as an
XSpace text proto. The reduction's numbers are checked against a second,
independent reckoning over the same events.

  JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_trace.py
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import devtrace  # noqa: E402


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto((HERE / "trace_small.pbtxt").read_text())


def _events(pd, line):
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            for ln in plane.lines:
                if ln.name == line:
                    return [(e.start_ns, e.end_ns, e) for e in ln.events]
    return []


def _window(pd):
    spans = [(e.start_ns, e.end_ns) for plane in pd.planes
             if plane.name.startswith("/host:") for ln in plane.lines
             for e in ln.events if e.name in devtrace.HARNESS_SPANS]
    return min(s for s, _ in spans), max(e for _, e in spans)


def _busy(iv):
    """Covered length by a sweep over +1/-1 edges."""
    edges = sorted([(s, 1) for s, _ in iv] + [(e, -1) for _, e in iv])
    depth, last, total = 0, None, 0.0
    for t, d in edges:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_window_busy_and_idle(pd):
    red = devtrace.reduce(pd)
    w0, w1 = _window(pd)
    assert red.window_s == pytest.approx((w1 - w0) * 1e-9)
    iv = [(max(s, w0), min(e, w1)) for s, e, _ in _events(pd, "XLA Ops")
          if min(e, w1) > max(s, w0)]
    assert red.busy_s == pytest.approx(_busy(iv) * 1e-9, rel=1e-9)
    assert 0.0 < red.busy_s <= red.window_s
    assert 0 < len(red.idle_gaps) <= 10
    longest = [s for _, s in red.idle_gaps]
    assert longest == sorted(longest, reverse=True)
    assert sum(longest) <= red.window_s - red.busy_s + 1e-9
    assert all(lab in devtrace.HARNESS_SPANS + ("other",)
               for lab, _ in red.idle_gaps)


def _leaves(evs):
    """Events no other event lies within, by brute force."""
    return [(s, e, ev) for s, e, ev in evs
            if not any(ev2 is not ev and s <= s2 and e2 <= e
                       and (s2, e2) != (s, e) for s2, e2, ev2 in evs)]


def test_kernels_and_programs(pd):
    red = devtrace.reduce(pd)
    w0, w1 = _window(pd)
    want = {}
    for s, e, ev in _leaves(_events(pd, "XLA Ops")):
        st = dict(ev.stats)
        text = ev.name + " " + " ".join(str(v) for v in st.values())
        for k in ("paged_block_attention", "fused_step"):
            if k in text and min(e, w1) > max(s, w0):
                want[k] = want.get(k, 0.0) + (min(e, w1) - max(s, w0)) * 1e-9
    assert want, "the recorded cut holds kernel events"
    for k, v in want.items():
        assert red.kernel_s[k] == pytest.approx(v, rel=1e-9)
    mods = _events(pd, "XLA Modules")
    assert any("slice_fn" in ev.name for _, _, ev in mods)
    assert red.program_s["slice"] > 0.0
    total = sum(red.program_s.values())
    assert total <= red.window_s * (1 + 1e-9)


def test_breakdown_shape(pd):
    bd = devtrace.reduce(pd).breakdown()
    assert set(bd) == {"device_ops", "idle_gaps"}
    for key in bd:
        assert len(bd[key]) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in bd[key])
