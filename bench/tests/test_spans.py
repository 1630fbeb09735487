"""Self-test of the idle attribution by ``sched.*`` span (``bench/spans.py``)
on a small hand-written trace, against a reckoning by hand.

  JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_spans.py
"""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import spans  # noqa: E402

US = 1_000_000   # picoseconds in a microsecond

# (name, start us, end us) on the host; the harness's slice_step and
# generator spans set the window [0, 110] us
HOST = [("slice_step", 0, 100),
        ("sched.boundary", 5, 95),
        ("sched.admit", 5, 20),
        ("sched.seed", 8, 14),
        ("sched.slice", 20, 25),
        ("sched.wait", 25, 60),
        ("sched.retire", 60, 90),
        ("sched.pull", 60, 70),
        ("generator", 104, 110)]
# device operations; the last lies past the window and does not count
OPS = [(0, 10), (12, 13), (22, 55), (58, 62), (100, 103), (120, 130)]

# idle stretches in the window and where each piece of them goes:
#   [10, 12]  sched.seed 2
#   [13, 22]  sched.seed 1, sched.admit 6, sched.slice 2 (straddles three)
#   [55, 58]  sched.wait 3
#   [62, 100] sched.pull 8, sched.retire 20, sched.boundary 5, slice_step 5
#   [103, 104] other 1 (no span), [104, 110] generator 6
WANT = {"sched.seed": 3, "sched.admit": 6, "sched.slice": 2,
        "sched.wait": 3, "sched.pull": 8, "sched.retire": 20,
        "sched.boundary": 5, "slice_step": 5, "other": 1, "generator": 6}


def _plane(pid, name, line, events, extra=""):
    names = sorted({n for n, _, _ in events})
    meta = {n: i + 1 for i, n in enumerate(names)}
    evs = "\n".join(
        f"    events {{ metadata_id: {meta[n]} offset_ps: {s * US} "
        f"duration_ps: {(e - s) * US} }}" for n, s, e in events)
    md = "\n".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}' for n, i in meta.items())
    return (f'planes {{\n  id: {pid}\n  name: "{name}"\n  lines {{\n'
            f'    id: 1\n    name: "{line}"\n    timestamp_ns: 1000\n'
            f'{evs}\n  }}\n{extra}{md}\n}}\n')


def _pd(host=HOST):
    from jax.profiler import ProfileData
    ops = [(f"%fusion.{i} = bf16[8,32]{{1,0}} fusion()", s, e)
           for i, (s, e) in enumerate(OPS)]
    text = (_plane(1, "/device:TPU:0", "XLA Ops", ops)
            + _plane(2, "/host:CPU", "python3", host))
    return ProfileData.from_text_proto(text)


def test_attribution_by_hand():
    att = spans.attribute(_pd())
    assert att.window_s == pytest.approx(110e-6)
    assert att.idle_s == pytest.approx(sum(WANT.values()) * 1e-6)
    assert set(att.by_span) == set(WANT)
    for k, v in WANT.items():
        assert att.by_span[k] == pytest.approx(v * 1e-6), k
    sched = sum(v for k, v in WANT.items() if k.startswith("sched."))
    assert att.sched_s == pytest.approx(sched * 1e-6)
    assert att.sched_share == pytest.approx(sched / 110)
    # the one boundary holds every sched.* piece of the idle time
    assert att.boundary_idle_s == [pytest.approx(sched * 1e-6)]
    note = att.note()
    assert "sched.retire 0.0000" in note and "(n=1)" in note


def test_parts_sum_to_idle_share_of_devtrace():
    import devtrace
    pd = _pd()
    att = spans.attribute(pd)
    red = devtrace.reduce(pd)
    assert att.window_s == pytest.approx(red.window_s)
    assert sum(att.by_span.values()) == pytest.approx(
        red.window_s - red.busy_s)
    assert att.sched_share <= red.idle_share


def test_no_sched_span_gives_none():
    host = [h for h in HOST if not h[0].startswith("sched.")]
    assert spans.attribute(_pd(host)) is None
    from jax.profiler import ProfileData
    recorded = ProfileData.from_text_proto(
        (HERE / "trace_small.pbtxt").read_text())
    assert spans.attribute(recorded) is None


def test_reader_without_trace_gives_none():
    ctx = SimpleNamespace(red=None, notes=[])
    assert spans.sched_idle_share(ctx) is None
    assert ctx.notes == []
