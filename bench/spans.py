"""Device-idle time put down to the program's own host phases.

The program times each phase of a slice boundary with a ``sched.*``
profiler span (``sched.boundary`` around the whole of
``Scheduler.slice_step``, and inside it ``sched.admit``, ``sched.seed``,
``sched.admit_prefill``, ``sched.plan``, ``sched.slice``, ``sched.wait``,
``sched.retire`` and its ``sched.pull``, ``sched.ingest``,
``sched.release``). They lie on the host plane of the same ``.xplane.pb``
that ``devtrace`` reduces, on the device's clock.

Over devtrace's window (first to last harness span), each stretch in
which no operation ran on a device is cut where spans start and end, and
each piece goes to the innermost ``sched.*`` span covering it; a piece no
such span covers goes to the harness span covering it
(``devtrace.HARNESS_SPANS``), else to ``other``. Idle time is averaged
over the devices, as devtrace's busy time is, so the parts sum to the
window's idle time.

A trace with no ``sched.*`` span (a program without them) gives ``None``.
"""
from __future__ import annotations

import functools
import statistics
from dataclasses import dataclass
from pathlib import Path

import devtrace

PREFIX = "sched."
BOUNDARY = "sched.boundary"
TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_trace"


@dataclass
class Attribution:
    window_s: float
    idle_s: float
    by_span: dict          # label -> idle seconds
    boundary_idle_s: list  # idle seconds inside each sched.boundary

    @property
    def sched_s(self) -> float:
        return sum(v for k, v in self.by_span.items()
                   if k.startswith(PREFIX))

    @property
    def sched_share(self) -> float:
        return self.sched_s / self.window_s if self.window_s else 0.0

    def note(self) -> str:
        parts = ", ".join(f"{k} {v:.4f}" for k, v in sorted(
            self.by_span.items(), key=lambda kv: -kv[1]))
        med = (1e3 * statistics.median(self.boundary_idle_s)
               if self.boundary_idle_s else 0.0)
        return (f"idle by span (s, of {self.idle_s:.4f} idle in a "
                f"{self.window_s:.4f} s window): {parts}; per "
                f"{BOUNDARY} median {med:.3f} ms idle "
                f"(n={len(self.boundary_idle_s)})")


def _pieces(spans, w0, w1):
    """[(a, b, label)] cutting [w0, w1] where any span starts or ends;
    each piece takes the innermost covering span, a ``sched.*`` span
    before any other, else ``other``. ``spans``: (start, end, name)."""
    pts = sorted({w0, w1} | {t for s, e, _ in spans for t in (s, e)
                             if w0 < t < w1})
    order = sorted(spans)
    active, i, out = [], 0, []
    for a, b in zip(pts, pts[1:]):
        while i < len(order) and order[i][0] <= a:
            active.append(order[i])
            i += 1
        active = [sp for sp in active if sp[1] > a]
        lab = "other"
        if active:
            best = max(active, key=lambda sp: (sp[2].startswith(PREFIX),
                                               sp[0], -sp[1]))
            lab = best[2]
        out.append((a, b, lab))
    return out


def _overlaps(gaps, pieces):
    """Yield (length, piece index) for every overlap of two sorted lists
    of disjoint intervals."""
    i = j = 0
    while i < len(gaps) and j < len(pieces):
        a = max(gaps[i][0], pieces[j][0])
        b = min(gaps[i][1], pieces[j][1])
        if b > a:
            yield b - a, j
        if gaps[i][1] <= pieces[j][1]:
            i += 1
        else:
            j += 1


def attribute(pd):
    """The attribution of a ``jax.profiler.ProfileData``, or ``None``
    when it holds no ``sched.*`` span."""
    harness, sched, devices = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in devtrace.HARNESS_SPANS:
                        harness.append((ev.start_ns, ev.end_ns, ev.name))
                    elif ev.name.startswith(PREFIX):
                        sched.append((ev.start_ns, ev.end_ns, ev.name))
        elif plane.name.startswith("/device:") and "TPU" in plane.name \
                and "Core" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines["XLA Ops"])
    if not sched or not harness or not devices:
        return None
    w0 = min(s for s, _, _ in harness)
    w1 = max(e for _, e, _ in harness)
    pieces = _pieces(sched + harness, w0, w1)
    bounds = sorted((s, e) for s, e, n in sched if n == BOUNDARY
                    and s >= w0 and e <= w1)
    by, per_bound, idle = {}, [0.0] * len(bounds), 0.0
    for line in devices:
        iv = [(max(ev.start_ns, w0), min(ev.end_ns, w1))
              for ev in line.events
              if min(ev.end_ns, w1) > max(ev.start_ns, w0)]
        merged = devtrace._union(iv)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        idle += sum(b - a for a, b in gaps)
        for d, j in _overlaps(gaps, pieces):
            lab = pieces[j][2]
            by[lab] = by.get(lab, 0.0) + d
        for d, j in _overlaps(gaps, bounds):
            per_bound[j] += d
    n = len(devices)
    return Attribution(
        window_s=(w1 - w0) * 1e-9, idle_s=idle * 1e-9 / n,
        by_span={k: v * 1e-9 / n for k, v in by.items()},
        boundary_idle_s=[v * 1e-9 / n for v in per_bound])


@functools.lru_cache(maxsize=1)
def _attribute_file(path: str):
    from jax.profiler import ProfileData
    return attribute(ProfileData.from_file(path))


def load(trace_dir=TRACE_DIR):
    """The attribution of the newest trace under ``trace_dir``, read
    once per process; ``None`` without a trace or ``sched.*`` spans."""
    try:
        return _attribute_file(devtrace.find_xplane(trace_dir))
    except FileNotFoundError:
        return None


def sched_idle_share(ctx):
    """The per-layer reading: device-idle time under ``sched.*`` spans
    as a share (%) of the traced window, with the attribution as a
    note; ``None`` without a trace reduction or ``sched.*`` spans."""
    if ctx.red is None:
        return None
    att = load()
    if att is None or att.window_s <= 0:
        return None
    ctx.notes.append(att.note())
    return 100.0 * att.sched_share
