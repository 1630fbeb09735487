"""Reduction of a profiler trace (XSpace) to the benchmark's device numbers.

On the chip the profiler writes one ``.xplane.pb``: a plane per device
(``/device:TPU:0``) whose ``XLA Ops`` line holds every operation the
device ran and whose ``XLA Modules`` line holds every program execution,
and a host plane (``/host:CPU``) whose threads hold the harness's
``TraceAnnotation`` spans, on the same clock.

The traced window runs from the start of the harness's first span to the
end of its last. Within it:
  busy_s      the union of the intervals in which an operation ran
              (a loop or call counts while its body runs), averaged
              over the devices used
  program_s   device time of each program, by a stable name taken from
              its jitted function (``slice_fn`` -> "slice", ``admit`` ->
              "admission", the rest by their own names)
  kernel_s    device time of each Pallas kernel, by the name the kernel
              gives its ``pallas_call`` (``paged_block_attention``,
              ``fused_step``, ...)
  top ops     the operations that took most device time: leaf events
              only (an event that holds others, such as a while loop,
              is a container, and its children count instead)
  idle gaps   the ten longest stretches with no operation on the
              device, each labelled by the harness span the host was in
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass

HARNESS_SPANS = ("generator", "slice_step", "responses", "idle_wait")
KERNELS = ("paged_block_attention", "fused_step", "quantized_fused_step",
           "quantized_matmul", "block_attention")
PROGRAMS = (("slice_fn", "slice"), ("admit", "admission"))


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def program_name(module: str) -> str:
    """A stable name for a program from its module name
    (``jit_slice_fn`` / ``jit_slice_fn(123)`` -> ``slice``)."""
    base = module.split("(")[0]
    if base.startswith("jit_"):
        base = base[4:]
    for key, name in PROGRAMS:
        if base == key or base.startswith(key + "."):
            return name
    return base


def op_name(name: str) -> str:
    """``%fusion.335 = bf16[8,32]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.335 bf16[8,32]``: the instruction and its result shape."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{")[0].split(" ")[0] if rest else ""
    return (head.lstrip("%") + " " + shape).strip()


def _leaves(events):
    """The events of one line that hold no other event."""
    evs = sorted(events, key=lambda ev: (ev.start_ns, -ev.end_ns))
    parent = [False] * len(evs)
    stack = []
    for i, ev in enumerate(evs):
        while stack and evs[stack[-1]].end_ns <= ev.start_ns:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(evs, parent) if not p]


def kernel_name(ev, st: dict):
    text = " ".join([ev.name] + [str(v) for v in st.values()
                                  if isinstance(v, str)])
    for k in KERNELS:
        if k in text:
            if k == "fused_step" and "quantized_fused_step" in text:
                return "quantized_fused_step"
            return k
    return None


def _union(iv):
    """Merged, sorted [(start, end)] of intervals."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    program_s: dict
    kernel_s: dict
    top_ops: list
    idle_gaps: list
    devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def reduce(pd) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData``."""
    spans = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HARNESS_SPANS:
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
        elif plane.name.startswith("/device:") and "TPU" in plane.name \
                and "Core" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
    if not spans:
        raise ValueError("no harness span in the trace")
    if not devices:
        raise ValueError("no device plane with an 'XLA Ops' line")
    w0 = min(s for s, _, _ in spans)
    w1 = max(e for _, e, _ in spans)
    window = (w1 - w0) * 1e-9
    busy_total = 0.0
    prog, kern, ops = {}, {}, {}
    gaps = []
    for lines in devices:
        events = list(lines["XLA Ops"].events)
        iv = [(max(ev.start_ns, w0), min(ev.end_ns, w1)) for ev in events
              if min(ev.end_ns, w1) > max(ev.start_ns, w0)]
        for ev in _leaves(events):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            d = (e - s) * 1e-9
            k = kernel_name(ev, _stats(ev))
            if k:
                kern[k] = kern.get(k, 0.0) + d
            name = op_name(ev.name)
            ops[name] = ops.get(name, 0.0) + d
        merged = _union(iv)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        for ev in (lines["XLA Modules"].events if "XLA Modules" in lines
                   else []):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e > s:
                p = program_name(ev.name)
                prog[p] = prog.get(p, 0.0) + (e - s) * 1e-9
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    spans.sort()
    labelled = [(_label(spans, a, b), (b - a) * 1e-9) for a, b in gaps[:10]]
    return Reduction(
        window_s=window, busy_s=busy_total / n,
        program_s={k: v / n for k, v in prog.items()},
        kernel_s={k: v / n for k, v in kern.items()},
        top_ops=sorted(((k, v / n) for k, v in ops.items()),
                       key=lambda x: -x[1]),
        idle_gaps=labelled, devices=n)


def _label(spans, a, b) -> str:
    """The harness span that overlaps the gap [a, b) most."""
    best, lab = 0, "other"
    for s, e, name in spans:
        if s >= b:
            break
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, lab = ov, name
    return lab


def find_xplane(trace_dir) -> str:
    files = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_dir(trace_dir):
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(trace_dir)))
