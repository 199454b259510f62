"""From a profiler trace to device busy time, kernel time and idle gaps.

The traced run wraps its window in a host span named ``WINDOW``; the
reduction keeps what lies inside it. On each device plane the ops are
the events of its ``XLA Ops`` line, named by their HLO instruction (an
event's name is the instruction's whole text: ``%fused_embed.1 = f32[...]
custom-call(...)`` becomes ``fused_embed.1``). A device is busy where at
least one op runs: the union of their intervals, never their sum. An
idle gap is named by the host span the harness had open at its middle
(``SPANS``, in that order of precedence), or by ``HOST_OTHER`` where
none was.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
SPANS = ("run_infer", "head", "submit")
HOST_OTHER = "lane host work outside run_infer and head"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


@dataclass
class Op:
    start_ns: float
    end_ns: float
    name: str                        # the HLO instruction, e.g. copy.1


@dataclass
class Trace:
    lo_ns: float                     # the window, on the trace's clock
    hi_ns: float
    devices: Dict[str, List[Op]] = field(default_factory=dict)
    spans: Dict[str, List[Interval]] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.hi_ns - self.lo_ns) * 1e-9


def union_ns(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    total, end = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


def gaps(intervals: Iterable[Interval], lo: float,
         hi: float) -> List[Interval]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end and end < hi:
            out.append((end, min(a, hi)))
        end = max(end, b)
    if end < hi:
        out.append((end, hi))
    return out


def device_busy_s(trace: Trace) -> List[float]:
    return [union_ns(((o.start_ns, o.end_ns) for o in ops), trace.lo_ns,
                     trace.hi_ns) * 1e-9 for ops in trace.devices.values()]


def op_name(text: str) -> str:
    """``%fused_embed.1 = f32[...] custom-call(...)`` -> ``fused_embed.1``."""
    head = text.split(" = ", 1)[0] if " = " in text else text
    return head.strip().lstrip("%")


def kernel_ops(trace: Trace, kernel: str) -> List[Op]:
    """The ops of one kernel: the instructions XLA named after it
    (``fused_embed``, ``fused_embed.1``, ...)."""
    return [o for ops in trace.devices.values() for o in ops
            if o.name == kernel or o.name.startswith(kernel + ".")]


def covered_share(ops: Sequence[Op], spans: Sequence[Interval]) -> float:
    """Share of the ops' time that lies inside the spans: near 1 for a
    kernel inside ``run_infer`` where the host and device clocks agree."""
    total = sum(o.end_ns - o.start_ns for o in ops)
    if not total:
        return 0.0
    inside = sum(union_ns(spans, o.start_ns, o.end_ns) for o in ops)
    return inside / total


def clipped_s(ops: Sequence[Op], lo: float, hi: float) -> float:
    return sum(max(0.0, min(o.end_ns, hi) - max(o.start_ns, lo))
               for o in ops) * 1e-9


def span_at(trace: Trace, t: float) -> str:
    for name in SPANS:
        for a, b in trace.spans.get(name, ()):
            if a <= t <= b:
                return name
    return HOST_OTHER


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time (summed over devices, by op
    name), and the longest idle gaps of the first device, each named by
    what the host was doing."""
    by_name: Dict[str, float] = {}
    for ops in trace.devices.values():
        for o in ops:
            d = max(0.0, min(o.end_ns, trace.hi_ns)
                    - max(o.start_ns, trace.lo_ns)) * 1e-9
            if d > 0:
                by_name[o.name] = by_name.get(o.name, 0.0) + d
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    idle: List[List] = []
    if trace.devices:
        first = trace.devices[sorted(trace.devices)[0]]
        g = gaps(((o.start_ns, o.end_ns) for o in first), trace.lo_ns,
                 trace.hi_ns)
        g.sort(key=lambda ab: ab[0] - ab[1])
        idle = [[span_at(trace, (a + b) / 2), (b - a) * 1e-9]
                for a, b in g[:top]]
    return {"device_ops": [[n, s] for n, s in device_ops],
            "idle_gaps": idle}


# -- reading a profiler trace ----------------------------------------------
def load(logdir: str, device_prefix: str = "/device:TPU:",
         devices: Optional[int] = None) -> Trace:
    """Read the ``.xplane.pb`` the profiler wrote under ``logdir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(sorted(paths)[-1])
    window: Optional[Interval] = None
    spans: Dict[str, List[Interval]] = {n: [] for n in SPANS}
    dev: Dict[str, List[Op]] = {}
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            suffix = plane.name[len(device_prefix):]
            if not suffix.isdigit():
                continue
            ops = dev.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(ev.start_ns, ev.start_ns + ev.duration_ns,
                                  op_name(ev.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == WINDOW:
                        window = iv
                    elif ev.name in spans:
                        spans[ev.name].append(iv)
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} span")
    if devices is not None:
        keep = sorted(dev, key=lambda n: int(n[len(device_prefix):]))
        dev = {n: dev[n] for n in keep[:devices]}
    return Trace(window[0], window[1], dev, spans)
