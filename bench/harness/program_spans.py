"""A profiler trace read with the program's own spans beside the harness's.

``trace.load`` keeps the harness's spans (``trace.SPANS``). The program
opens its own at the served path's layer boundaries
(``repro.pipeline.spans.SPAN_NAMES``: ``lane.*``, ``share.*``,
``backend.*``, ``engine.*``); ``load`` here adds those to the same
``Trace``, where the program has them.

A moment of the window is named by the innermost span open then, the
one that opened last: a lane-side span first (``lane.*``, ``share.*``,
``backend.*``, the harness's ``run_infer`` and ``head``), since the
device waits on the lane worker while clients submit; else a front-side
one (``engine.*``, the harness's ``submit``); else ``trace.HOST_OTHER``.
With the harness's spans alone that is ``trace.span_at``'s naming. An
idle gap is named by the moment at its middle (``idle_gaps``);
``idle_by_span`` splits the first device's idle time over the names of
every moment.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

from harness import trace as tr

try:
    from repro.pipeline.spans import SPAN_NAMES as PROGRAM_SPANS
except ImportError:                  # a program without in-program spans
    PROGRAM_SPANS: Tuple[str, ...] = ()

LANE_PREFIXES = ("lane.", "share.", "backend.")


def load(logdir: str, device_prefix: str = "/device:TPU:",
         devices: Optional[int] = None) -> tr.Trace:
    """``trace.load``, with the program's spans added to ``spans``."""
    from jax.profiler import ProfileData
    trace = tr.load(logdir, device_prefix, devices)
    for name in PROGRAM_SPANS:
        trace.spans.setdefault(name, [])
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(sorted(paths)[-1])
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in PROGRAM_SPANS:
                    trace.spans[ev.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return trace


def _lane_side(name: str) -> bool:
    return name in ("run_infer", "head") or name.startswith(LANE_PREFIXES)


def _innermost(open_spans: Iterable[Tuple[float, float, str]]) -> str:
    """The name for a moment at which ``open_spans`` (start, end, name)
    are open: the innermost lane-side span, else the innermost
    front-side one, else ``HOST_OTHER``. Innermost is the latest start;
    a tie goes to the earlier end, then to ``trace.SPANS``' order."""
    best: Dict[bool, Tuple] = {}
    for a, b, name in open_spans:
        side = _lane_side(name)
        key = (a, -b, -tr.SPANS.index(name) if name in tr.SPANS else 0)
        if side not in best or key > best[side][0]:
            best[side] = (key, name)
    for side in (True, False):
        if side in best:
            return best[side][1]
    return tr.HOST_OTHER


def span_at(trace: tr.Trace, t: float) -> str:
    return _innermost((a, b, name) for name, ivs in trace.spans.items()
                      for a, b in ivs if a <= t <= b)


def timeline(trace: tr.Trace) -> List[Tuple[float, float, str]]:
    """The window cut at every span boundary, each stretch named as
    ``span_at`` names a moment inside it."""
    lo, hi = trace.lo_ns, trace.hi_ns
    ivs = sorted((max(a, lo), min(b, hi), name)
                 for name, spans in trace.spans.items()
                 for a, b in spans if b > lo and a < hi)
    cuts = sorted({lo, hi, *(a for a, _, _ in ivs), *(b for _, b, _ in ivs)})
    out: List[Tuple[float, float, str]] = []
    active: List[Tuple[float, float, str]] = []
    i = 0
    for t0, t1 in zip(cuts[:-1], cuts[1:]):
        while i < len(ivs) and ivs[i][0] <= t0:
            active.append(ivs[i])
            i += 1
        active = [s for s in active if s[1] >= t1]
        name = _innermost(active)
        if out and out[-1][2] == name and out[-1][1] == t0:
            out[-1] = (out[-1][0], t1, name)
        else:
            out.append((t0, t1, name))
    return out


def _first_device_gaps(trace: tr.Trace) -> List[tr.Interval]:
    first = trace.devices[sorted(trace.devices)[0]]
    return tr.gaps(((o.start_ns, o.end_ns) for o in first), trace.lo_ns,
                   trace.hi_ns)


def idle_gaps(trace: tr.Trace, top: int = 10) -> List[List]:
    """The first device's ``top`` longest idle gaps, longest first, each
    as [name of the moment at its middle, seconds]: ``breakdown``'s
    ``idle_gaps`` under this module's naming."""
    if not trace.devices:
        return []
    g = sorted(_first_device_gaps(trace), key=lambda ab: ab[0] - ab[1])
    return [[span_at(trace, (a + b) / 2), (b - a) * 1e-9]
            for a, b in g[:top]]


def idle_by_span(trace: tr.Trace) -> Dict[str, float]:
    """The first device's idle seconds in the window, split over the
    name of each moment (``timeline``), largest first."""
    if not trace.devices:
        return {}
    stretches = timeline(trace)
    out: Dict[str, float] = {}
    j = 0
    for a, b in _first_device_gaps(trace):
        while j < len(stretches) and stretches[j][1] <= a:
            j += 1
        k = j
        while k < len(stretches) and stretches[k][0] < b:
            s0, s1, name = stretches[k]
            d = min(b, s1) - max(a, s0)
            if d > 0:
                out[name] = out.get(name, 0.0) + d * 1e-9
            k += 1
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
