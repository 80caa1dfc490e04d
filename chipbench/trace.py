"""Reduction of a profiler trace to device busy time, launches and gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``. Every per-layer metric that comes from the
device trace is computed from the :class:`TraceSummary` made here, so
each run reduces a trace the same way.

- Busy time is the union of the intervals of the device's ``XLA Ops``
  events inside the window; idle is the rest of the window.
- A launch is one event on the device's ``XLA Modules`` line: one
  execution of one compiled program.
- Idle time is attributed, piece by piece, to the innermost benchmark
  span (host side) that covers it, or to ``outside_spans``.
- The window runs from the start of the first ``request`` span to the end
  of the last one.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "request"
NO_SPAN = "outside_spans"
TOP = 10  # entries kept in each breakdown list

Interval = Tuple[float, float]  # (start_ns, end_ns)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over the chips that ran anything
    launches: int  # program executions over all chips
    n_requests: int  # request spans in the window
    device_ops: List[Tuple[str, float]]  # (program, seconds), largest first
    idle_gaps: List[Tuple[str, float]]  # (host span, idle seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` under ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that merged ``busy`` does not cover."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(idle: Sequence[Interval],
              spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle ns per innermost (shortest) span covering each piece of it."""
    marks = [(s, 1, i) for i, (_, s, _e) in enumerate(spans)]
    marks += [(e, -1, i) for i, (_, _s, e) in enumerate(spans)]
    marks += [(s, 2, -1) for s, _ in idle] + [(e, -2, -1) for _, e in idle]
    marks.sort(key=lambda m: m[0])
    out: Dict[str, float] = {}
    active: set = set()
    in_idle, t = 0, None
    for when, kind, i in marks:
        if in_idle and t is not None and when > t:
            inner = min(active, key=lambda j: spans[j][2] - spans[j][1],
                        default=None)
            name = NO_SPAN if inner is None else spans[inner][0]
            out[name] = out.get(name, 0.0) + (when - t)
        t = when
        if kind == 1:
            active.add(i)
        elif kind == -1:
            active.discard(i)
        else:
            in_idle += kind // 2
    return out


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def read_planes(pd, span_names: Sequence[str]):
    """(per-chip {"ops": [...], "modules": [...]}, host spans)."""
    chips, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            chips.append({"ops": lines.get(OPS_LINE, []),
                          "modules": lines.get(MODULES_LINE, [])})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [ev for ev in _events(ln) if ev[0] in span_names]
    return chips, spans


def summarize(pd, span_names: Sequence[str]) -> TraceSummary:
    """Reduce one trace; ``span_names`` are the host spans that idle time
    is attributed to (``request`` among them, which sets the window)."""
    chips, spans = read_planes(pd, span_names)
    requests = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not requests:
        raise RuntimeError("trace holds no request span")
    lo = min(s for s, _ in requests)
    hi = max(e for _, e in requests)
    chips = [c for c in chips if clip([o[1:] for o in c["ops"]], lo, hi)]
    if not chips:
        raise RuntimeError("trace holds no device operation in the window")
    busy_ns, launches = 0.0, 0
    per_program: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for c in chips:
        busy = union(clip([o[1:] for o in c["ops"]], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for name, ns in attribute(gaps(busy, lo, hi), spans).items():
            idle[name] = idle.get(name, 0.0) + ns / len(chips)
        for name, s, e in c["modules"]:
            if lo <= s < hi:
                launches += 1
                per_program[name] = per_program.get(name, 0.0) + (e - s)
    top = lambda d: sorted(((k, v * 1e-9) for k, v in d.items()),
                           key=lambda kv: -kv[1])[:TOP]
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns * 1e-9 / len(chips),
        launches=launches,
        n_requests=len(requests),
        device_ops=top(per_program),
        idle_gaps=top(idle),
    )


def summarize_dir(log_dir: str, span_names: Sequence[str]) -> TraceSummary:
    from jax.profiler import ProfileData

    return summarize(ProfileData.from_file(find_xplane(log_dir)), span_names)
