"""From a JAX profiler trace to device busy time, idle gaps and top ops.

The trace is the ``.xplane.pb`` the profiler writes. Device planes are
``/device:TPU:<k>``; their ``XLA Ops`` line holds one event per operation
run on that chip. Host spans come from the harness's own
``TraceAnnotation`` spans (names starting with ``bench.``) and from what
the host's Python thread (the line holding those spans) recorded around
them. Device and host clocks are aligned by the profiler to within about
a millisecond, which is all the attribution of gaps needs.

* busy: the union of a chip's operation intervals inside the window;
* idle share: 1 - busy / window;
* idle gaps: the complement of the first chip's busy time inside the
  window, each named by the innermost harness span and the shortest other
  host event that cover its midpoint (what the host was doing while the
  chip waited).
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no interval of ``busy`` (merged)
    covers."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] ...`` -> ``fusion.3``: the HLO instruction
    name without its shape and operands."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


class Trace:
    """The events of one trace that the reduction needs, in nanoseconds."""

    def __init__(self, ops: Dict[str, List[Tuple[float, float, str]]],
                 host: List[Tuple[float, float, str]]):
        self.ops = ops      # device plane name -> [(start, end, op name)]
        self.host = host    # host Python thread -> [(start, end, name)]

    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        return cls.of(ProfileData.from_file(path))

    @classmethod
    def of(cls, data) -> "Trace":
        """The trace of a ``jax.profiler.ProfileData`` already read."""
        ops: Dict[str, List[Tuple[float, float, str]]] = {}
        host: List[Tuple[float, float, str]] = []
        for plane in data.planes:
            name = plane.name
            if name.startswith(DEVICE_PREFIX) and name[len(DEVICE_PREFIX):].isdigit():
                ops[name] = [(e.start_ns, e.start_ns + e.duration_ns,
                              op_name(e.name))
                             for line in plane.lines if line.name == OPS_LINE
                             for e in line.events]
            elif name == HOST_PLANE:
                # the Python thread: the line that holds the harness spans
                for line in plane.lines:
                    events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                              for e in line.events]
                    if any(n.startswith(SPAN_PREFIX) for _, _, n in events):
                        host.extend(events)
        return cls(ops, host)

    def span(self, name: str) -> Optional[Interval]:
        """The first host event of that name, or None."""
        for a, b, n in self.host:
            if n == name:
                return (a, b)
        return None

    def host_at(self, t: float) -> str:
        """What the host was doing at ``t``: the innermost harness span and
        the shortest other host event covering it."""
        covering = [(b - a, n) for a, b, n in self.host if a <= t < b]
        spans = sorted(c for c in covering if c[1].startswith(SPAN_PREFIX))
        other = sorted(c for c in covering if not c[1].startswith(SPAN_PREFIX))
        parts = ([spans[0][1]] if spans else []) + ([other[0][1]] if other else [])
        return " / ".join(parts) or "no host event"


def reduce(trace: Trace, window: Interval, top: int = 10) -> dict:
    """Busy and idle over ``window`` (ns), averaged over the device planes,
    with the top operations and the longest idle gaps."""
    lo, hi = window
    if not trace.ops:
        raise ValueError("the trace holds no device plane")
    busy_ns, by_op, first_gaps = [], {}, None
    for plane in sorted(trace.ops):
        events = trace.ops[plane]
        busy = union(clip([(a, b) for a, b, _ in events], lo, hi))
        busy_ns.append(sum(b - a for a, b in busy))
        for a, b, name in events:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                by_op[name] = by_op.get(name, 0.0) + d
        if first_gaps is None:   # the gaps of the first chip
            first_gaps = gaps(busy, lo, hi)
    n = len(trace.ops)
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_ns) / n * 1e-9
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(first_gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": [[name, ns / n * 1e-9] for name, ns in ops],
        "idle_gaps": [[trace.host_at((a + b) / 2), (b - a) * 1e-9]
                      for a, b in longest],
        "n_devices": n,
    }
