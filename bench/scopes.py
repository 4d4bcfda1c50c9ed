"""From a JAX profiler trace to device time by named scope, host time by
program span, and the chip's idle time by the span the host was in.

Scopes. The program names the parts of its round with ``jax.named_scope``
(``local_train``, ``mix``, ``coeffs``, ``eval``, ``batch_gather``,
``analytics``; ``repro.core.decentralized``). XLA keeps the scope path in
each HLO instruction's ``op_name``, and the TPU profiler writes it into
the event metadata of each operation on a chip's ``XLA Ops`` line as the
stat ``tf_op``, e.g. ``jit(work)/while/body/closed_call/dot_general:``.
``jax.profiler.ProfileData`` gives events but not their metadata's
stats, so this module reads ``tf_op`` from the ``XSpace`` protobuf with a
reader of the few fields it needs (``XSpace.planes``, ``XPlane.name``,
``event_metadata``, ``stat_metadata``, ``XStat``), without TensorFlow,
and joins events to their metadata by event name. XLA gives a fusion
the ``op_name`` of one instruction it fused (its root, or the matrix
product it fused), so a whole fusion is owned by that instruction's
scope. Loop ops (``while``) carry no ``tf_op`` on the v5e, so a loop's
own time between its body's ops is ``unscoped``. A path component after
the first matches a scope by its name, with any transform around it
taken off (``vmap(local_train)``).

The scopes reach the trace only from an executable compiled from a
program that has them: JAX's persistent cache leaves op metadata out of
its key (``jax_compilation_cache_include_metadata_in_key`` is off), so
an executable that a checkout without the scopes put in a shared cache
is served to one with them, and its trace shows no scope; the readers
of scope time then find nothing to read.

Scope names are open: the sweep's own (``SCOPES``) and those that a
configuration's model file, ``bench/models/<model>.py``, declares with
``scopes(cfg)`` (``declared``), such as the expert layer of a model that
has one.

Self and inclusive time. Operations nest on the line (a ``while`` covers
its body's operations). At each instant of a chip's busy time the
innermost covering operation (the latest to start; of those, the first
to end) owns the time. The innermost scope of its path owns it as self
time, or ``unscoped``; every scope of its path owns it as inclusive time.
So the scopes' self times and ``unscoped`` sum to the busy time, and a
scope nested in another (a model's expert layer inside ``local_train``)
takes self time from it but leaves its inclusive time as it was. Both are
averaged over the chips as ``bench.tracefile`` averages busy time.

Spans. The program's host spans (``repro.*``, ``jax.profiler
.TraceAnnotation``) lie on the host's Python line beside the harness's
(``bench.*``): each span name's seconds inside the window, and the first
chip's idle seconds inside the window by the innermost program span
covering them (``outside`` where none does).
"""
from __future__ import annotations

import heapq
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench import spec, tracefile

#: the sweep's scope names (``repro.core.decentralized``)
SCOPES = ("local_train", "mix", "coeffs", "eval", "batch_gather",
          "analytics")
SPAN_PREFIX = "repro."
UNSCOPED = "unscoped"
OUTSIDE = "outside"
TF_OP = "tf_op"

Labeled = Tuple[float, float, str]
_TRANSFORM = re.compile(r"^\w+\((.*)\)$")


# -- protobuf wire format ----------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message in ``buf[i:end]``: an int for
    a varint, ``(start, end)`` for a length-delimited field, the raw bytes
    of a fixed-width one."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, val


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, entry) -> Optional[Tuple[int, int]]:
    """The value (field 2) of a protobuf map entry."""
    for f, v in _fields(buf, *entry):
        if f == 2:
            return v
    return None


def read_tf_ops(path: str) -> Dict[str, Dict[str, str]]:
    """device plane name -> {event metadata name: its ``tf_op``}."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:        # XSpace.planes
            continue
        name, events, stats = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:        # XPlane.name
                name = _str(buf, v)
            elif f == 4:      # XPlane.event_metadata (id -> XEventMetadata)
                events.append(_map_value(buf, v))
            elif f == 5:      # XPlane.stat_metadata (id -> XStatMetadata)
                meta = _map_value(buf, v)
                sid, sname = 0, ""
                for mf, mv in _fields(buf, *meta):
                    if mf == 1:
                        sid = mv
                    elif mf == 2:
                        sname = _str(buf, mv)
                stats[sid] = sname
        if not _is_device(name):
            continue
        tf_op = [k for k, v in stats.items() if v == TF_OP]
        ops: Dict[str, str] = {}
        for meta in events:
            ename, value = "", None
            for mf, mv in _fields(buf, *meta):
                if mf == 2:          # XEventMetadata.name
                    ename = _str(buf, mv)
                elif mf == 5:        # XEventMetadata.stats
                    value = _tf_op_value(buf, mv, tf_op, stats) or value
            if value is not None:
                ops[ename] = value
        out[name] = ops
    return out


def _tf_op_value(buf: bytes, stat, tf_op: List[int],
                 names: Dict[int, str]) -> Optional[str]:
    """The string of an ``XStat`` whose metadata is ``tf_op``: its
    ``str_value``, or the name its ``ref_value`` points to."""
    sid, value = None, None
    for f, v in _fields(buf, *stat):
        if f == 1:
            sid = v
        elif f == 5:
            value = _str(buf, v)
        elif f == 7:
            value = names.get(v)
    return value if sid in tf_op else None


def _is_device(name: str) -> bool:
    pre = tracefile.DEVICE_PREFIX
    return name.startswith(pre) and name[len(pre):].isdigit()


# -- self time ---------------------------------------------------------

def innermost(events: Sequence[Labeled], lo: float, hi: float
              ) -> List[Labeled]:
    """The covered parts of [lo, hi] as disjoint segments in time order,
    each labelled by its innermost covering event: of those covering it,
    the latest to start, and of those the first to end."""
    evs = sorted((max(a, lo), min(b, hi), label) for a, b, label in events
                 if min(b, hi) > max(a, lo))
    bounds = sorted({t for a, b, _ in evs for t in (a, b)})
    out: List[Labeled] = []
    heap: List[Tuple[float, float, int, str]] = []
    k = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while k < len(evs) and evs[k][0] <= t0:
            a, b, label = evs[k]
            heapq.heappush(heap, (-a, b, k, label))
            k += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        if heap:
            label = heap[0][3]
            if out and out[-1][1] == t0 and out[-1][2] == label:
                out[-1] = (out[-1][0], t1, label)
            else:
                out.append((t0, t1, label))
    return out


def declared(cfg: dict) -> Tuple[str, ...]:
    """The scope names of a configuration's program: ``SCOPES`` and those
    its model file declares with ``scopes(cfg)``."""
    model = spec.model(cfg)
    extra = tuple(model.scopes(cfg)) if hasattr(model, "scopes") else ()
    return SCOPES + tuple(s for s in extra if s not in SCOPES)


def named_scope(part: str, scopes: Sequence[str] = SCOPES
                ) -> Optional[str]:
    """The scope one path component names, with any transform around it
    taken off (``vmap(local_train)``), or None."""
    while True:
        m = _TRANSFORM.match(part)
        if m is None:
            return part if part in scopes else None
        part = m.group(1)


def scopes_of(path: str, scopes: Sequence[str] = SCOPES) -> List[str]:
    """The scopes named in an op path, outermost first. The first
    component names the program (``jit(f)``), or alone an argument (a
    copy of argument ``coeffs`` has the path ``coeffs``): never a scope."""
    found = (named_scope(p, scopes) for p in path.rstrip(":").split("/")[1:])
    return [s for s in found if s is not None]


def scope_of(path: str, scopes: Sequence[str] = SCOPES) -> str:
    """The innermost scope named in an op path, or ``unscoped``."""
    found = scopes_of(path, scopes)
    return found[-1] if found else UNSCOPED


def device_ops(path: str, data=None) -> Dict[str, List[Labeled]]:
    """device plane name -> [(start ns, end ns, ``tf_op``)] of its
    ``XLA Ops`` line (``""`` where an event's metadata has no ``tf_op``).
    ``data`` is the trace's ``jax.profiler.ProfileData`` where it is
    already read."""
    from jax.profiler import ProfileData

    tf_ops = read_tf_ops(path)
    out: Dict[str, List[Labeled]] = {}
    for plane in (data or ProfileData.from_file(path)).planes:
        if plane.name not in tf_ops:
            continue
        names = tf_ops[plane.name]
        out[plane.name] = [
            (e.start_ns, e.start_ns + e.duration_ns, names.get(e.name, ""))
            for line in plane.lines if line.name == tracefile.OPS_LINE
            for e in line.events]
    return out


def path_seconds(ops: Dict[str, List[Labeled]], window: tracefile.Interval
                 ) -> Dict[str, float]:
    """Busy seconds inside the window by the path of the innermost op,
    averaged over the chips."""
    if not ops:
        raise ValueError("the trace holds no device plane")
    out: Dict[str, float] = {}
    for events in ops.values():
        for a, b, path in innermost(events, *window):
            out[path] = out.get(path, 0.0) + (b - a) * 1e-9 / len(ops)
    return out


def self_seconds(paths: Dict[str, float], scopes: Sequence[str] = SCOPES
                 ) -> Dict[str, float]:
    """Self seconds of each scope and ``unscoped``, from ``path_seconds``;
    they sum to the busy seconds."""
    out: Dict[str, float] = {}
    for path, secs in paths.items():
        scope = scope_of(path, scopes)
        out[scope] = out.get(scope, 0.0) + secs
    return out


def inclusive_seconds(paths: Dict[str, float], scopes: Sequence[str] = SCOPES
                      ) -> Dict[str, float]:
    """Inclusive seconds of each scope: the busy seconds whose op path
    holds it, at any depth."""
    out: Dict[str, float] = {}
    for path, secs in paths.items():
        for scope in set(scopes_of(path, scopes)):
            out[scope] = out.get(scope, 0.0) + secs
    return out


def scope_seconds(ops: Dict[str, List[Labeled]], window: tracefile.Interval,
                  scopes: Sequence[str] = SCOPES) -> Dict[str, float]:
    """Self seconds of each scope and ``unscoped`` inside the window,
    averaged over the chips; they sum to the busy seconds."""
    return self_seconds(path_seconds(ops, window), scopes)


# -- host spans --------------------------------------------------------

def span_seconds(host: Sequence[Labeled], window: tracefile.Interval,
                 prefix: str = SPAN_PREFIX) -> Dict[str, float]:
    """Seconds of each host span whose name starts with ``prefix``, summed
    over its occurrences inside the window."""
    out: Dict[str, float] = {}
    for a, b, name in host:
        if name.startswith(prefix):
            for c, d in tracefile.clip([(a, b)], *window):
                out[name] = out.get(name, 0.0) + (d - c) * 1e-9
    return out


def idle_by_span(host: Sequence[Labeled], chip: Sequence[Labeled],
                 window: tracefile.Interval) -> Dict[str, float]:
    """One chip's idle seconds inside the window by the innermost program
    span covering them, or ``outside``."""
    lo, hi = window
    busy = tracefile.union(tracefile.clip([(a, b) for a, b, _ in chip],
                                          lo, hi))
    spans = [(a, b, n) for a, b, n in host if n.startswith(SPAN_PREFIX)]
    # the window as an outermost span: every instant has an owner
    owners = innermost(spans + [(lo, hi, OUTSIDE)], lo, hi)
    out: Dict[str, float] = {}
    i = 0
    for g0, g1 in tracefile.gaps(busy, lo, hi):
        while owners[i][1] <= g0:
            i += 1
        j = i
        while j < len(owners) and owners[j][0] < g1:
            a, b, name = owners[j]
            d = min(b, g1) - max(a, g0)
            if d > 0:
                out[name] = out.get(name, 0.0) + d * 1e-9
            j += 1
    return out


def split(trace: tracefile.Trace, ops: Dict[str, List[Labeled]],
          window: tracefile.Interval, scopes: Sequence[str] = SCOPES) -> dict:
    """What a trace says of the program's scopes and spans inside the
    window: busy seconds, scope self and inclusive seconds (``ops`` from
    :func:`device_ops`, scope names ``scopes``), span seconds, the
    window's calls (the harness's ``bench.call.<i>`` spans) and the first
    chip's idle seconds by program span."""
    paths = path_seconds(ops, window)
    self_s = self_seconds(paths, scopes)
    lo, hi = window
    calls = sum(1 for a, b, n in trace.host
                if n.startswith("bench.call.") and lo <= a and b <= hi)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(self_s.values()),
        "scope_s": self_s,
        "inclusive_s": inclusive_seconds(paths, scopes),
        "span_s": span_seconds(trace.host, window),
        "calls": calls,
        "idle_by_span_s": idle_by_span(trace.host, ops[min(ops)], window),
    }


def share(ctx: dict, scope: str) -> Optional[float]:
    """A metric reader's share of the traced window's busy time that ops
    under ``scope`` (inclusive) took, in percent; None without a trace or
    where no op carries the scope."""
    if ctx["scopes"] is None or ctx["trace"]["busy_s"] <= 0:
        return None
    secs = ctx["scopes"]["inclusive"].get(scope)
    return None if not secs else 100.0 * secs / ctx["trace"]["busy_s"]
