"""Reduction of a JAX profiler trace by the program's own names.

The served path names its work twice on the profiler's clock:

- device scopes: the program runs each sublayer of the paged forward
  and the decode horizon under a ``jax.named_scope``, whose names the
  configuration's family module lists (``SCOPES`` in
  ``bench/reference/<family>.py``). The scope is a component of the
  op's ``op_name`` path, which a TPU trace keeps in the stat
  ``OP_PATH_STAT`` of each ``XLA Ops`` event's metadata (read by hand on
  a v5e trace). Each op gets the first of the scope names in its path,
  or ``unscoped`` (ops XLA adds, such as copies of a loop's carried
  state, have no path of the program's);
- host spans: ``jax.profiler.TraceAnnotation`` spans of the bench
  (``bench.*``, ``harness/drive.py``), the loop (``loop.*``,
  ``serve/loop.py``) and the engine (``engine.*``, ``serve/engine.py``),
  nested on the one serving thread.

``reduce`` gives the device seconds of each scope in each jitted step,
the device's idle gaps labelled by the innermost host span that covers
most of each, and the host's own time (``loop.step`` less the
``engine.sync`` waits inside it). The traced window is the one
``harness/trace.py`` finds: from the end of ``start_trace`` to the
start of ``stop_trace``.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from harness import trace

UNSCOPED = "unscoped"
OP_PATH_STAT = "tf_op"
SPAN_PREFIXES = ("bench.", "loop.", "engine.")

Span = Tuple[str, float, float]


def scope_of(path: Optional[str], names: Sequence[str]) -> str:
    """``jit(_decode_h)/while/body/kv_write/scatter`` -> ``kv_write``
    where ``kv_write`` is one of the scope ``names``."""
    for part in (path or "").split("/"):
        if part in names:
            return part
    return UNSCOPED


def by_scope(devices: Sequence[dict],
             names: Sequence[str]) -> Dict[str, Dict[str, float]]:
    """{step kind: {scope: device seconds}} per chip, from ``devices``:
    one dict per chip, {"modules": [(name, start, end)], "ops":
    [(name, start, end, op_name path)]}. An op belongs to the jitted
    step whose execution holds its midpoint; ops that only enclose
    others count through their body."""
    out: Dict[str, Dict[str, float]] = {}
    for dev in devices:
        mods = sorted((s, e, trace._module_kind(n))
                      for n, s, e in dev["modules"])
        for name, s, e, path in dev["ops"]:
            if trace.op_base(name) in trace.CONTAINERS:
                continue
            mid = (s + e) / 2
            kind = next((k for a, b, k in mods if a <= mid <= b), None)
            table = out.setdefault(str(kind), {})
            scope = scope_of(path, names)
            table[scope] = table.get(scope, 0.0) + (e - s) / 1e9
    n = max(len(devices), 1)
    return {k: {s: v / n for s, v in t.items()} for k, t in out.items()}


def innermost_label(host: Sequence[Span], a: float, b: float) -> str:
    """The host span that covers most of the gap (a, b), each instant
    counted for the innermost span open then (the one opened last), so
    a gap spent in ``engine.sync`` reads ``engine.sync`` and not the
    ``engine.decode`` and ``loop.step`` around it."""
    over = [h for h in host if h[1] < b and h[2] > a]
    if not over:
        return "no span"
    edges = sorted({a, b} | {min(max(x, a), b) for _, s, e in over
                             for x in (s, e)})
    cover: Dict[str, float] = {}
    for lo, hi in zip(edges, edges[1:]):
        live = [h for h in over if h[1] <= lo and h[2] >= hi]
        name = max(live, key=lambda h: (h[1], -h[2]))[0] if live \
            else "no span"
        cover[name] = cover.get(name, 0.0) + hi - lo
    return max(cover.items(), key=lambda kv: kv[1])[0]


def idle_gaps(devices: Sequence[dict], host: Sequence[Span],
              window_ns: float) -> List[Tuple[str, float]]:
    """Every stretch of the window with no device op, labelled by
    ``innermost_label``, as (label, seconds)."""
    gaps = []
    for dev in devices:
        busy = trace.union(trace.clip([(s, e) for _, s, e, _ in dev["ops"]],
                                      0, window_ns))
        edges = [0.0] + [x for iv in busy for x in iv] + [window_ns]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((innermost_label(host, a, b), (b - a) / 1e9))
    return gaps


def _total(intervals) -> float:
    return sum(b - a for a, b in trace.union(intervals))


def host_seconds(host: Sequence[Span], window_ns: float) -> float:
    """Seconds of the window inside ``loop.step`` spans less those
    inside the ``engine.sync`` spans within them: the host's own work,
    not its waits on the device."""
    steps = trace.clip([(s, e) for n, s, e in host if n == "loop.step"],
                       0, window_ns)
    waits = [(max(s, a), min(e, b)) for n, s, e in host
             if n == "engine.sync" for a, b in steps if s < b and e > a]
    return (_total(steps) - _total(waits)) / 1e9


def reduce_events(devices: Sequence[dict], host: Sequence[Span],
                  window_ns: float, names: Sequence[str]) -> dict:
    """The reduction, from plain events (times in ns from the window's
    start); ``host`` holds (span name, start, end), ``names`` the scope
    names."""
    gaps = idle_gaps(devices, host, window_ns)
    n = max(len(devices), 1)
    by_label: Dict[str, float] = {}
    for label, sec in gaps:
        by_label[label] = by_label.get(label, 0.0) + sec / n
    return {
        "by_scope": by_scope(devices, names),
        "scoped_idle_gaps": [[k, v] for k, v in
                             sorted(gaps, key=lambda kv: -kv[1])[:10]],
        "idle_by_innermost_span": by_label,
        "host_s": host_seconds(host, window_ns),
    }


def read(trace_dir: Path):
    """(devices, host spans, window length) from the trace written under
    ``trace_dir``, times in ns from the window's start."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"no trace under {trace_dir}")
    raw = files[-1].read_bytes()
    paths = op_paths(memoryview(raw))
    pd = ProfileData.from_serialized_xspace(raw)
    devices, host = [], []
    lo = hi = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            named = paths.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [(e.name, e.start_ns, e.end_ns)
                                      for e in line.events]
                elif line.name == "XLA Ops":
                    dev["ops"] = [(e.name, e.start_ns, e.end_ns,
                                   named.get(e.name)) for e in line.events]
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        host.append((e.name, e.start_ns, e.end_ns))
                    elif e.name.endswith(" start_trace"):
                        lo = e.end_ns
                    elif e.name.endswith(" stop_trace"):
                        hi = e.start_ns
    if lo is None or hi is None or hi <= lo:
        raise RuntimeError("the trace does not record its own start and "
                           "stop calls")
    devices = [{"modules": [(n, s - lo, e - lo) for n, s, e in d["modules"]],
                "ops": [(n, s - lo, e - lo, p) for n, s, e, p in d["ops"]]}
               for d in devices]
    return devices, [(n, s - lo, e - lo) for n, s, e in host], hi - lo


def reduce(trace_dir: Path, names: Sequence[str]) -> dict:
    """``reduce_events`` of the trace written under ``trace_dir``, by the
    scope ``names``."""
    return reduce_events(*read(trace_dir), names)


def op_paths(buf) -> Dict[str, Dict[str, str]]:
    """{device plane: {op event name: op_name path}} from the stat
    ``OP_PATH_STAT`` of the event metadata. ``ProfileData`` shows only
    an event's own stats, so this reads the ``XSpace`` protobuf's wire
    format itself, no more of it than it needs: XSpace.planes = 1;
    XPlane.name = 2, event_metadata = 4, stat_metadata = 5 (maps: key =
    1, value = 2); X*Metadata.name = 2; XEventMetadata.display_name =
    4, stats = 5; XStat.metadata_id = 1, str_value = 5, ref_value = 7
    (a string kept as the name of a stat metadata)."""
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(buf):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f in (4, 5):
                entry = dict(_fields(buf, *v))
                if f == 4:
                    events.append(entry.get(2))
                else:
                    stat_names[entry.get(1, 0)] = next(
                        (_text(buf, w) for g, w in _fields(buf, *entry[2])
                         if g == 2), "")
        if not name.startswith("/device:"):
            continue
        ids = {k for k, n in stat_names.items() if n == OP_PATH_STAT}
        table = out.setdefault(name, {})
        for meta in filter(None, events):
            names, path = [], None
            for g, w in _fields(buf, *meta):
                if g in (2, 4):
                    names.append(_text(buf, w))
                elif g == 5:
                    stat = dict(_fields(buf, *w))
                    if stat.get(1, 0) in ids:
                        path = (_text(buf, stat[5]) if 5 in stat
                                else stat_names.get(stat.get(7)))
            if path:
                table.update((n, path) for n in names)
    return out


def _fields(buf, start: int = 0, end: Optional[int] = None):
    """(field number, value) of each field of one protobuf message in
    ``buf[start:end]``: an int for a varint, a (start, end) slice for a
    length-delimited field, None for a fixed-width one."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _text(buf, span: Tuple[int, int]) -> str:
    return bytes(buf[span[0]:span[1]]).decode()


def scope_time(red: Optional[dict], kind: str, scope: str) -> float:
    """Device seconds of one scope inside one jitted step, 0 where the
    reduction has no scopes (a trace of a program without them)."""
    if not red or "by_scope" not in red:
        return 0.0
    return red["by_scope"].get(kind, {}).get(scope, 0.0)
