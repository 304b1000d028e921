"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics and the breakdown read.

Layout of a TPU trace (read by hand on a v5e): the plane
``/device:TPU:<i>`` has a line ``XLA Modules`` (one event per execution
of a jitted program, named ``jit_<function>(<fingerprint>)``) and a line
``XLA Ops`` (every HLO op, a ``while`` enclosing the ops of its body;
a Pallas kernel is a custom call named after the function that makes
it). The plane ``/host:CPU`` has a line per thread; the Python thread's
line holds the ``TraceAnnotation`` spans. Event times are nanoseconds
from the start of the trace, on one clock for host and device.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Kernel families by the name of the op that runs them.
KERNELS = {
    "attention": ("flash_e2softmax_paged",),
    "norm": ("_qnorm_call", "_fused_call", "_fused_q_call"),
}
# Jitted steps by their function's name.
MODULES = {"prefill": "jit__prefill", "decode": "jit__decode_h"}
# Ops that only enclose others; their time is their body's.
CONTAINERS = ("while", "conditional", "call")

_SUFFIX = re.compile(r"[.\d]+$")


def op_base(name: str) -> str:
    """``%flash_e2softmax_paged.21 = f32[...] custom-call(...)`` ->
    ``flash_e2softmax_paged``."""
    return _SUFFIX.sub("", name.split("=")[0].strip().lstrip("%"))


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _kernel_family(base: str) -> Optional[str]:
    for fam, names in KERNELS.items():
        if base in names:
            return fam
    return None


def _module_kind(name: str) -> Optional[str]:
    for kind, prefix in MODULES.items():
        if name.startswith(prefix + "("):
            return kind
    return None


def reduce_events(devices: Sequence[dict], host: List[Tuple[str, float,
                                                                float]],
                  window_ns: float, spans: Sequence[str]) -> dict:
    """The reduction, from plain events.

    ``devices``: one dict per chip, {"modules": [(name, start, end)],
    "ops": [(name, start, end)]}; ``host``: [(span name, start, end)];
    times in ns from the trace start; ``window_ns`` the traced length.
    """
    busy_total = 0.0
    modules: Dict[str, List[float]] = {}
    kernels: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    host = [h for h in host if h[0] in spans]
    for dev in devices:
        mods = sorted((s, e, _module_kind(n)) for n, s, e in dev["modules"])
        starts = [m[0] for m in mods]
        for s, e, kind in mods:
            if kind is not None:
                m = modules.setdefault(kind, [0.0, 0])
                m[0] += (e - s) / 1e9
                m[1] += 1
        busy = union(clip([(s, e) for _, s, e in dev["ops"]], 0, window_ns))
        busy_total += sum(b - a for a, b in busy) / 1e9
        for name, s, e in dev["ops"]:
            base = op_base(name)
            if base in CONTAINERS:
                continue
            ops[base] = ops.get(base, 0.0) + (e - s) / 1e9
            fam = _kernel_family(base)
            if fam is None:
                continue
            mid = (s + e) / 2
            i = bisect.bisect_right(starts, mid) - 1
            kind = mods[i][2] if i >= 0 and mid <= mods[i][1] else None
            key = f"{fam}@{kind}"
            kernels[key] = kernels.get(key, 0.0) + (e - s) / 1e9
        edges = [0.0] + [x for iv in busy for x in iv] + [window_ns]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_label(host, a, b), (b - a) / 1e9))
    n = max(len(devices), 1)
    by_label: Dict[str, float] = {}
    for label, sec in gaps:
        by_label[label] = by_label.get(label, 0.0) + sec
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_total / n,
        "modules": {k: (v[0] / n, v[1] / n) for k, v in modules.items()},
        "kernels": {k: v / n for k, v in kernels.items()},
        "top_ops": [[k, v / n] for k, v in
                    sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v] for k, v in
                      sorted(gaps, key=lambda kv: -kv[1])[:10]],
        "idle_by_host_span": {k: v / n for k, v in by_label.items()},
    }


def _label(host, a: float, b: float) -> str:
    """The bench span that covers most of the gap (a, b)."""
    best, cover = "no span", 0.0
    for name, s, e in host:
        c = min(b, e) - max(a, s)
        if c > cover:
            best, cover = name, c
    return best


def reduce(trace_dir: Path, spans: Sequence[str]) -> dict:
    """Reduce the trace written under ``trace_dir``. The traced window
    runs from the end of the profiler's ``start_trace`` call to the
    start of its ``stop_trace`` call, as the trace itself records."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    devices, host = [], []
    lo = hi = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        host.append((e.name, e.start_ns, e.end_ns))
                    elif e.name.endswith(" start_trace"):
                        lo = e.end_ns
                    elif e.name.endswith(" stop_trace"):
                        hi = e.start_ns
    if lo is None or hi is None or hi <= lo:
        raise RuntimeError("the trace does not record its own start and "
                           "stop calls")
    shift = lambda evs: [(n, s - lo, e - lo) for n, s, e in evs]
    devices = [{k: shift(v) for k, v in d.items()} for d in devices]
    return reduce_events(devices, shift(host), hi - lo, spans)


def module_time(red: Optional[dict], kind: str) -> Tuple[float, float]:
    """(device seconds, executions) of a jitted step in the trace."""
    if not red:
        return 0.0, 0
    return tuple(red["modules"].get(kind, (0.0, 0)))


def kernel_time(red: Optional[dict], family: str,
                module: Optional[str]) -> float:
    """Device seconds of a kernel family, inside one jitted step or in
    any (``module=None``)."""
    if not red:
        return 0.0
    return sum(v for k, v in red["kernels"].items()
               if k.split("@")[0] == family
               and (module is None or k.split("@")[1] == module))
