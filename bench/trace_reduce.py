"""From a profiler trace (``.xplane.pb``) to device busy time, kernel time,
exposed collective time and the breakdown.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation that ran. The host plane ``/host:CPU`` holds the
benchmark's own ``jax.profiler.TraceAnnotation`` spans; the span named
``window`` bounds what is reduced. Host and device events share one clock
in the trace.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "window"
OPS_LINE = "XLA Ops"
COLLECTIVE_RE = re.compile(
    r"all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|all_to_all|all_gather|all_reduce|psum", re.I)
_DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[float, float]


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def _clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _minus(a: Interval, cover: Sequence[Interval]) -> float:
    """Length of interval ``a`` not covered by the sorted union ``cover``."""
    s, e = a
    left = e - s
    for cs, ce in cover:
        if ce <= s:
            continue
        if cs >= e:
            break
        left -= min(e, ce) - max(s, cs)
    return max(left, 0.0)


class Op:
    __slots__ = ("name", "start", "end", "text")

    def __init__(self, name: str, start: float, end: float, text: str):
        self.name, self.start, self.end, self.text = name, start, end, text


def _event_text(ev) -> str:
    """The event's name and every string stat (HLO op, long name, kernel
    name), so that a kernel is found under whichever the trace gives."""
    parts = [ev.name]
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_ops(pd) -> Dict[int, List[Op]]:
    out: Dict[int, List[Op]] = {}
    for plane in pd.planes:
        m = _DEVICE_RE.match(plane.name)
        if not m:
            continue
        ops = []
        texts: Dict[str, str] = {}
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                if ev.name not in texts:
                    texts[ev.name] = _event_text(ev)
                # A TPU trace names an operation by its whole HLO line;
                # the breakdown keeps the instruction's name.
                name = ev.name.split(" = ", 1)[0].lstrip("%")
                ops.append(Op(name, ev.start_ns, ev.start_ns + ev.duration_ns,
                              texts[ev.name]))
        out[int(m.group(1))] = ops
    return out


def host_spans(pd) -> List[Tuple[str, float, float]]:
    spans = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return spans


def reduce(path: str, kernels: Sequence[str] = (),
           devices: Optional[Sequence[int]] = None, top: int = 10) -> Dict:
    """Reduce one trace. Times are in seconds, summed per device and then
    averaged over the devices reduced (``devices``, default all that ran
    an operation in the window).

    Returns ``window_s``, ``busy_s``, ``idle_share``, ``kernel_s`` (per
    name in ``kernels``: the time of the operations whose name or string
    stats contain it; absent when none ran), ``collective_s`` and
    ``collective_exposed_s`` (absent when no collective ran), and
    ``device_ops`` / ``idle_gaps`` for the breakdown (the gaps of the
    first device reduced).
    """
    pd = load(path)
    spans = host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no host span named {WINDOW_SPAN!r}")
    lo, hi = windows[0][0], windows[-1][1]
    per_dev = device_ops(pd)
    if devices is None:
        devices = sorted(d for d, ops in per_dev.items()
                         if any(o.end > lo and o.start < hi for o in ops))
    if not devices:
        raise ValueError(f"{path}: no device operation in the window")
    n = float(len(devices))
    busy = 0.0
    kernel_s: Dict[str, float] = defaultdict(float)
    by_name: Dict[str, float] = defaultdict(float)
    coll = exposed = 0.0
    saw_coll = False
    gaps: List[Tuple[float, float]] = []
    for d in devices:
        ops = [o for o in per_dev.get(d, []) if o.end > lo and o.start < hi]
        union = _union(_clip(((o.start, o.end) for o in ops), lo, hi))
        busy += _length(union)
        other = _union(_clip(((o.start, o.end) for o in ops
                              if not COLLECTIVE_RE.search(o.text)), lo, hi))
        for o in ops:
            s, e = max(o.start, lo), min(o.end, hi)
            by_name[o.name] += (e - s) / n
            for k in kernels:
                if k in o.text:
                    kernel_s[k] += (e - s) / n
            if COLLECTIVE_RE.search(o.text):
                saw_coll = True
                coll += (e - s) / n
                exposed += _minus((s, e), other) / n
        if d == devices[0]:
            edges = [lo] + [x for iv in union for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    window_s = (hi - lo) * 1e-9
    busy_s = busy / n * 1e-9
    out = {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "kernel_s": {k: v * 1e-9 for k, v in kernel_s.items()},
        "device_ops": [[k, v * 1e-9] for k, v in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_label(spans, s, e), (e - s) * 1e-9] for s, e in
                      sorted(gaps, key=lambda g: g[0] - g[1])[:top]],
    }
    if saw_coll:
        out["collective_s"] = coll * 1e-9
        out["collective_exposed_s"] = exposed * 1e-9
    return out


def _host_label(spans, s: float, e: float) -> str:
    """What the host was doing through a device gap: the innermost host
    event that covers the whole gap, leaving out the window span and the
    Python tracer's calls of builtins."""
    best = None
    for name, hs, he in spans:
        if (hs <= s and e <= he and name != WINDOW_SPAN
                and not name.startswith("$builtins")):
            if best is None or he - hs < best[1] - best[0]:
                best = (hs, he, name)
    return best[2] if best else "no host span"
