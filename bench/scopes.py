"""Device time by program scope, and device idle time by program span,
from a profiler trace (``.xplane.pb``) of the training step.

``scope_time`` needs the step's module name and its instruction-to-scope
map (``Session.op_scopes()``); ``idle_by_span`` needs the span paths the
program emitted (the keys of ``repro.utils.trace.snapshot()["spans"]``).
Both reduce the host span ``window``, as ``bench.trace_reduce.reduce``
does, and leave that function and its outputs as they are.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench.trace_reduce import (_DEVICE_RE, WINDOW_SPAN, Interval, _clip,
                                _union, device_ops, host_spans, load)

MODULES_LINE = "XLA Modules"
NO_SPAN = "no program span"


def _window(spans) -> Interval:
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no host span named {WINDOW_SPAN!r}")
    return windows[0][0], windows[-1][1]


def module_intervals(pd, module: str) -> Dict[int, List[Interval]]:
    """Per device, the intervals in which ``module`` ran (events on the
    ``XLA Modules`` line are named ``<module>(<fingerprint>)``)."""
    out: Dict[int, List[Interval]] = {}
    for d, plane in _device_planes(pd):
        out[d] = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                  for line in plane.lines if line.name == MODULES_LINE
                  for ev in line.events if ev.name.split("(", 1)[0] == module]
    return out


def _device_planes(pd):
    for plane in pd.planes:
        m = _DEVICE_RE.match(plane.name)
        if m:
            yield int(m.group(1)), plane


def _inside(t: float, intervals: Sequence[Interval]) -> bool:
    return any(s <= t < e for s, e in intervals)


def scope_time(path: str, module: str, ops: Dict[str, str],
               devices: Optional[Sequence[int]] = None) -> Dict:
    """Device seconds of the step module's operations in the window, by
    scope path (``ops`` maps an instruction name to its path), averaged
    over the devices that ran the module.

    Only operations that start inside one of the module's runs count:
    the eager optimizer's modules reuse instruction names such as
    ``add``. Returns ``by_scope`` ({path: s}), ``unattributed_s`` (the
    module's operations in no scope) and ``step_s`` (all of them).
    """
    pd = load(path)
    lo, hi = _window(host_spans(pd))
    runs = {d: _clip(iv, lo, hi) for d, iv in module_intervals(pd, module).items()}
    per_dev = device_ops(pd)
    if devices is None:
        devices = sorted(d for d, iv in runs.items() if iv)
    if not devices:
        raise ValueError(f"{path}: module {module!r} did not run in the window")
    n = float(len(devices))
    by_scope: Dict[str, float] = defaultdict(float)
    unattributed = step = 0.0
    for d in devices:
        iv = runs.get(d, [])
        for o in per_dev.get(d, []):
            if not _inside(o.start, iv):
                continue
            t = (min(o.end, hi) - max(o.start, lo)) * 1e-9 / n
            step += t
            scope = ops.get(o.name)
            if scope:
                by_scope[scope] += t
            else:
                unattributed += t
    return {"by_scope": dict(by_scope), "unattributed_s": unattributed,
            "step_s": step}


def scope_sum(by_scope: Dict[str, float], part: str) -> float:
    """Seconds of every scope path that has ``part`` as one of its parts
    (``aggregate`` sums both directions of every layer's aggregation)."""
    return sum(t for p, t in by_scope.items() if part in p.split("/"))


def idle_by_span(path: str, names: Iterable[str], module: Optional[str] = None,
                 device: Optional[int] = None) -> Dict[str, float]:
    """Device idle seconds in the window, by the innermost host span named
    in ``names`` that was open at the time; ``NO_SPAN`` holds the idle time
    when none was. With ``module``, idle time inside one of that module's
    runs goes under the module's name instead: the device waited between
    the module's own operations, whatever the host was doing. The device is
    ``device``, or the first that ran an operation in the window (as
    ``reduce``'s ``idle_gaps``)."""
    pd = load(path)
    spans = host_spans(pd)
    lo, hi = _window(spans)
    names = set(names)
    prog = [(s, e, n) for n, s, e in spans if n in names and e > lo and s < hi]
    per_dev = device_ops(pd)
    if device is None:
        device = min(d for d, ops in per_dev.items()
                     if any(o.end > lo and o.start < hi for o in ops))
    runs = module_intervals(pd, module).get(device, []) if module else []
    busy = _union(_clip(((o.start, o.end) for o in per_dev[device]), lo, hi))
    out: Dict[str, float] = defaultdict(float)
    for gs, ge in _gaps(busy, lo, hi):
        cuts = sorted({gs, ge} | {x for s, e, _ in prog for x in (s, e)
                                   if gs < x < ge}
                      | {x for iv in runs for x in iv if gs < x < ge})
        for a, b in zip(cuts, cuts[1:]):
            if _inside(a, runs):
                out[module] += (b - a) * 1e-9
                continue
            cover = [(e - s, n) for s, e, n in prog if s <= a and b <= e]
            out[min(cover)[1] if cover else NO_SPAN] += (b - a) * 1e-9
    return dict(out)


def _gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Tuple[float, float]]:
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
