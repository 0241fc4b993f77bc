"""The program's spans and counters: the one place the training path is
measured from inside.

``span(name)`` times a host-side stage. It opens a
``jax.profiler.TraceAnnotation`` named by the span's nested path (for
example ``epoch/optimizer``), so a profiler trace carries the span on the
clock of the device operations, and it adds the wall seconds to an
in-memory table keyed by that path. ``count(name, n)`` adds to a flat
counter table. ``snapshot()`` returns both as plain dicts; a caller takes
deltas between two snapshots. Memory grows with the number of distinct
names only, never with the number of events.

Compile events are counted too, through ``jax.monitoring``: for each of
tracing, lowering, backend compile (which includes a load from the
persistent cache) and the cache load alone, ``compile.<kind>.n`` events
and ``compile.<kind>.s`` seconds, in total and by function
(``compile.<kind>.s[<fun_name>]``). Tracing a function that calls another
``jit`` function traces both; only the outermost event's seconds go into
the totals. Tracing, lowering and backend compile that run inside a span
also add to the span table under ``<path>/compile``, so the table says
which stage compiled.

There is no switch: with no profiler running a span costs one inactive
``TraceAnnotation`` and one dict update, and spans are put around
stages, never around single operations.
"""

from __future__ import annotations

import contextlib
import re
import time
from typing import Dict, Iterator, List

import jax

_SPAN_FIELDS = ("n", "s", "min")
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}

_stack: List[str] = []
_spans: Dict[str, List[float]] = {}
_counters: Dict[str, float] = {}
_depth: Dict[str, int] = {}


def _add_span(path: str, seconds: float) -> None:
    row = _spans.get(path)
    if row is None:
        _spans[path] = [1, seconds, seconds]
        return
    row[0] += 1
    row[1] += seconds
    row[2] = min(row[2], seconds)


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Time the enclosed host code under ``<enclosing path>/<name>``."""
    _stack.append(name)
    path = "/".join(_stack)
    t = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(path):
            yield
    finally:
        _add_span(path, time.perf_counter() - t)
        _stack.pop()


def count(name: str, n: float = 1) -> None:
    _counters[name] = _counters.get(name, 0) + n


def snapshot() -> Dict[str, Dict]:
    """``{"spans": {path: {"n", "s", "min"}}, "counters": {name: value}}``
    (calls, total seconds, shortest call): copies, safe to keep while the
    tables go on counting."""
    return {"spans": {p: dict(zip(_SPAN_FIELDS, row)) for p, row in _spans.items()},
            "counters": dict(_counters)}


def delta(before: Dict[str, Dict], after: Dict[str, Dict]) -> Dict[str, Dict]:
    """What happened between two snapshots: counts and seconds subtract;
    ``min`` is left out, since it does not."""
    spans = {}
    for p, a in after["spans"].items():
        b = before["spans"].get(p, {"n": 0, "s": 0.0})
        if a["n"] > b["n"]:
            spans[p] = {"n": a["n"] - b["n"], "s": a["s"] - b["s"]}
    counters = {k: v - before["counters"].get(k, 0)
                for k, v in after["counters"].items()
                if v != before["counters"].get(k, 0)}
    return {"spans": spans, "counters": counters}


def _on_start(event: str, value: float, **kw) -> None:
    # JAX reports the start of a compile stage as a scalar; the depth it
    # keeps tells a nested event (an inner ``jit`` traced inside an outer
    # one) from an outermost one when the stage's duration arrives.
    kind = _COMPILE_EVENTS.get(event)
    if kind is not None:
        _depth[kind] = _depth.get(kind, 0) + 1


def _on_duration(event: str, seconds: float, **kw) -> None:
    kind = _COMPILE_EVENTS.get(event)
    if kind is None:
        return
    depth = max(_depth.get(kind, 0) - 1, 0)
    _depth[kind] = depth
    fun = kw.get("fun_name")
    if fun is not None:
        count(f"compile.{kind}.n[{fun}]")
        count(f"compile.{kind}.s[{fun}]", seconds)
    if depth:
        return
    count(f"compile.{kind}.n")
    count(f"compile.{kind}.s", seconds)
    if kind != "cache_load" and _stack:
        _add_span("/".join(_stack) + "/compile", seconds)


# The ``jax.named_scope`` names the training step uses (core/model.py,
# core/layers.py, core/trainer.py, core/exchange.py, kernels/seg_aggregate.py).
PROGRAM_SCOPE = re.compile(
    r"label_prop|loss|layer\d+|norm|dropout|aggregate|update|exchange_issue"
    r"|local|exchange_finalize|k\d+|agg_bwd|quantize|dequantize|attention"
    r"|sddmm")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", re.M)


def _split_top(op_name: str) -> List[str]:
    """Split a name stack on the ``/`` that lie outside parentheses."""
    parts, depth, start = [], 0, 0
    for i, c in enumerate(op_name):
        depth += (c == "(") - (c == ")")
        if c == "/" and depth == 0:
            parts.append(op_name[start:i])
            start = i + 1
    parts.append(op_name[start:])
    return parts


def scope_path(op_name: str) -> str:
    """The program scopes in an op's name stack, outermost first, with a
    last ``bwd`` when the op belongs to the VJP (JAX wraps the forward
    scopes a backward op came from in ``transpose(...)``). Empty when the
    op is in no program scope. ``jit(f)`` names a function, not a scope."""
    path, bwd = [], False
    for part in _split_top(op_name):
        while True:
            m = re.fullmatch(r"([\w.<>]+)\((.*)\)", part)
            if m is None or m.group(1) == "jit":
                break
            bwd = bwd or m.group(1) == "transpose"
            part = m.group(2)
        if m is None:
            path += [p for p in part.split("/") if PROGRAM_SCOPE.fullmatch(p)]
    if path and bwd:
        path.append("bwd")
    return "/".join(path)


def op_scopes(hlo_text: str) -> Dict[str, object]:
    """``{"module": name, "ops": {instruction: scope path}}`` of a compiled
    module's text, for every instruction in a program scope."""
    module = re.match(r"HloModule ([\w.\-]+)", hlo_text)
    ops = {}
    for name, op_name in _INSTR.findall(hlo_text):
        path = scope_path(op_name)
        if path:
            ops[name] = path
    return {"module": module.group(1) if module else None, "ops": ops}


jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
