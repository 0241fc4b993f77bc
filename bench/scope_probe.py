#!/usr/bin/env python3
"""One traced run of a cell that reads the program's scopes and spans.

    python3 bench/scope_probe.py --workload <cell> --seed <n> [--epochs 2]

It builds the cell as ``bench/run.py`` does, warms the step up, times two
untraced epochs, then traces ``--epochs`` more under the benchmark's
profiler options and reduces the trace four ways: ``trace_reduce.reduce``
(busy time, kernel time), ``scopes.scope_time`` over the step's
instruction-to-scope map (``Session.op_scopes()``, read after the peak
memory), ``scopes.idle_by_span`` over the program's span paths (idle time
inside the step's own run apart), and the
per-layer readers of ``bench/metrics/`` that read the program's tables.
It checks nothing against the reference. The last line of standard output
is one JSON object; ``--out`` writes it to a file as well.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as R  # noqa: E402

READERS = ("agg_slot_fill", "agg_ns_per_slot", "compile_s",
                   "build_partition_s", "agg_kernel_ms")


def span_cost_s(reps: int = 20000) -> float:
    """Host seconds of one ``span`` with a ``count`` inside, no profiler."""
    from repro.utils import trace
    t = time.perf_counter()
    for _ in range(reps):
        with trace.span("probe"):
            trace.count("probe")
    return (time.perf_counter() - t) / reps


def probe(setup, seed: int, epochs: int) -> dict:
    import jax

    from bench import data, models, scopes, trace_reduce
    from repro.run import RunSpec, build_session
    from repro.utils import trace
    from repro.utils.compile_cache import enable_compile_cache

    cell, config, traffic = setup["cell"], setup["config"], setup["traffic"]
    arch = models.for_config(config, setup["models_dir"])
    devs = R.require_chips(cell["chips"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    source = data.register_sources(config)
    spec = RunSpec().with_overrides(R.spec_overrides(arch, config, traffic, source))
    t = time.perf_counter()
    session = build_session(spec)
    host_build_s = time.perf_counter() - t
    R.place_weights(session.trainer, arch.make_weights(config, seed))
    for _ in range(R.CHECK_STEPS + int(traffic.get("warmup_epochs", 0))):
        session.train_epoch()
    untraced = []
    for _ in range(2):
        t = time.perf_counter()
        session.train_epoch()
        untraced.append(time.perf_counter() - t)

    tmp = tempfile.mkdtemp(prefix="bench-probe-")
    try:
        before = trace.snapshot()
        t = time.perf_counter()
        path = R.record_trace(session, epochs, tmp)
        traced_s = (time.perf_counter() - t) / epochs
        spans = trace.delta(before, trace.snapshot())["spans"]
        reduced = trace_reduce.reduce(path, kernels=arch.KERNELS)
        peak = max(R.device_peak_bytes(d) for d in devs[:cell["chips"]])
        op_map = session.op_scopes()
        st = scopes.scope_time(path, op_map["module"], op_map["ops"])
        idle = scopes.idle_by_span(path, spans, module=op_map["module"])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    ctx = {"trace": reduced, "trace_epochs": epochs}
    metrics = {m: R.read_metric(setup["metrics_dir"], m, ctx) for m in READERS}
    per = lambda s: 1e3 * s / epochs  # noqa: E731
    metrics.update({
        "agg_layer_ms": per(scopes.scope_sum(st["by_scope"], "aggregate")),
        "update_ms": per(scopes.scope_sum(st["by_scope"], "update")),
    })
    cost = span_cost_s()
    top = sorted(st["by_scope"].items(), key=lambda kv: -kv[1])
    return {
        "device": {"kind": devs[0].device_kind, "memory_peak_bytes": peak},
        "metrics": metrics,
        "busy_ms_per_epoch": per(reduced["busy_s"]),
        "idle_share": reduced["idle_share"],
        "step_ms_per_epoch": per(st["step_s"]),
        "unattributed_share": st["unattributed_s"] / st["step_s"],
        "scopes_ms_per_epoch": {p: per(s) for p, s in top},
        "idle_by_span_ms_per_epoch": {k: per(v) for k, v in idle.items()},
        "traced_spans": spans,
        "host_build_s": host_build_s,
        "epoch_s_untraced": untraced,
        "epoch_s_traced": traced_s,
        "span_cost_us": 1e6 * cost,
        "span_share_of_epoch": 4 * cost / min(untraced),
        "idle_gaps": reduced["idle_gaps"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    try:
        result = probe(R.load_cell(args.workload), args.seed, args.epochs)
    except R.NoChip as e:
        print(f"scope_probe: {e}", file=sys.stderr)
        return 2
    line = json.dumps(result)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
