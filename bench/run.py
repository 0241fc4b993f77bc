#!/usr/bin/env python3
"""One benchmark run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its architecture, its traffic mix, its
limits and the readers of its per-layer metrics are found by name:
``BENCHMARK.json`` at the root of the checkout,
``bench/configs/<config>.json``, ``bench/models/<model>.py`` (the
configuration's ``model.model``: the reference, the weights, the spec keys,
the work counts and the kernel names), ``bench/traffic/<traffic>.json``,
``bench/limits/<cell>.json`` and ``bench/metrics/<metric>.py``. Adding any
of them needs no edit here.

A run builds the session through the program's ``build_session`` from the
benchmark's own graph, puts the weights drawn from ``--seed`` in place,
and trains three epochs through ``Session.train_epoch``: they compile
(or load from the persistent cache) and warm up, and their losses, the
first gradient and the parameters after them are what ``correct``
compares with the architecture's plain reference. Then it trains for
``--seconds`` (the window), and with ``--trace 1`` traces a few more
epochs. After the program's state is freed the reference follows the
same three steps.

The last line of standard output is one JSON object. A run that finds no
TPU, or fewer chips than the cell asks for, exits 2 and prints none.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CHECK_STEPS = 3


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T0:8.2f}s] {msg}", file=sys.stderr,
          flush=True)


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Dict:
    """Everything one cell needs, found by name from ``BENCHMARK.json``."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    here = os.path.join(root, "bench")
    limits_path = os.path.join(here, "limits", f"{name}.json")
    return {
        "cell": cell,
        "config": _json(os.path.join(root, config["file"])),
        "traffic": _json(os.path.join(here, "traffic", f"{cell['traffic']}.json")),
        "limits": (_json(limits_path)["limits"]
                   if os.path.exists(limits_path) else None),
        "end_to_end": bench["end_to_end"],
        "per_layer": bench["per_layer"],
        "metrics_dir": os.path.join(here, "metrics"),
        "models_dir": os.path.join(here, "models"),
        "peaks": _json(os.path.join(here, "peaks.json")),
    }


def spec_overrides(arch, config: Dict, traffic: Dict, source: str):
    """The program's spec keys: the graph's, the architecture module's
    ``spec(config)``, then the traffic mix's."""
    m, g = config["model"], config["graph"]
    over = {
        "graph.source": source, "graph.features": source,
        "graph.nodes": g["nodes"], "graph.classes": m["num_classes"],
        "graph.avg_degree": g["avg_degree"], "graph.feat_dim": m["in_dim"],
        "graph.seed": g["seed"], "exec.lr": m["lr"], "exec.seed": 0,
    }
    over.update(arch.spec(config))
    over.update(traffic["spec"])
    return [f"{k}={str(v).lower() if isinstance(v, bool) else v}"
            for k, v in over.items()]


def require_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def host_tree(tree):
    import jax
    import numpy as np
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


def place_weights(trainer, weights) -> None:
    """Put the benchmark's weights where the trainer keeps its own, with
    the same placement (replicated over the mesh under shard_map)."""
    import jax
    trainer.params = jax.tree.map(lambda new, old: jax.device_put(new, old.sharding),
                                  weights, trainer.params)


def checked_steps(session, steps: int = CHECK_STEPS) -> Dict:
    """The first ``steps`` epochs of the window's own call, from the
    weights in place: losses, the first gradient (Adam's first moment
    after one step over ``1 - beta1``) and the parameters after them."""
    import jax

    from bench.reference import B1
    tr = session.trainer
    losses, grad1 = [], None
    for _ in range(steps):
        losses.append(session.train_epoch()["loss"])
        if grad1 is None:
            grad1 = jax.tree.map(lambda m: m / (1.0 - B1), host_tree(tr.opt_state.mu))
    return {"losses": losses, "grad1": grad1, "params": host_tree(tr.params)}


def kernel_calls(session):
    """The aggregation kernel's edge sets per epoch: the local graphs and
    each exchange stage's receive scatter, summed over the workers; each
    runs forward and backward once per layer."""
    import numpy as np
    calls = []
    local_e = local_r = 0
    for c in session.pg.local_csr:
        rows = np.repeat(np.arange(c.num_rows), np.diff(c.indptr))
        real = np.asarray(c.weights) != 0
        local_e += int(real.sum())
        local_r += int(np.unique(rows[real]).size)
    calls.append({"set": "local", "edges": local_e, "rows": local_r, "per_epoch": 2})
    wd = session.wd
    plans = ([("flat", wd.plan)] if wd.plan is not None else
             [("intra", wd.hier_plan.intra), ("inter", wd.hier_plan.inter)])
    for level, plan in plans:
        w = np.asarray(plan.recv_weight)
        dst = np.asarray(plan.recv_dst)
        edges = int((w != 0).sum())
        rows = sum(int(np.unique(dst[p][w[p] != 0]).size) for p in range(w.shape[0]))
        calls.append({"set": level, "edges": edges, "rows": rows, "per_epoch": 2})
    return calls


def device_peak_bytes(device) -> int:
    """The device's peak memory: the peak of its allocated buffers plus
    the peak of the region the runtime reserves for compiled programs'
    temporaries, which ``peak_bytes_in_use`` leaves out."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def read_metric(metrics_dir: str, name: str, ctx: Dict):
    path = os.path.join(metrics_dir, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def record_trace(session, epochs: int, log_dir: str) -> str:
    """Trace ``epochs`` calls of ``Session.train_epoch`` inside a host span
    named ``window``; returns the ``.xplane.pb`` written under ``log_dir``."""
    import jax

    from bench import trace_reduce
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 1
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            for _ in range(epochs):
                with jax.profiler.TraceAnnotation("train_epoch"):
                    session.train_epoch()
    finally:
        jax.profiler.stop_trace()
    found = [os.path.join(d, f) for d, _, fs in os.walk(log_dir) for f in fs
             if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise RuntimeError(f"expected one trace file, found {found}")
    return found[0]


def run(setup: Dict, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True) -> Dict:
    """One run; returns the result object (without printing it)."""
    import jax
    import numpy as np

    from bench import check, data, models
    from repro.run import RunSpec, build_session
    from repro.utils.compile_cache import enable_compile_cache

    cell, config, traffic = setup["cell"], setup["config"], setup["traffic"]
    arch = models.for_config(config, setup["models_dir"])
    devs = require_chips(cell["chips"]) if require_tpu else jax.devices()
    # Cache every program, the eager optimizer's small ones too, so that a
    # run after the first in a checkout compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{enable_compile_cache()}")
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: compiles.append(time.perf_counter())
        if ev == "/jax/core/compile/backend_compile_duration" else None)

    source = data.register_sources(config)
    spec = RunSpec().with_overrides(spec_overrides(arch, config, traffic, source))
    t = time.perf_counter()
    session = build_session(spec)
    host_build_s = time.perf_counter() - t
    log(f"host build {host_build_s:.3f} s: {session.graph.num_nodes} nodes, "
        f"{session.graph.num_edges} edges (self-loops included); {spec.describe()}")

    weights = arch.make_weights(config, seed)
    weights0 = host_tree(weights)
    place_weights(session.trainer, weights)
    del weights
    t = time.perf_counter()
    prog = checked_steps(session)
    for _ in range(int(traffic.get("warmup_epochs", 0))):
        session.train_epoch()
    warmup_s = time.perf_counter() - t
    setup_s = time.perf_counter() - T0
    log(f"warm-up {warmup_s:.3f} s, losses {prog['losses']}; set-up {setup_s:.3f} s")

    n_compiles = len(compiles)
    t0 = time.perf_counter()
    losses = []
    while True:
        losses.append(session.train_epoch()["loss"])
        t1 = time.perf_counter()
        if t1 - t0 >= seconds:
            break
    epoch_s = (t1 - t0) / len(losses)
    in_window = len(compiles) - n_compiles
    log(f"window {t1 - t0:.3f} s, {len(losses)} epochs, epoch_s {epoch_s:.6f}, "
        f"compiles in window {in_window}")
    reduced = None
    if trace:
        from bench import trace_reduce
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            path = record_trace(session, int(traffic["trace_epochs"]), tmp)
            reduced = trace_reduce.reduce(path, kernels=arch.KERNELS)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        log(f"trace: {json.dumps(reduced)}")

    peak = max(device_peak_bytes(d) for d in devs[:cell["chips"]])
    graph = data.graph_for(config)
    counts = {
        "nodes": graph.num_nodes,
        "edges": graph.src.size + graph.num_nodes,
        "dims": arch.widths(config),
        "kernel_calls": kernel_calls(session),
    }
    del session
    gc.collect()

    t = time.perf_counter()
    ref = arch.train_steps(graph, weights0, config["model"], CHECK_STEPS)
    values = check.readings(prog, ref, weights0)
    log(f"reference {time.perf_counter() - t:.3f} s, losses {ref['losses']}")
    limits = setup["limits"]
    correct = limits is not None and check.judge(values, limits)
    failed = sum(not math.isfinite(v) for v in losses)
    work = models.epoch_work(arch, counts)
    log(f"work per epoch: {json.dumps(work)}")

    ctx = {"config": config, "traffic": traffic, "cell": cell,
           "chips": cell["chips"], "counts": counts, "work": work, "trace": reduced,
           "trace_epochs": traffic.get("trace_epochs"),
           "peaks": _peaks(setup["peaks"], devs[0].device_kind),
           "timing": {"host_build_s": host_build_s, "warmup_s": warmup_s,
                      "setup_s": setup_s, "epoch_s": epoch_s}}
    metrics = {}
    if trace:
        for m in setup["per_layer"]:
            v = read_metric(setup["metrics_dir"], m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s, "epoch_s": epoch_s,
               "peak_hbm_gib": peak / 2**30}
        for m in setup["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct and failed == 0),
              "attempted": len(losses), "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": (limits or {}).get(k)}
                        for k, v in values.items()}
    return result


def _peaks(table: Dict, kind: str) -> Dict:
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    setup = load_cell(args.workload)
    try:
        result = run(setup, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
