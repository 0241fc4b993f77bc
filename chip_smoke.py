#!/usr/bin/env python3
"""Smoke run of the paper's GraphSAGE on a TPU, through the user entry points.

    python chip_smoke.py             # one chip: train, evaluate, serve
    python chip_smoke.py --chips 4   # four chips: shard_map vs vmap only

One chip: the Table-2 ogbn-products model (3-layer GraphSAGE, hidden 256,
100 input features, 47 classes, LayerNorm, label propagation, dropout 0.5)
on a 131,072-node SBM graph of average degree 25, partitioned hybrid into
4 workers in 2 groups and trained on the flagship schedule (Int2 inter
wire, inter delayed-communication period 2, overlap, bucketed-ELL
aggregation) with all four workers vmapped on the chip. It checks that
the compiled step runs the Pallas aggregation kernel, compiles once,
gives finite falling losses, matches the COO realization's per-node
logits, first-step gradients and first-epoch loss, and that 8 served
requests match the full-batch forward.

``--chips 4`` runs only the four-chip path and what it is compared with:
the same spec under ``exec.mode=shard_map`` (one worker per chip) against
``vmap`` on one chip of the same process: first-step gradients, then
loss trajectories.

It exits non-zero, with no ``ok`` line, when JAX finds no TPU or any check
fails. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Per-epoch times printed here are a smoke observation, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

EPOCHS = 3
SERVE_REQUESTS = 8
# Paper Table 2, ogbn-products row (repro.configs.graphsage_paper), with
# the preset's degree and 8x its synthetic node count.
SPEC = [
    "graph.source=sbm", "graph.nodes=131072", "graph.avg_degree=25",
    "graph.classes=47", "graph.feat_dim=100", "graph.seed=0",
    "partition.nparts=4", "partition.groups=2", "partition.strategy=hybrid",
    "schedule.inter_bits=2", "schedule.inter_cd=2", "schedule.overlap=true",
    "schedule.agg_backend=ell",
    "model.model=sage", "model.hidden_dim=256", "model.num_layers=3",
    "model.dropout=0.5", "model.norm=layer", "model.label_prop=true",
    "exec.mode=vmap", f"exec.epochs={EPOCHS}", "exec.lr=0.01", "exec.seed=0",
]
# Eval-mode logits at the initial parameters, ELL kernel vs COO
# scatter-add, per node, relative to the largest logit. The two add the
# same float32 neighbour terms in different orders (~1e-7 relative); the
# chip's default one-pass bfloat16 matmuls can round an input differing by
# that ulp to the next bfloat16 value, which moves a logit by ~1e-3 of its
# scale at most (the CPU, exact in float32, reads 3.8e-7). A dropped or
# mis-scattered bucket loses whole neighbour sums: with the smallest
# bucket dropped the CPU reads 0.21.
LOGIT_RTOL = 1e-2
# First-epoch training loss, ELL vs COO: the same sums in different order
# plus the rare Int2 element whose stochastic rounding that order flips,
# averaged over ~10^5 nodes (4.3e-7 on the chip).
COO_RTOL = 1e-5
# Served logits vs the full-batch forward. Both run the same kernel with
# the same per-row slot order; XLA may fuse the dense layers differently
# for the two program shapes, which moves float32 results by a few ulp.
SERVE_TOL = 1e-4
# Kernel vs the XLA reference at the smoke's per-worker shapes. The kernel
# multiplies and adds in float32 on the vector unit; the reference runs at
# "highest" matmul precision, so the two differ only in the order of at
# most 64 float32 terms of magnitude < 10.
KERNEL_ATOL, KERNEL_RTOL = 1e-4, 1e-5
# First-step gradients, ELL vs COO and shard_map on four chips vs vmap on
# one, per leaf relative to the leaf's largest value. On the CPU, with
# exact float32 matmuls, both read 3-4 ulp: the same terms summed in
# another order. The chip's programs differ in fusion, its one-pass
# bfloat16 matmuls round ulp-apart inputs apart, and the backward Int2
# wire re-quantizes what they produce; shard_map vs vmap read 7.7e-3
# there. A planted fault reads far higher on the CPU: the smallest degree
# bucket dropped 0.36, one worker missing from the gradient psum 1.76.
GRAD_RTOL = 5e-2
# Loss trajectories, shard_map on four chips vs vmap on one. The psum over
# the interconnect and the vmapped reduction add float32 partial sums in
# different orders; an Int2 rounding flipped by that difference and three
# Adam steps carry it forward. A misplaced shard or a wrong exchange moves
# the loss by more than 1e-2.
MESH_RTOL = 1e-3


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"{time.perf_counter() - T0:7.1f}s {msg}", flush=True)


def tpu_devices(count: int):
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"JAX found no TPU (platform {devs[0].platform!r})")
    check(len(devs) >= count, f"need {count} chips, JAX sees {len(devs)}")
    log(f"device: {devs[0].device_kind} x{len(devs)}")
    return devs


def build(spec, cache, label: str):
    from repro.run import build_session
    t0 = time.perf_counter()
    session = build_session(spec, cache=cache)
    log(f"[{label}] host build {time.perf_counter() - t0:.3f} s: "
        f"{session.graph.num_nodes} nodes, {session.graph.num_edges} edges, "
        f"{spec.describe()}")
    log(f"[{label}] exchange schedule: {session.schedule.describe()}")
    return session


def compile_step(session, label: str) -> str:
    t0 = time.perf_counter()
    text = session.lower().compile().as_text()
    log(f"[{label}] step compile {time.perf_counter() - t0:.3f} s")
    return text


def train(session, label: str):
    losses, times = [], []
    for _ in range(EPOCHS):
        t0 = time.perf_counter()
        m = session.train_epoch()
        times.append(time.perf_counter() - t0)
        losses.append(m["loss"])
        log(f"[{label}] epoch {len(losses)} loss {m['loss']:.6f} "
            f"train_acc {m['train_acc']:.4f} ({times[-1]:.3f} s)")
    check(all(math.isfinite(v) for v in losses),
          f"[{label}] non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"[{label}] loss did not fall: {losses}")
    check(session.step_cache_size() == 1,
          f"[{label}] step compiled {session.step_cache_size()} times")
    warm = times[1:]
    log(f"[{label}] epoch seconds after warm-up (smoke observation, not a "
        f"benchmark): {warm}, mean {sum(warm) / len(warm):.3f}")
    return losses


def peak_bytes(dev) -> None:
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")


def serve_phase(spec, session, cache) -> None:
    """Serve requests with the trained parameters through build_server and
    compare them with the server's own full-batch forward."""
    import numpy as np

    from repro.checkpoint import CheckpointManager
    from repro.serve import ServeSpec, build_server
    from repro.serve.spec import ServeConfig

    with tempfile.TemporaryDirectory() as ckpt:
        session.trainer.save_train_state(
            CheckpointManager(ckpt),
            meta={"graph_hash": spec.graph.content_hash(),
                  "spec_hash": spec.content_hash()})
        server = build_server(ServeSpec(run=spec, serve=ServeConfig(ckpt=ckpt)),
                              cache=cache)
    rng = np.random.default_rng(0)
    targets = rng.choice(session.graph.num_nodes, SERVE_REQUESTS, replace=False)
    t0 = time.perf_counter()
    served = server.serve_batch([[int(t)] for t in targets])
    dt = time.perf_counter() - t0
    ref = server.full_batch_logits()[targets]
    got = np.concatenate(served)
    check(bool(np.isfinite(got).all()), "served logits are not finite")
    diff = float(np.abs(got - ref).max())
    scale = max(1.0, float(np.abs(ref).max()))
    log(f"[serve] {SERVE_REQUESTS} requests in {dt:.3f} s (first batch, "
        f"compile included); max |served - full batch| = {diff!r} "
        f"(bit-identical: {bool(np.array_equal(got, ref))}); "
        f"compiled programs {server.compiled_programs()}")
    check(diff <= SERVE_TOL * scale,
          f"served logits differ from the full-batch forward by {diff}")


def kernel_phase() -> None:
    """The compiled kernel against ``ref.seg_aggregate_ref`` on the chip,
    at one worker's source rows and widths 100 (padded) and 256."""
    import jax
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.seg_aggregate import seg_aggregate
    n, r = 36864, 8192
    for f, k in ((100, 1), (256, 8), (100, 64)):
        kx, ki, kw = jax.random.split(jax.random.PRNGKey(f + k), 3)
        x = jax.random.normal(kx, (n, f))
        idx = jax.random.randint(ki, (r, k), 0, n)
        w = jax.random.uniform(kw, (r, k))
        got = seg_aggregate(x, idx, w)
        with jax.default_matmul_precision("highest"):
            want = ref.seg_aggregate_ref(x, idx, w)
        err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
        log(f"[kernel] N={n} F={f} R={r} K={k}: max |kernel - ref| {err!r}")
        check(np.allclose(got, want, rtol=KERNEL_RTOL, atol=KERNEL_ATOL),
              f"kernel differs from the reference at F={f} K={k}: {err}")


def step_grads(session, label: str) -> list:
    """Epoch-0 gradients from the session's initial parameters, before any
    update: the compiled train step, called without the optimizer."""
    import jax
    import numpy as np
    tr = session.trainer
    key = jax.random.PRNGKey(1000003)  # train_epoch's key at epoch 0
    t0 = time.perf_counter()
    g = jax.block_until_ready(tr._step(*tr._step_args(key))[0])
    log(f"[{label}] first step dispatch {time.perf_counter() - t0:.3f} s")
    return [np.asarray(a) for a in
            jax.tree_util.tree_leaves(tr._unreplicate(g))]


def compare_grads(label: str, got: list, want: list) -> None:
    """Largest per-leaf |got - want| over the leaf's largest |want|."""
    import numpy as np
    worst = max(float(np.abs(a - b).max())
                / max(float(np.abs(b).max()), float(np.finfo(np.float32).tiny))
                for a, b in zip(got, want))
    log(f"[{label}] first-step grads: largest leaf diff {worst!r} of the "
        f"leaf's largest value = "
        f"{worst / float(np.finfo(np.float32).eps):.1f} ulp (tolerance "
        f"{GRAD_RTOL})")
    check(worst <= GRAD_RTOL, f"[{label}] first-step grads differ by {worst}")


def one_chip(spec) -> None:
    from repro.run import BuildCache
    devs = tpu_devices(1)
    kernel_phase()
    import numpy as np
    cache = BuildCache()
    session = build(spec, cache, "ell")
    text = compile_step(session, "ell")
    check("tpu_custom_call" in text,
          "compiled step has no tpu_custom_call: the kernel did not run")
    log(f"[ell] compiled step holds {text.count('tpu_custom_call')} "
        "tpu_custom_call mentions")
    logits0 = np.asarray(session.trainer.predict())
    grads0 = step_grads(session, "ell")
    losses = train(session, "ell")
    log(f"[ell] eval_acc {session.evaluate():.4f}")

    coo = build(spec.with_overrides(["schedule.agg_backend=coo"]), cache, "coo")
    coo_logits0 = np.asarray(coo.trainer.predict())
    diff = np.abs(logits0 - coo_logits0).max(axis=-1)
    scale = float(np.abs(coo_logits0).max())
    log(f"[coo] initial eval logits, max |ell - coo| over {diff.size} rows "
        f"{float(diff.max())!r} = {float(diff.max()) / scale!r} of the "
        f"largest logit (tolerance {LOGIT_RTOL}); rows above 1e-4 of it: "
        f"{int((diff > 1e-4 * scale).sum())}")
    check(float(diff.max()) <= LOGIT_RTOL * scale,
          f"ell vs coo initial logits differ by {float(diff.max())}")
    compare_grads("coo", grads0, step_grads(coo, "coo"))
    coo_loss = coo.train_epoch()["loss"]
    rel = abs(coo_loss - losses[0]) / abs(coo_loss)
    log(f"[coo] first-epoch loss {coo_loss:.6f} vs ell {losses[0]:.6f}: "
        f"rel diff {rel!r} (tolerance {COO_RTOL})")
    check(rel <= COO_RTOL, f"ell vs coo first-epoch loss rel diff {rel}")
    del coo

    serve_phase(spec, session, cache)
    peak_bytes(devs[0])


# Collective ops and their replica groups in compiled HLO text.
_COLLECTIVE_RE = re.compile(
    r"= [^=]*?\b(all-to-all|all-reduce|reduce-scatter|all-gather)"
    r"(?:-start)?\(.*?replica_groups=(\{[^=]*?\}\}|\[[^\s,]*\]<=\[[^\]]*\]"
    r"(?:T\([^)]*\))?)")


def _group_devices(groups: str) -> set:
    if groups.startswith("{"):
        return {int(v) for v in re.findall(r"\d+", groups)}
    dims = [int(v) for v in groups[1:groups.index("]")].split(",")]
    return set(range(math.prod(dims)))


def four_chips(spec) -> None:
    import jax
    import numpy as np

    from repro.run import BuildCache
    devs = tpu_devices(4)
    cache = BuildCache()
    sm = build(spec.with_overrides(["exec.mode=shard_map"]), cache, "shard_map")
    homes = {len({s.device for s in leaf.addressable_shards})
             for leaf in jax.tree_util.tree_leaves(sm.trainer.wd)}
    check(homes == {4}, f"worker data spans {homes} devices, not 4")
    log("[shard_map] every WorkerData leaf is sharded over 4 distinct devices")
    text = compile_step(sm, "shard_map")
    check("tpu_custom_call" in text, "shard_map step has no tpu_custom_call")
    found = {}
    for op, groups in _COLLECTIVE_RE.findall(text):
        found.setdefault(op, set()).update(_group_devices(groups))
    log(f"[shard_map] collectives and the devices they span: "
        f"{ {k: sorted(v) for k, v in sorted(found.items())} }")
    check(found.get("all-to-all") == {0, 1, 2, 3},
          "no all-to-all over the 4 devices in the compiled step")
    check(found.get("all-reduce") == {0, 1, 2, 3},
          "no all-reduce over the 4 devices in the compiled step")
    vm = build(spec, cache, "vmap")
    compare_grads("mesh", step_grads(sm, "shard_map"), step_grads(vm, "vmap"))
    sm_losses = train(sm, "shard_map")
    del sm
    vm_losses = train(vm, "vmap")
    rel = float(np.max(np.abs(np.subtract(sm_losses, vm_losses))
                       / np.abs(vm_losses)))
    log(f"[mesh] shard_map vs vmap loss rel diff {rel!r} "
        f"(tolerance {MESH_RTOL})")
    check(rel <= MESH_RTOL, f"shard_map vs vmap losses differ by {rel}")
    for d in devs[:4]:
        peak_bytes(d)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    try:
        import jax

        from repro.run import RunSpec
        from repro.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"FAIL: the repro package is not importable: {e}",
              file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache()}")
    spec = RunSpec().with_overrides(SPEC)
    try:
        (one_chip if args.chips == 1 else four_chips)(spec)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
