import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input shape) on the
production meshes and extract roofline inputs.

For each combination this lowers the real step function —

  * train_4k     -> ``train_step`` (fwd + bwd + AdamW, microbatched)
  * prefill_32k  -> ``forward_train`` logits (inference prefill)
  * decode_32k / long_500k -> ``serve_step`` (1 token, KV/state cache)

against ShapeDtypeStruct inputs with production shardings, calls
``.lower().compile()``, and records ``memory_analysis()`` /
``cost_analysis()`` plus the collective bytes parsed from the partitioned
HLO. Results land in ``experiments/dryrun/<arch>__<shape>__<mesh>.json``
and feed §Dry-run/§Roofline of EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--gcn]
"""

import argparse
import functools
import json
import time
import traceback
from pathlib import Path
from typing import Optional

import jax

from repro.configs import ARCH_NAMES, INPUT_SHAPES, get_arch
from repro.launch.hlo_stats import parse_collectives
from repro.launch.input_specs import input_specs
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import forward_train, serve_step, train_step

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _mem_dict(mem) -> dict:
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        try:
            out[k] = int(getattr(mem, k))
        except Exception:
            pass
    return out


def _cost_dict(cost) -> dict:
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    out = {}
    for k, v in dict(cost).items():
        if k not in ("flops", "bytes accessed", "transcendentals"):
            continue
        try:
            out[k] = float(v)
        except Exception:
            pass
    return out


def _layer_quantum(arch) -> int:
    """Smallest layer-count step that keeps the arch structure valid."""
    if arch.family == "hybrid":
        return arch.attn_every
    if arch.family == "ssm":
        return arch.xlstm_group
    return 1


def reduced_arch(arch, num_layers: int):
    import dataclasses as dc
    kw = {"num_layers": num_layers}
    if arch.family == "audio":
        kw["enc_layers"] = num_layers
    return dc.replace(arch, **kw)


def cost_extrapolate(arch_name: str, shape_name: str, mesh) -> dict:
    """HLO FLOPs/bytes with loop correction: cost_analysis counts while
    bodies once, so measure L1- and L2-layer variants and extrapolate
    linearly to the full depth (layer stacks are homogeneous scans).
    Train shapes are measured at one microbatch of the global batch and
    scaled by num_microbatches (optimizer flops ~O(N), negligible error)."""
    import dataclasses as dc
    arch = get_arch(arch_name)
    q = _layer_quantum(arch)
    l1, l2 = q, 2 * q
    if arch.num_layers <= l2:
        l1, l2 = None, arch.num_layers  # tiny model: measure directly
    shape = INPUT_SHAPES[shape_name]
    spec_probe = input_specs(arch, shape_name, mesh)
    nm = spec_probe.get("num_microbatches") or 1

    def measure(layers):
        a = reduced_arch(arch, layers)
        if shape.kind == "train" and nm > 1:
            sh = dc.replace(shape, global_batch=shape.global_batch // nm)
            sp = _specs_for(a, sh, mesh, num_microbatches=1)
        else:
            sp = _specs_for(a, shape, mesh, num_microbatches=1)
        lowered = _lower(a, sp, mesh)
        cost = _cost_dict(lowered.compile().cost_analysis())
        return cost

    c2 = measure(l2)
    out = {"L2": l2, "cost_L2": c2, "num_microbatches": nm}
    keys = [k for k in ("flops", "bytes accessed") if k in c2]
    if l1 is not None:
        c1 = measure(l1)
        out["L1"] = l1
        out["cost_L1"] = c1
        est = {}
        for k in keys:
            per_layer = (c2[k] - c1[k]) / (l2 - l1)
            est[k] = c2[k] + (arch.num_layers - l2) * per_layer
        out["per_layer"] = {k: (c2[k] - c1[k]) / (l2 - l1) for k in keys}
    else:
        est = {k: c2[k] for k in keys}
    if shape.kind == "train" and nm > 1:
        est = {k: v * nm for k, v in est.items()}
    out["estimated_full"] = est
    return out


def _specs_for(arch, shape, mesh, num_microbatches=None):
    """input_specs but for an already-materialized (possibly reduced) arch
    and shape object."""
    import repro.launch.input_specs as mod
    reason = mod.skip_reason(arch, shape)
    if reason:
        return {"skip": reason}
    window = mod.effective_window(arch, shape)
    params, pspecs = mod.param_input_specs(arch, mesh,
                                           fsdp=(shape.kind == "train"))
    out = {"params": params, "param_specs": pspecs, "window": window,
           "shape": shape}
    if shape.kind == "train":
        out["opt_state"] = mod.opt_input_specs(params, pspecs, mesh)
        out["batch"] = mod.batch_input_specs(arch, shape, mesh)
        out["num_microbatches"] = (num_microbatches if num_microbatches
                                   else mod.num_microbatches(arch, shape, mesh))
    elif shape.kind == "prefill":
        out["batch"] = mod.batch_input_specs(arch, shape, mesh)
    else:
        cache, tokens = mod.decode_input_specs(arch, shape, mesh)
        out["cache"] = cache
        out["tokens"] = tokens
    return out


def _lower(arch, spec, mesh):
    window = spec["window"]
    shape = spec["shape"]
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            nm = spec["num_microbatches"]
            fn = functools.partial(train_step, cfg=arch, lr=3e-4,
                                   num_microbatches=nm, window=window)
            return jax.jit(fn).lower(spec["params"], spec["opt_state"],
                                     spec["batch"])
        if shape.kind == "prefill":
            def prefill(params, batch):
                tokens = batch["tokens"]
                extra = {k: v for k, v in batch.items() if k != "tokens"}
                logits, _ = forward_train(params, arch, tokens, extra or None,
                                          window)
                return logits
            return jax.jit(prefill).lower(spec["params"], spec["batch"])
        fn = functools.partial(serve_step, cfg=arch, window=window)
        def decode(params, cache, tokens):
            return fn(params, cache, tokens)
        return jax.jit(decode).lower(spec["params"], spec["cache"],
                                     spec["tokens"])


def build_lowered(arch_name: str, shape_name: str, mesh):
    arch = get_arch(arch_name)
    spec = input_specs(arch, shape_name, mesh)
    if "skip" in spec:
        return None, spec["skip"]
    lowered = _lower(arch, spec, mesh)
    meta = {"num_microbatches": spec.get("num_microbatches"),
            "window": spec["window"], "kind": spec["shape"].kind}
    return lowered, meta


def run_one(arch_name: str, shape_name: str, multi_pod: bool,
            save: bool = True, hlo_out: bool = False,
            extrapolate: bool = None,
            out_dir: Optional[Path] = None) -> dict:
    out_dir = Path(out_dir) if out_dir else OUT_DIR
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch_name, "shape": shape_name, "mesh": mesh_name,
           "chips": 512 if multi_pod else 256, "status": "ok"}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        lowered, meta = build_lowered(arch_name, shape_name, mesh)
        if lowered is None:
            rec["status"] = "skip"
            rec["skip_reason"] = meta
            return _finish(rec, t0, save, out_dir)
        rec.update(meta)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
        rec["memory"] = _mem_dict(compiled.memory_analysis())
        rec["cost"] = _cost_dict(compiled.cost_analysis())
        hlo = compiled.as_text()
        rec["collectives"] = parse_collectives(hlo)
        # Loop-aware FLOP/traffic estimate (cost_analysis counts while bodies
        # once — verified — so §Roofline uses this HLO walk instead).
        from repro.launch.hlo_stats import analyze_hlo
        rec["hlo_analysis"] = analyze_hlo(hlo)
        rec["hlo_bytes"] = len(hlo)
        if hlo_out:
            out_dir.mkdir(parents=True, exist_ok=True)
            (out_dir / f"{arch_name}__{shape_name}__{mesh_name}.hlo").write_text(hlo)
        print(compiled.memory_analysis())
        ca = rec["cost"]
        print(f"  flops={ca.get('flops', 0):.3e} bytes={ca.get('bytes accessed', 0):.3e} "
              f"coll_operand_bytes={rec['collectives']['total']['operand_bytes']:.3e}")
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    return _finish(rec, t0, save, out_dir)


def _finish(rec: dict, t0: float, save: bool,
            out_dir: Optional[Path] = None) -> dict:
    out_dir = Path(out_dir) if out_dir else OUT_DIR
    rec["total_s"] = round(time.time() - t0, 2)
    if save:
        out_dir.mkdir(parents=True, exist_ok=True)
        path = out_dir / f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
        path.write_text(json.dumps(rec, indent=1, default=str))
    tag = rec["status"].upper()
    print(f"[{tag}] {rec['arch']} x {rec['shape']} on {rec['mesh']} "
          f"({rec['total_s']}s)" + (f" :: {rec.get('error','')}" if tag == "ERROR" else ""))
    return rec


def gcn_base_spec(nparts: int, scale: int = 13) -> "RunSpec":
    """The dry-run's base RunSpec: a structural R-MAT stand-in graph
    (zero features/labels — host preprocessing at laptop scale) lowered
    through the production shard_map trainer with the paper's Table-2
    GraphSAGE shape and an Int2 wire."""
    from repro.run import RunSpec
    return RunSpec().with_overrides([
        "graph.source=rmat", f"graph.scale={scale}", "graph.edge_factor=8",
        "graph.seed=7", "graph.feat_dim=128", "graph.classes=40",
        f"partition.nparts={nparts}", "partition.seed=0",
        "schedule.bits=2", "model.hidden_dim=256", "model.num_layers=3",
        "exec.mode=shard_map", "exec.seed=0",
    ])


def run_gcn_dryrun(spec, mesh_name: str = None, save: bool = True,
                   assert_overlap: bool = False,
                   out_dir: Optional[Path] = None) -> dict:
    """Dry-run the paper's distributed GCN trainer on the production mesh —
    ``build_session(spec).lower()`` plus the HLO analyses.

    ``partition.groups=0`` is 1-D graph-parallel over all chips (flat
    schedule); ``groups=G`` lowers the two-level (group, node) shard_map
    trainer on a G x (nparts/G) mesh. The schedule section threads
    straight through, so e.g. ``--groups 16 --cd 4`` dry-runs delayed-comm
    on the hierarchical exchange. The record carries the spec (and its
    content hash — the artifact names its exact configuration), the
    schedule description, the CommStats per-stage wire-byte predictions
    next to the collective bytes parsed from the partitioned HLO, and the
    collective scheduling order parsed from the *lowered* StableHLO — the
    overlap proof: with the two-phase LayerProgram the wire collectives
    precede the bucketed aggregation's dot ops in program order.

    ``--chips``/``--scale`` shrink the run for the fast CI check (default
    is the full 256/512-chip mesh on rmat-13); ``assert_overlap`` flips
    the record to error status when the parsed order shows the wire is NOT
    issued before the aggregation compute.
    """
    from repro.launch.hlo_stats import collective_order
    from repro.run import build_session

    groups = spec.partition.groups
    nparts = spec.partition.nparts
    gs = spec.graph
    size = gs.scale if gs.source == "rmat" else gs.nodes
    shape_name = (f"{gs.source}{size}-fullbatch"
                  + (f"-g{groups}" if groups else ""))
    rec = {"arch": "supergcn-graphsage", "shape": shape_name,
           "mesh": mesh_name or f"{nparts}chips", "chips": nparts,
           "status": "ok", "spec": spec.to_dict(),
           "spec_hash": spec.content_hash()}
    t0 = time.time()
    try:
        session = build_session(spec)
        pg = session.pg
        rec["agg_backend"] = spec.schedule.agg_backend
        rec["schedule"] = session.schedule.describe()
        rec["predicted_wire_bytes"] = session.predicted_wire_bytes()
        lowered = session.lower()
        # Overlap evidence lives in the lowered (trace-order) module; the
        # compiled text below is scheduler-normalized (see hlo_stats).
        order = collective_order(lowered.as_text())
        rec["collective_order"] = dict(order, events=order["events"][:64],
                                       num_events=len(order["events"]))
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
        rec["memory"] = _mem_dict(compiled.memory_analysis())
        rec["cost"] = _cost_dict(compiled.cost_analysis())
        rec["collectives"] = parse_collectives(compiled.as_text())
        rec["comm_stats"] = pg.stats.as_dict()
        print(compiled.memory_analysis())
        print(f"  collective order: wire_before_compute="
              f"{order['wire_before_compute']} inter_wire_before_compute="
              f"{order['inter_wire_before_compute']}")
        if assert_overlap:
            # Served by the auditor's overlap-order rule (same invariant,
            # same framework as `make audit`); reuse this run's session and
            # lowered module instead of rebuilding.
            from repro.analysis.hlo_rules import OverlapOrderRule
            from repro.analysis.rules import AuditContext, Severity

            ctx = AuditContext(spec, spec_name=shape_name)
            ctx._session = session
            ctx._lowered = lowered
            if not any(s.overlap for s in session.schedule.stages):
                raise AssertionError(
                    "overlap check failed: no stage of the resolved "
                    f"schedule overlaps ({session.schedule.describe()}) — "
                    "pass --overlap (or a hierarchical topology, whose "
                    "schedule overlaps by default)")
            findings = OverlapOrderRule().check(ctx)
            rec["audit_findings"] = [f.as_dict() for f in findings]
            errors = [f for f in findings
                      if f.severity == Severity.ERROR]
            if errors:
                raise AssertionError(
                    "overlap check failed: " + "; ".join(
                        f.message for f in errors))
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    return _finish(rec, t0, save, out_dir)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--gcn", action="store_true",
                    help="dry-run the SuperGCN distributed trainer")
    from repro.run import add_spec_args, spec_from_args
    add_spec_args(ap)
    # Legacy --gcn flags: aliases onto the RunSpec (default=None = "not
    # passed"; the base spec supplies the dry-run defaults, incl. bits=2).
    ap.add_argument("--groups", type=int, default=None,
                    help="with --gcn: num_groups for the hierarchical "
                         "(group, node) trainer (0 = flat 1-D); alias for "
                         "--set partition.groups=G")
    ap.add_argument("--bits", type=int, default=None, choices=(0, 2, 4, 8),
                    help="with --gcn: wire format for the exchange "
                         "schedule (base spec: 2); alias for "
                         "--set schedule.bits=B")
    ap.add_argument("--cd", type=int, default=None,
                    help="with --gcn: delayed-comm refresh period; alias "
                         "for --set schedule.cd=N")
    ap.add_argument("--agg-backend", default=None, choices=("coo", "ell"),
                    help="with --gcn: aggregation realization (bucketed "
                         "blocked-ELL kernel dispatch vs COO scatter-add); "
                         "alias for --set schedule.agg_backend=B")
    ap.add_argument("--overlap", dest="overlap", action="store_true",
                    default=None,
                    help="with --gcn: force two-phase wire/compute overlap "
                         "(default: on for hierarchical, off for flat)")
    ap.add_argument("--no-overlap", dest="overlap", action="store_false",
                    help="with --gcn: force the sequential parity schedule")
    ap.add_argument("--scale", type=int, default=None,
                    help="with --gcn: R-MAT scale of the stand-in graph "
                         "(base spec: 13); alias for --set graph.scale=N")
    ap.add_argument("--chips", type=int, default=0,
                    help="with --gcn: worker count (0 = full production "
                         "mesh; small values give a fast CI-sized dry-run)")
    ap.add_argument("--assert-overlap", action="store_true",
                    help="with --gcn: exit non-zero unless the lowered HLO "
                         "issues the wire collectives before the "
                         "aggregation compute")
    ap.add_argument("--hlo-out", action="store_true")
    ap.add_argument("--out", default="",
                    help="artifact directory for the per-combo json/hlo "
                         f"records (default: {OUT_DIR}) — point scratch "
                         "runs at a tmp dir so ignored seed artifacts "
                         "stop reappearing in experiments/dryrun/")
    args = ap.parse_args()
    out_dir = Path(args.out) if args.out else None

    if args.gcn:
        nparts = args.chips or (512 if args.multi_pod else 256)
        spec = spec_from_args(
            args, base=gcn_base_spec(nparts, scale=args.scale or 13))
        # Label the production mesh only when the resolved spec still
        # targets it (a --spec/--set override of nparts wins over --chips).
        mesh_name = (("2x16x16" if args.multi_pod else "16x16")
                     if not args.chips and spec.partition.nparts == nparts
                     else None)
        rec = run_gcn_dryrun(spec, mesh_name=mesh_name,
                             assert_overlap=args.assert_overlap,
                             out_dir=out_dir)
        raise SystemExit(0 if rec["status"] == "ok" else 1)
    if args.all:
        results = []
        for a in ARCH_NAMES:
            for s in INPUT_SHAPES:
                results.append(run_one(a, s, args.multi_pod,
                                       hlo_out=args.hlo_out,
                                       out_dir=out_dir))
        ok = sum(r["status"] == "ok" for r in results)
        skip = sum(r["status"] == "skip" for r in results)
        err = sum(r["status"] == "error" for r in results)
        print(f"\n== dry-run summary: {ok} ok / {skip} skip / {err} error ==")
        raise SystemExit(1 if err else 0)
    if not (args.arch and args.shape):
        ap.error("need --arch and --shape (or --all / --gcn)")
    rec = run_one(args.arch, args.shape, args.multi_pod,
                  hlo_out=args.hlo_out, out_dir=out_dir)
    raise SystemExit(0 if rec["status"] in ("ok", "skip") else 1)


if __name__ == "__main__":
    main()
