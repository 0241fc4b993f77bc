"""Training launcher.

Two modes, mirroring the two systems in this repo:

* ``--gcn``: the paper's distributed full-batch GCN training, driven by a
  declarative :class:`repro.run.RunSpec` (``--spec file.json`` +
  ``--set section.field=value``). The historical explicit flags
  (``--nparts``, ``--bits``, ``--groups``, per-stage ``--intra-bits`` /
  ``--inter-bits`` / ``--intra-cd`` / ``--inter-cd``, ...) keep working as
  deprecation aliases onto the same spec paths.
* ``--arch``: transformer LM training on synthetic tokens for any assigned
  architecture (smoke-scale by default; production shapes are exercised by
  the dry-run, not executed on CPU).

Examples:
  python -m repro.launch.train --gcn --nparts 8 --bits 2 --epochs 30
  python -m repro.launch.train --gcn --spec specs/hier_int2_inter.json \
      --set exec.epochs=100 --set schedule.inter_cd=4
  python -m repro.launch.train --arch tinyllama-1.1b --smoke --steps 5
"""

from __future__ import annotations

import argparse
import time


def run_gcn(args):
    from repro.run import build_session, spec_from_args

    spec = spec_from_args(args)
    print(f"spec: {spec.describe()}")
    session = build_session(spec)
    g, s = session.graph, session.comm_stats()
    print(f"graph: {g.num_nodes} nodes, {g.num_edges} edges, "
          f"{spec.graph.classes} classes")
    print(f"partition comm volumes: vanilla={s.vanilla} pre={s.pre} "
          f"post={s.post} hybrid={s.hybrid} (selected={s.selected})")
    p = session.partition_stats()
    print(f"partition health: cut_fraction={p['cut_fraction']:.4f} "
          f"load_imbalance={p['load_imbalance']:.3f} "
          f"agg_slot_imbalance={p['agg_slot_imbalance']:.3f} "
          f"agg_stacked_slots={p['agg_stacked_slots']} "
          f"(refine={spec.partition.refine})")
    print(f"exchange schedule: {session.schedule.describe()}")
    t0 = time.time()
    try:
        hist = session.fit(ckpt_dir=getattr(args, "ckpt_dir", None),
                           resume=bool(getattr(args, "resume", False)))
        dt = time.time() - t0
        for h in hist:
            print(f"epoch {h['epoch']:4d} loss {h['loss']:.4f} "
                  f"train_acc {h['train_acc']:.4f} eval_acc {h.get('eval_acc', 0):.4f}")
        epochs = spec.exec.epochs
        print(f"trained {epochs} epochs in {dt:.1f}s "
              f"({dt / max(epochs, 1) * 1e3:.1f} ms/epoch)")
        if spec.exec.mode == "multiproc":
            smry = session.trainer.summary()
            rss = [r["rss_after_slices"] for r in smry.get("ranks", [])]
            print(f"multiproc: {smry['nprocs']} procs on the "
                  f"{smry['platform'].upper()}, shared store "
                  f"{smry['store_bytes'] / 1e6:.1f} MB (one copy), "
                  f"rank RSS {[round(r / 1e6, 1) for r in rss]} MB")
    finally:
        session.close()


def run_lm(args):
    import jax
    from repro.configs import get_arch, get_smoke_arch
    from repro.models import init_params, train_step
    from repro.optim import adamw_init

    seed = args.seed if args.seed is not None else 0
    cfg = get_smoke_arch(args.arch) if args.smoke else get_arch(args.arch)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    opt = adamw_init(params)
    step = jax.jit(lambda p, o, b: train_step(p, o, b, cfg,
                                              num_microbatches=args.microbatches))
    key = jax.random.PRNGKey(seed + 1)
    b, s = args.batch, args.seq_len
    for i in range(args.steps):
        key, sub = jax.random.split(key)
        batch = {"tokens": jax.random.randint(sub, (b, s), 0, cfg.vocab_size)}
        if cfg.family == "audio":
            batch["frames"] = jax.random.normal(sub, (b, cfg.enc_frames, cfg.d_model))
        if cfg.family == "vlm":
            batch["patches"] = jax.random.normal(sub, (b, cfg.vision_patches, cfg.d_model))
        t0 = time.time()
        params, opt, loss = step(params, opt, batch)
        print(f"step {i}: loss {float(loss):.4f} ({time.time() - t0:.2f}s)")


def main():
    from repro.run import add_spec_args

    ap = argparse.ArgumentParser()
    ap.add_argument("--gcn", action="store_true")
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=None)
    # The declarative entry point (the canonical way to configure --gcn).
    add_spec_args(ap)
    # Legacy gcn flags: deprecation aliases onto RunSpec paths (see
    # repro.run.cli.LEGACY_ALIASES). default=None = "not passed"; only
    # user-supplied values override the spec.
    ap.add_argument("--nparts", type=int, default=None,
                    help="alias for --set partition.nparts=N")
    ap.add_argument("--nodes", type=int, default=None,
                    help="alias for --set graph.nodes=N")
    ap.add_argument("--classes", type=int, default=None,
                    help="alias for --set graph.classes=N")
    ap.add_argument("--degree", type=float, default=None,
                    help="alias for --set graph.avg_degree=D")
    ap.add_argument("--feat-dim", type=int, default=None,
                    help="alias for --set graph.feat_dim=F")
    ap.add_argument("--hidden", type=int, default=None,
                    help="alias for --set model.hidden_dim=H")
    ap.add_argument("--model", default=None,
                    choices=["gcn", "sage", "gin", "gat"],
                    help="alias for --set model.model=NAME")
    ap.add_argument("--strategy", default=None,
                    choices=["hybrid", "pre", "post", "vanilla"],
                    help="alias for --set partition.strategy=NAME")
    ap.add_argument("--bits", type=int, default=None, choices=[0, 2, 4, 8],
                    help="alias for --set schedule.bits=B")
    ap.add_argument("--lp", dest="lp", action="store_true", default=None,
                    help="alias for --set model.label_prop=true")
    ap.add_argument("--no-lp", dest="lp", action="store_false",
                    help="alias for --set model.label_prop=false")
    ap.add_argument("--cd", type=int, default=None,
                    help="delayed-comm period (DistGNN baseline; 1=sync); "
                         "alias for --set schedule.cd=N")
    ap.add_argument("--agg-backend", default=None, choices=["coo", "ell"],
                    help="aggregation realization (bucketed blocked-ELL "
                         "kernel dispatch vs COO scatter-add parity "
                         "fallback); alias for --set schedule.agg_backend=B")
    ap.add_argument("--groups", type=int, default=None,
                    help="num_groups for the hierarchical two-level "
                         "exchange (0 = flat; group_size auto-derives as "
                         "nparts/groups); alias for --set partition.groups=G")
    ap.add_argument("--intra-bits", type=int, default=None,
                    choices=[0, 2, 4, 8],
                    help="override the intra-group stage's wire bits; "
                         "alias for --set schedule.intra_bits=B")
    ap.add_argument("--inter-bits", type=int, default=None,
                    choices=[0, 2, 4, 8],
                    help="override the inter-group stage's wire bits "
                         "(hierarchical default: Int2; 0 pins fp32); "
                         "alias for --set schedule.inter_bits=B")
    ap.add_argument("--intra-cd", type=int, default=None,
                    help="override the intra-group stage's refresh period; "
                         "alias for --set schedule.intra_cd=N")
    ap.add_argument("--inter-cd", type=int, default=None,
                    help="override the inter-group stage's refresh period "
                         "(stale inter, fresh intra); alias for "
                         "--set schedule.inter_cd=N")
    ap.add_argument("--overlap", dest="overlap", action="store_true",
                    default=None,
                    help="issue the exchange wire before the local "
                         "aggregation (two-phase LayerProgram; default: on "
                         "for hierarchical schedules, off for flat); "
                         "alias for --set schedule.overlap=true")
    ap.add_argument("--no-overlap", dest="overlap", action="store_false",
                    help="force the sequential parity schedule; "
                         "alias for --set schedule.overlap=false")
    ap.add_argument("--epochs", type=int, default=None,
                    help="alias for --set exec.epochs=N")
    ap.add_argument("--lr", type=float, default=None,
                    help="alias for --set exec.lr=LR")
    ap.add_argument("--mode", default=None,
                    choices=["vmap", "shard_map", "multiproc"],
                    help="alias for --set exec.mode=MODE (multiproc spawns "
                         "one pinned OS process per partition over a "
                         "shared-memory graph store)")
    ap.add_argument("--nprocs", type=int, default=None,
                    help="multiproc worker count (must equal "
                         "partition.nparts; 0/omitted = nparts); alias for "
                         "--set exec.nprocs=N")
    # Fault tolerance (checkpointing + multiproc supervision)
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="snapshot period in epochs (0 = off); alias for "
                         "--set exec.ckpt_every=N")
    ap.add_argument("--max-restarts", type=int, default=None,
                    help="multiproc worker respawns before a failing run "
                         "degrades to a clean abort; alias for "
                         "--set exec.max_restarts=N")
    ap.add_argument("--heartbeat-s", dest="heartbeat_s", type=float,
                    default=None,
                    help="stale-heartbeat deadline for declaring a live "
                         "multiproc worker hung (0 = off); alias for "
                         "--set exec.heartbeat_s=S")
    ap.add_argument("--ckpt-dir", type=str, default=None,
                    help="checkpoint directory: turns on periodic atomic "
                         "snapshots (per-rank subdirs under multiproc) and "
                         "enables --resume")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest valid checkpoint from "
                         "--ckpt-dir before training (the resumed run "
                         "reproduces the uninterrupted loss trajectory)")
    # lm options
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.gcn:
        run_gcn(args)
    elif args.arch:
        run_lm(args)
    else:
        ap.error("choose --gcn or --arch <name>")


if __name__ == "__main__":
    main()
