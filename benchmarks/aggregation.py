"""Fig 8 analogue: aggregation-operator performance on a single CPU.

Compares realizations of the paper's `index_add`/SpMM stage on synthetic
R-MAT graphs of increasing size:

  vanilla   — scatter-add in edge order (PyG-baseline access pattern:
              random writes to dst rows),
  sorted    — scatter-add after sorting edges by destination (the paper's
              "clustering and sorting" step alone),
  clustered — dst-sorted segment accumulate (indices_are_sorted lets XLA
              use the contiguous-run path),
  ell       — max-degree padded ELL (dst-clustered gather + dense
              accumulate). On power-law graphs the padding blows up as
              rows x max_degree, so large scales report the slot count and
              skip the timing — the reason this layout never reached the
              training loop,
  bucketed  — the production layout: degree-bucketed blocked-ELL
              (growth-2 ladder, total padded slots < 2 x nnz) dispatched
              through the same segment-aggregate primitive the distributed
              trainer uses (XLA realization on CPU),
  kernel    — the same bucketed layout through the Pallas kernel in
              interpret mode (functional check only; the compiled kernel
              targets TPU), smallest scale only.

The paper reports 1.8-8.4x over PyG on Xeon; the reproduction target is
the *ordering* (bucketed/clustered >= sorted > vanilla), bounded bucketed
padding (<= 2 x nnz, asserted), and a growing advantage with size.

CLI:
  python benchmarks/aggregation.py [--quick] [--feat-dim F] [--out FILE]

``--out`` writes a machine-readable JSON artifact (rows + per-scale layout
accounting + acceptance booleans); CI archives it next to the comm-volume
sweep, and the checked-in copy lives at experiments/BENCH_aggregation.json.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph import rmat_graph
from repro.graph.structure import (
    bucketed_ell_from_csr,
    ell_from_csr,
    stack_bucketed_ells,
    transpose_csr,
)
from repro.kernels import bucketed_aggregate, device_bucketed
from repro.kernels.ref import seg_aggregate_ref

# Timing the full max-degree ELL needs a [rows, max_degree, F] gather in
# memory; past this many padded slots we report the blow-up instead.
ELL_TIMING_SLOT_BUDGET = 1 << 21


def _time(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def _bench_scale(scale: int, feat_dim: int, iters: int,
                 with_kernel: bool) -> tuple:
    """Rows + layout accounting for one R-MAT scale."""
    g = rmat_graph(scale, edge_factor=8, seed=scale).mean_normalized()
    n = g.num_nodes
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(n, feat_dim)).astype(np.float32))
    csr = g.csr_by_dst()
    deg = csr.row_degrees()
    max_deg = int(deg.max())

    # vanilla: edge-order scatter add (random dst writes)
    src = jnp.asarray(g.src, jnp.int32)
    dst = jnp.asarray(g.dst, jnp.int32)
    w = jnp.asarray(g.edge_weight)

    @jax.jit
    def vanilla(x, src=src, dst=dst, w=w, n=n):
        return jnp.zeros((n, x.shape[1]), x.dtype).at[dst].add(
            w[:, None] * x[src])

    # sorted: same scatter after dst-sort (paper §4 step 1)
    order = np.argsort(np.asarray(g.dst), kind="stable")
    src_s = jnp.asarray(g.src[order], jnp.int32)
    dst_s = jnp.asarray(g.dst[order], jnp.int32)
    w_s = jnp.asarray(g.edge_weight[order])

    @jax.jit
    def sorted_scatter(x, src=src_s, dst=dst_s, w=w_s, n=n):
        return jnp.zeros((n, x.shape[1]), x.dtype).at[dst].add(
            w[:, None] * x[src])

    # clustered: dst-sorted segment accumulate
    @jax.jit
    def clustered(x, src=src_s, dst=dst_s, w=w_s, n=n):
        return jax.ops.segment_sum(w[:, None] * x[src], dst,
                                   num_segments=n, indices_are_sorted=True)

    # bucketed: the trainer's hot path (degree-bucketed blocked-ELL through
    # the segment-aggregate primitive; ref/XLA realization on CPU)
    ell = bucketed_ell_from_csr(csr)
    ell_t = bucketed_ell_from_csr(transpose_csr(csr))
    dell = device_bucketed(stack_bucketed_ells([ell]), squeeze=True)
    dell_t = device_bucketed(stack_bucketed_ells([ell_t]), squeeze=True)
    # Device slots include the 8-row kernel alignment sliver; the < 2 x nnz
    # ladder guarantee (asserted below) is on the pre-alignment layout.
    layout_slots = ell.padded_slots
    bucketed_slots = sum(int(b.idx.shape[0]) * int(b.idx.shape[1])
                         for b in dell.buckets)

    @jax.jit
    def bucketed(x, dell=dell, dell_t=dell_t):
        return bucketed_aggregate(x, dell, dell_t, use_kernel=False)

    t_van = _time(vanilla, x, iters=iters)
    t_sort = _time(sorted_scatter, x, iters=iters)
    t_clu = _time(clustered, x, iters=iters)
    t_buck = _time(bucketed, x, iters=iters)

    maxpad_slots = n * max(max_deg, 1)
    rows = [
        {"name": f"aggregation_fig8/rmat{scale}/vanilla",
         "us_per_call": round(t_van, 1),
         "derived": f"edges={g.num_edges}"},
        {"name": f"aggregation_fig8/rmat{scale}/sorted",
         "us_per_call": round(t_sort, 1),
         "derived": f"speedup_vs_vanilla={t_van / t_sort:.2f}x"},
        {"name": f"aggregation_fig8/rmat{scale}/clustered_segment",
         "us_per_call": round(t_clu, 1),
         "derived": f"speedup_vs_vanilla={t_van / t_clu:.2f}x"},
    ]

    # ell (max-degree padding): time it only while the padded gather fits.
    t_ell = None
    if maxpad_slots <= ELL_TIMING_SLOT_BUDGET:
        eidx, ew, _ = ell_from_csr(csr)
        eidx, ew = jnp.asarray(eidx), jnp.asarray(ew)

        @jax.jit
        def ell_maxpad(x, idx=eidx, w=ew):
            return seg_aggregate_ref(x, idx, w)

        t_ell = _time(ell_maxpad, x, iters=iters)
        rows.append({
            "name": f"aggregation_fig8/rmat{scale}/ell",
            "us_per_call": round(t_ell, 1),
            "derived": f"padded_slots={maxpad_slots}"
                       f"({maxpad_slots / csr.nnz:.1f}x_nnz)"})
    else:
        rows.append({
            "name": f"aggregation_fig8/rmat{scale}/ell",
            "us_per_call": 0.0,
            "derived": f"skipped:padded_slots={maxpad_slots}"
                       f"({maxpad_slots / csr.nnz:.1f}x_nnz)"})

    rows.append({
        "name": f"aggregation_fig8/rmat{scale}/bucketed",
        "us_per_call": round(t_buck, 1),
        "derived": f"speedup_vs_vanilla={t_van / t_buck:.2f}x,"
                   f"padded_slots={bucketed_slots}"
                   f"({bucketed_slots / csr.nnz:.2f}x_nnz)"})

    t_kernel = None
    if with_kernel:
        @jax.jit
        def kernel(x, dell=dell, dell_t=dell_t):
            return bucketed_aggregate(x, dell, dell_t, use_kernel=True)

        t_kernel = _time(kernel, x, iters=1)
        # Every bucket runs the kernel (the wrapper pads unaligned shapes);
        # label whether it was compiled or interpreted.
        realized = ("pallas_mosaic" if jax.default_backend() == "tpu"
                    else "pallas_interpret(functional_check)")
        rows.append({
            "name": f"aggregation_fig8/rmat{scale}/kernel",
            "us_per_call": round(t_kernel, 1),
            "derived": realized})

    layout = {
        "nodes": n,
        "edges": int(csr.nnz),
        "max_degree": max_deg,
        "maxpad_slots": int(maxpad_slots),
        "layout_slots": int(layout_slots),
        "layout_padding_ratio": round(layout_slots / csr.nnz, 4),
        "bucketed_slots": int(bucketed_slots),
        "bucketed_padding_ratio": round(bucketed_slots / csr.nnz, 4),
        "buckets": [[int(b.idx.shape[1]), int(b.idx.shape[0])]
                    for b in dell.buckets],
        "us": {"vanilla": t_van, "sorted": t_sort, "clustered": t_clu,
               "ell": t_ell, "bucketed": t_buck, "kernel": t_kernel},
    }
    # Acceptance bound: the growth-2 ladder guarantees < 2 x nnz padding
    # pre row-alignment (the device slots add a bounded 8-row sliver per
    # bucket, reported above but not asserted — it depends on bucket count,
    # not the ladder).
    if layout_slots > 2 * csr.nnz:
        raise AssertionError(
            f"rmat{scale}: bucketed layout slots {layout_slots} > "
            f"2 x nnz ({2 * csr.nnz})")
    return rows, layout


def run(feat_dim: int = 128, scales=(10, 12, 14), quick: bool = False):
    rows, _ = run_with_artifact(feat_dim, scales, quick)
    return rows


def run_with_artifact(feat_dim: int = 128, scales=(10, 12, 14),
                      quick: bool = False):
    if quick:
        scales = tuple(scales[:2])
    iters = 2 if quick else 5
    rows, layouts = [], {}
    for i, scale in enumerate(scales):
        # Interpret-mode Pallas is far too slow beyond the smallest scale.
        r, layout = _bench_scale(scale, feat_dim, iters, with_kernel=(i == 0))
        rows.extend(r)
        layouts[f"rmat{scale}"] = layout
    xla_keys = ("vanilla", "sorted", "clustered", "ell")
    artifact = {
        "benchmark": "aggregation_fig8",
        "feat_dim": feat_dim,
        "scales": list(scales),
        "quick": quick,
        "rows": rows,
        "layouts": layouts,
        "acceptance": {
            "bucketed_slots_le_2x_nnz": all(
                l["layout_padding_ratio"] <= 2.0 for l in layouts.values()),
            "bucketed_fastest_cpu": all(
                all(l["us"][k] is None or l["us"]["bucketed"] <= l["us"][k]
                    for k in xla_keys)
                for l in layouts.values()),
        },
    }
    return rows, artifact


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--quick", action="store_true",
                    help="reduced scales/iters (the CI bench job)")
    ap.add_argument("--feat-dim", type=int, default=128)
    ap.add_argument("--out", type=str, default=None,
                    help="write the JSON artifact here")
    args = ap.parse_args()
    rows, artifact = run_with_artifact(args.feat_dim, quick=args.quick)
    print("name,us_per_call,derived")
    for row in rows:
        print(f"{row['name']},{row['us_per_call']},{row['derived']}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
