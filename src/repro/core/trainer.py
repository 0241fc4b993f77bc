"""Full-batch GCN training — single-device and distributed (Fig 2).

The distributed step runs per-worker code written against a named axis
(``psum`` / ``all_to_all``) and executes it two ways:

* ``mode="vmap"``   — P virtual workers on one device (numerically identical
  collectives via vmap's named-axis support; used by tests and the CPU
  container),
* ``mode="shard_map"`` — P real devices on a mesh (production path; the
  dry-run harness lowers this on the 512-device host mesh).

One training step per epoch (full batch): masked-LP feature assembly →
per-layer [LayerNorm → dropout → local aggregation ∥ halo exchange
(optionally Int2-quantized) → UPDATE] → masked CE loss → psum(grads) →
AdamW. Synchronous, fresh boundary nodes every epoch (Table 1).

The DistGNN-style delayed-communication baseline (cd-N) reuses stale halo
buffers for N-1 epochs — the paper's ABCI comparison target.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import model as M
from repro.core.exchange import ExchangeSchedule
from repro.core.halo import (
    DeviceHaloPlan,
    DeviceHierPlan,
    stack_halo_plan,
    stack_hier_plan,
)
from repro.core.layers import gat_aggregate, gat_attention
from repro.graph.remote import (
    HierPartitionedGraph,
    build_halo_plan,
    build_hier_halo_plan,
)
from repro.graph.structure import (
    Graph,
    bucketed_ell_from_csr,
    ell_from_csr,
    reverse_slot_index,
    stack_bucketed_ells,
    transpose_csr,
)
from repro.kernels import aggregate as kernel_aggregate
from repro.kernels import bucketed_aggregate, device_bucketed
from repro.kernels.seg_aggregate import DeviceBucketedEll, bucketed_slots
from repro.kernels.ref import seg_aggregate_ref
from repro.optim import adamw_init, adamw_update
from repro.utils import trace


# --------------------------------------------------------------------------
# Single-device path (full-graph ELL aggregation; paper Fig 8 operator level)
# --------------------------------------------------------------------------


class SingleGraphData(NamedTuple):
    x: jax.Array
    labels: jax.Array
    train_mask: jax.Array
    eval_mask: jax.Array
    ell_idx: jax.Array
    ell_w: jax.Array
    ell_valid: jax.Array
    # The shared degree-bucketed layout (fwd + reverse-graph for the VJP):
    # GCN/SAGE/GIN aggregation and GAT attention both consume it, so the
    # layout is built once at preprocessing time.
    ell: Optional[DeviceBucketedEll] = None
    ell_t: Optional[DeviceBucketedEll] = None
    # Each reverse slot's forward slot (graph.structure.reverse_slot_index):
    # GAT's attention weights reach the transposed pass through it.
    ell_t_slot: Optional[Tuple[jax.Array, ...]] = None


def prepare_single(g: Graph, x: np.ndarray, eval_mask: Optional[np.ndarray] = None,
                   norm: str = "mean",
                   layouts: Tuple[str, ...] = ("dense", "bucketed")
                   ) -> SingleGraphData:
    """``layouts`` trims the prepared neighbour layouts: "dense" is the
    max-degree ELL (seg_aggregate / use_kernel paths; its padding blows up
    as rows x max_degree on power-law graphs), "bucketed" the shared
    degree-bucketed layout (GAT path). The default builds both for
    API compatibility; ``train_gcn_single`` picks per model."""
    gn = g.gcn_normalized() if norm == "gcn" else g.mean_normalized()
    csr = gn.csr_by_dst()
    train = g.train_mask if g.train_mask is not None else np.ones(g.num_nodes, bool)
    if eval_mask is None:
        eval_mask = ~train
    if "dense" in layouts:
        idx, w, valid = ell_from_csr(csr)
    else:
        idx = np.zeros((g.num_nodes, 1), np.int32)
        w = np.zeros((g.num_nodes, 1), np.float32)
        valid = np.zeros((g.num_nodes, 1), bool)
    ell = ell_t = slot_t = None
    if "bucketed" in layouts:
        stacked = stack_bucketed_ells([bucketed_ell_from_csr(csr)])
        stacked_t = stack_bucketed_ells([bucketed_ell_from_csr(transpose_csr(csr))])
        ell = device_bucketed(stacked, squeeze=True)
        ell_t = device_bucketed(stacked_t, squeeze=True)
        slot_t = tuple(jnp.asarray(a[0])
                       for a in reverse_slot_index(stacked, stacked_t))
    return SingleGraphData(
        x=jnp.asarray(x),
        labels=jnp.asarray(g.labels, jnp.int32),
        train_mask=jnp.asarray(train),
        eval_mask=jnp.asarray(eval_mask),
        ell_idx=jnp.asarray(idx, jnp.int32),
        ell_w=jnp.asarray(w),
        ell_valid=jnp.asarray(valid),
        ell=ell,
        ell_t=ell_t,
        ell_t_slot=slot_t,
    )


def make_single_agg_fn(data: SingleGraphData, use_kernel: bool = False):
    def agg_fn(l: int, h: jax.Array, scores=None) -> jax.Array:
        if scores is not None:  # GAT: h is W x, weighted by attention
            if data.ell is not None:
                return gat_attention(h, *scores, data.ell, data.ell_t,
                                     data.ell_t_slot)
            return gat_aggregate(h, *scores, data.ell_idx, data.ell_valid)
        if use_kernel:
            return kernel_aggregate(h, data.ell_idx, data.ell_w)
        return seg_aggregate_ref(h, data.ell_idx, data.ell_w)
    return agg_fn


@functools.partial(jax.jit, static_argnames=("cfg", "lr"))
def single_train_step(params, opt_state, cfg: M.GCNConfig, data: SingleGraphData,
                      key: jax.Array, lr: float = 0.01):
    kp, kd = jax.random.split(key)
    prop_mask, loss_mask = M.lp_masks(kp, data.train_mask, cfg.lp_rate)
    if not cfg.label_prop:
        prop_mask = jnp.zeros_like(prop_mask)
        loss_mask = data.train_mask

    def loss_fn(p):
        agg = make_single_agg_fn(data)
        logits = M.forward(p, cfg, data.x, data.labels, prop_mask, agg,
                           train=True, dropout_key=kd)
        ls, correct, cnt = M.loss_and_metrics(logits, data.labels, loss_mask)
        return ls / jnp.maximum(cnt, 1.0), (correct, cnt)

    (loss, (correct, cnt)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    params, opt_state = adamw_update(grads, opt_state, params, lr)
    return params, opt_state, {"loss": loss, "train_acc": correct / jnp.maximum(cnt, 1.0)}


@functools.partial(jax.jit, static_argnames=("cfg",))
def single_eval(params, cfg: M.GCNConfig, data: SingleGraphData):
    # Inference-time LP: propagate all train labels, score on eval nodes.
    prop = data.train_mask if cfg.label_prop else jnp.zeros_like(data.train_mask)
    agg = make_single_agg_fn(data)
    logits = M.forward(params, cfg, data.x, data.labels, prop, agg, train=False)
    _, correct, cnt = M.loss_and_metrics(logits, data.labels, data.eval_mask)
    return correct / jnp.maximum(cnt, 1.0)


def train_gcn_single(g: Graph, x: np.ndarray, cfg: M.GCNConfig, epochs: int,
                     lr: float = 0.01, seed: int = 0, log_every: int = 0):
    data = prepare_single(
        g, x, layouts=("bucketed",) if cfg.model == "gat" else ("dense",))
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    opt_state = adamw_init(params)
    history = []
    for e in range(epochs):
        params, opt_state, m = single_train_step(
            params, opt_state, cfg, data, jax.random.PRNGKey(seed * 100003 + e), lr)
        if log_every and (e % log_every == 0 or e == epochs - 1):
            acc = single_eval(params, cfg, data)
            history.append({"epoch": e, "loss": float(m["loss"]), "eval_acc": float(acc)})
    return params, history


# --------------------------------------------------------------------------
# Distributed path (shard_map / vmap over the worker axis)
# --------------------------------------------------------------------------


# Hierarchical schedules default the slow inter-group wire to Int2 when the
# base ``bits`` is fp32 (ROADMAP: the bits_ablation_stage convergence rows
# justify it). ``inter_bits=0`` opts a config back into the fp32 slow wire.
HIER_INTER_BITS_DEFAULT = 2


class WorkerData(NamedTuple):
    """Per-worker arrays; in the stacked form every field has leading dim P.

    Exactly one of ``plan`` (flat exchange) / ``hier_plan`` (two-level
    exchange) is set; ``None`` fields carry no leaves, so vmap/shard_map
    tree-mapping skips them.
    """

    x: jax.Array           # [M, F] padded owned features
    labels: jax.Array      # [M]
    train_mask: jax.Array  # [M] (False on padding)
    eval_mask: jax.Array   # [M]
    owned_mask: jax.Array  # [M]
    coo_src: jax.Array     # [nnz] local COO aggregation graph
    coo_dst: jax.Array     # [nnz]
    coo_w: jax.Array       # [nnz] (0 on padding)
    plan: Optional[DeviceHaloPlan] = None
    hier_plan: Optional[DeviceHierPlan] = None
    # Degree-bucketed blocked-ELL layout of the local graph (fwd + the
    # reverse-graph layout driving the kernel's custom VJP) — the "ell"
    # aggregation backend's hot path; the COO triple above is its parity
    # fallback.
    ell: Optional[DeviceBucketedEll] = None
    ell_t: Optional[DeviceBucketedEll] = None
    # GAT only: each reverse slot's forward slot, one [.., Rb, K] int32 per
    # reverse bucket (graph.structure.reverse_slot_index).
    ell_t_slot: Optional[Tuple[jax.Array, ...]] = None


@dataclass(frozen=True)
class DistConfig:
    nparts: int
    axis_name: str = "workers"
    bits: int = 0            # wire format: 0=fp32, 2=Int2 (paper), 4, 8
    cd: int = 1              # delayed-comm period (DistGNN baseline; 1 = sync)
    lr: float = 0.01
    # Aggregation realization: "ell" (default) dispatches the local graph
    # and the exchange recv scatter through the degree-bucketed blocked-ELL
    # segment-aggregate kernel (paper §4); "coo" keeps the naive edge-order
    # scatter-add as a parity fallback.
    agg_backend: str = "ell"
    # Two-level (hierarchical) exchange: nparts = num_groups * group_size
    # workers on nested axes (group_axis outer, node_axis inner). 0 = flat.
    num_groups: int = 0
    group_size: int = 0
    node_axis: str = "node"
    group_axis: str = "group"
    # Per-stage overrides for the hierarchical exchange schedule; None means
    # inherit ``bits`` / ``cd`` — EXCEPT the inter wire, whose default is
    # Int2 when ``bits`` is fp32 (HIER_INTER_BITS_DEFAULT): the per-stage
    # convergence evidence (benchmarks/bits_ablation.py
    # ``bits_ablation_stage/`` rows) shows Int2-inter + fp32-intra matches
    # fp32-everywhere accuracy with ~13x smaller inter bytes, so the slow
    # wire ships quantized unless explicitly pinned (inter_bits=0 is the
    # fp32 slow wire). inter_cd=4 + cd=1 refreshes the inter-group buffer
    # every 4 epochs while the intra level stays fresh (stale inter, fresh
    # intra — the paper-faithful configuration).
    intra_bits: Optional[int] = None
    inter_bits: Optional[int] = None
    intra_cd: Optional[int] = None
    inter_cd: Optional[int] = None
    # Two-phase layer scheduling: issue the exchange wire before the local
    # bucketed aggregation so XLA can hide the in-flight collectives behind
    # the hot compute. None = topology default (hierarchical schedules
    # overlap, flat stays sequential); True/False force it. Overlap changes
    # op order only, never values.
    overlap: Optional[bool] = None

    def __post_init__(self):
        if self.agg_backend not in ("coo", "ell"):
            raise ValueError(
                f"agg_backend must be 'coo' or 'ell', got {self.agg_backend!r}")
        if self.num_groups or self.group_size:
            if self.num_groups < 1 or self.group_size < 1:
                raise ValueError(
                    "hierarchical DistConfig needs both num_groups >= 1 and "
                    f"group_size >= 1, got {self.num_groups}x{self.group_size}")
            if self.num_groups * self.group_size != self.nparts:
                raise ValueError(
                    f"num_groups * group_size ({self.num_groups}x"
                    f"{self.group_size}) must equal nparts ({self.nparts})")
        elif any(v is not None for v in (self.intra_bits, self.inter_bits,
                                         self.intra_cd, self.inter_cd)):
            raise ValueError(
                "intra_/inter_ stage overrides need a hierarchical "
                "DistConfig (num_groups/group_size)")
        self.schedule()  # validate bits/cd via StageSpec

    @property
    def hierarchical(self) -> bool:
        # num_groups=1 is the degenerate-but-valid G=1 endpoint of a G x W
        # sweep: the inter level is an identity exchange over a size-1 axis.
        return self.num_groups >= 1 and self.group_size >= 1

    def schedule(self) -> ExchangeSchedule:
        """The composable exchange schedule this config describes."""
        if self.hierarchical:
            pick = lambda override, default: default if override is None else override
            # Quantized slow wire by default: with fp32 base bits the inter
            # stage still ships Int2 (the bits_ablation_stage evidence).
            inter_default = self.bits or HIER_INTER_BITS_DEFAULT
            return ExchangeSchedule.hierarchical(
                self.num_groups, self.group_size,
                intra_bits=pick(self.intra_bits, self.bits),
                inter_bits=pick(self.inter_bits, inter_default),
                intra_cd=pick(self.intra_cd, self.cd),
                inter_cd=pick(self.inter_cd, self.cd),
                node_axis=self.node_axis, group_axis=self.group_axis,
                overlap=self.overlap)
        return ExchangeSchedule.flat(self.nparts, bits=self.bits, cd=self.cd,
                                     axis_name=self.axis_name,
                                     overlap=self.overlap)

    def sync_fp32(self) -> "DistConfig":
        """This config with every stage forced to fresh fp32 (eval wire).

        The hierarchical inter stage needs an explicit ``inter_bits=0``
        pin — leaving it None would fall back to the Int2 default."""
        return dataclasses.replace(
            self, bits=0, cd=1,
            intra_bits=None, inter_bits=0 if self.hierarchical else None,
            intra_cd=None, inter_cd=None)

    @property
    def psum_axes(self):
        """Axis name(s) spanning all workers, for grad/metric reductions."""
        if self.hierarchical:
            return (self.node_axis, self.group_axis)
        return self.axis_name


class HostWorkerData(NamedTuple):
    """Partition-time worker arrays *before* device placement: the pure
    numpy product of the build (padded per-partition arrays stacked on the
    worker axis, stacked bucketed-ELL tuples, host halo plans). The
    in-process backends lift it onto the device via
    :func:`_lift_worker_data`; the multiproc runtime instead publishes it
    byte-for-byte through the shared-memory store and each rank
    device-copies only its own slice."""

    x: np.ndarray            # [P, M, F] f32
    labels: np.ndarray       # [P, M] i32
    train_mask: np.ndarray   # [P, M] bool
    eval_mask: np.ndarray    # [P, M] bool
    owned_mask: np.ndarray   # [P, M] bool
    coo_src: np.ndarray      # [P, nnz_max] i64
    coo_dst: np.ndarray      # [P, nnz_max] i64
    coo_w: np.ndarray        # [P, nnz_max] f32
    ell_stacked: list        # stack_bucketed_ells output (fwd)
    ell_t_stacked: list      # stack_bucketed_ells output (reverse graph)
    plan: Optional[object]   # graph.remote.HaloPlan (flat) or None
    hier_plan: Optional[object]  # graph.remote.HierHaloPlan or None
    max_owned: int
    ell_t_slot: Optional[list] = None  # reverse_slot_index output (GAT)


def prepare_distributed_host(
    g: Graph,
    x: np.ndarray,
    pg,
    eval_mask: Optional[np.ndarray] = None,
    attention: bool = False,
) -> HostWorkerData:
    """Pad per-partition arrays to common shapes and stack on the worker
    axis — the host (numpy-only) half of :func:`prepare_distributed`.
    ``attention`` also builds each reverse slot's forward slot, which an
    aggregation whose weights the layer computes (GAT) needs.

    ``g`` must already carry edge weights (use gcn_normalized/mean_normalized
    *before* partitioning so pre-aggregation applies source-side weights).
    ``pg`` may be a flat ``PartitionedGraph`` (flat plan) or a
    ``HierPartitionedGraph`` (two-level plan; ``hier_plan`` is set instead
    of ``plan``).
    """
    P = pg.nparts
    M_ = pg.max_owned
    F = x.shape[1]
    train = g.train_mask if g.train_mask is not None else np.ones(g.num_nodes, bool)
    if eval_mask is None:
        eval_mask = ~train
    labels = g.labels if g.labels is not None else np.zeros(g.num_nodes, np.int32)

    xs = np.zeros((P, M_, F), np.float32)
    ls = np.zeros((P, M_), np.int32)
    tm = np.zeros((P, M_), bool)
    em = np.zeros((P, M_), bool)
    om = np.zeros((P, M_), bool)
    nnz_max = max(max(c.nnz for c in pg.local_csr), 1)
    cs = np.zeros((P, nnz_max), np.int64)
    cd_ = np.zeros((P, nnz_max), np.int64)
    cw = np.zeros((P, nnz_max), np.float32)
    for p in range(P):
        o = pg.owned[p]
        n = len(o)
        xs[p, :n] = x[o]
        ls[p, :n] = labels[o]
        tm[p, :n] = train[o]
        em[p, :n] = eval_mask[o]
        om[p, :n] = True
        c = pg.local_csr[p]
        dst = np.repeat(np.arange(c.num_rows), np.diff(c.indptr))
        cs[p, :c.nnz] = c.indices
        cd_[p, :c.nnz] = dst
        cw[p, :c.nnz] = c.weights

    # Degree-bucketed blocked-ELL layouts, fixed at partition time (fwd +
    # reverse-graph for the custom VJP), padded to common shapes over P.
    base = pg.base if isinstance(pg, HierPartitionedGraph) else pg
    local_ell = base.local_ell or [bucketed_ell_from_csr(c)
                                   for c in pg.local_csr]
    local_ell_t = base.local_ell_t or [
        bucketed_ell_from_csr(transpose_csr(c)) for c in pg.local_csr]

    ell_stacked = stack_bucketed_ells(local_ell)
    ell_t_stacked = stack_bucketed_ells(local_ell_t)
    common = dict(
        x=xs, labels=ls, train_mask=tm, eval_mask=em, owned_mask=om,
        coo_src=cs, coo_dst=cd_, coo_w=cw,
        ell_stacked=ell_stacked, ell_t_stacked=ell_t_stacked,
        max_owned=M_,
        ell_t_slot=(reverse_slot_index(ell_stacked, ell_t_stacked)
                    if attention else None),
    )
    if isinstance(pg, HierPartitionedGraph):
        # build_hier_halo_plan already pads both levels to quant row groups.
        return HostWorkerData(**common, plan=None,
                              hier_plan=build_hier_halo_plan(pg))
    # Pad wire rows per pair to a multiple of the quant row group (4).
    R = pg.stats.padded_rows_per_pair
    R = max(4, (R + 3) // 4 * 4)
    return HostWorkerData(**common, plan=build_halo_plan(pg, rows_per_pair=R),
                          hier_plan=None)


def _lift_worker_data(hwd: HostWorkerData) -> WorkerData:
    """Device-materialize a HostWorkerData for the in-process backends
    (stacked over the worker axis; vmap/shard_map slice per worker)."""
    common = dict(
        x=jnp.asarray(hwd.x), labels=jnp.asarray(hwd.labels),
        train_mask=jnp.asarray(hwd.train_mask),
        eval_mask=jnp.asarray(hwd.eval_mask),
        owned_mask=jnp.asarray(hwd.owned_mask),
        coo_src=jnp.asarray(hwd.coo_src, jnp.int32),
        coo_dst=jnp.asarray(hwd.coo_dst, jnp.int32),
        coo_w=jnp.asarray(hwd.coo_w),
        ell=device_bucketed(hwd.ell_stacked),
        ell_t=device_bucketed(hwd.ell_t_stacked),
        ell_t_slot=(None if hwd.ell_t_slot is None else
                    tuple(jnp.asarray(a, jnp.int32) for a in hwd.ell_t_slot)),
    )
    if hwd.hier_plan is not None:
        return WorkerData(**common, hier_plan=stack_hier_plan(
            hwd.hier_plan, num_rows=hwd.max_owned))
    return WorkerData(**common, plan=stack_halo_plan(
        hwd.plan, num_rows=hwd.max_owned))


def prepare_distributed(
    g: Graph,
    x: np.ndarray,
    pg,
    eval_mask: Optional[np.ndarray] = None,
    norm_applied: bool = True,
) -> WorkerData:
    """:func:`prepare_distributed_host` + device lift (see both)."""
    return _lift_worker_data(prepare_distributed_host(g, x, pg, eval_mask))


def _local_aggregate(h: jax.Array, wd: WorkerData,
                     agg_backend: str = "coo") -> jax.Array:
    """Local (intra-partition) aggregation.

    ``"ell"`` runs the paper's operator: degree-bucketed blocked-ELL
    dispatch through the segment-aggregate kernel, with the custom VJP
    reusing the reverse-graph layout. ``"coo"`` is the PyG-baseline
    edge-order scatter-add kept for parity checks.
    """
    if agg_backend == "ell" and wd.ell is not None:
        return bucketed_aggregate(h, wd.ell, wd.ell_t)
    vals = wd.coo_w[:, None] * h[wd.coo_src]
    return jnp.zeros_like(h).at[wd.coo_dst].add(vals)


def _dist_forward(params, cfg: M.GCNConfig, dc: DistConfig, wd: WorkerData,
                  prop_mask, key, train: bool,
                  halo_cache=None, epoch=None, schedule=None):
    """Per-worker forward, sequenced through the schedule's LayerProgram:
    per layer, ``issue`` (launch overlapped wire pipelines, inter first) ->
    local bucketed aggregation -> ``finalize`` (scatter receives). The
    in-flight collectives carry no data dependency on the local aggregation
    and precede it in the trace, so XLA can overlap the slow wire with the
    hot compute; ``overlap=False`` stages run inside ``finalize``,
    reproducing the sequential trace bit-for-bit.

    ``halo_cache`` is the schedule-owned per-layer pytree (one stale recv
    buffer per delayed stage per layer); ``epoch`` drives each stage's
    refresh. With no cache provided the schedule runs fully sync (every
    stage fresh — the eval semantics). Returns (logits, new_halo_cache).
    """
    sched = schedule if schedule is not None else dc.schedule()
    if halo_cache is None and sched.uses_cache:
        sched = sched.as_sync()
    prog = sched.layer_program(wd, agg_backend=dc.agg_backend)
    new_cache: List = []

    def agg_fn_factory(dropout_key):
        def agg_fn(l: int, h: jax.Array, scores=None) -> jax.Array:
            if scores is not None:
                # GAT: attention over the local graph. One worker holds
                # every edge (the trainer refuses more), so no halo moves.
                return gat_attention(h, *scores, wd.ell, wd.ell_t,
                                     wd.ell_t_slot)
            kq = jax.random.fold_in(key, 7919 + l) if key is not None else None
            entry = halo_cache[l] if halo_cache is not None else None
            with jax.named_scope("exchange_issue"):
                inflight = prog.issue(h, kq, cache_entry=entry, epoch=epoch)
            with jax.named_scope("local"):
                local = _local_aggregate(h, wd, dc.agg_backend)
            with jax.named_scope("exchange_finalize"):
                agg, ne = prog.finalize(local, inflight)
            new_cache.append(ne)
            return agg
        return agg_fn

    kd = jax.random.fold_in(key, 104729) if key is not None else jax.random.PRNGKey(0)
    logits = M.forward(params, cfg, wd.x, wd.labels, prop_mask,
                       agg_fn_factory(kd), train=train, dropout_key=kd)
    return logits, new_cache


@jax.jit
def _count_nonzero(arrays) -> jax.Array:
    return sum(jnp.count_nonzero(a) for a in arrays)


def make_dist_train_step(cfg: M.GCNConfig, dc: DistConfig, use_cache: bool = False):
    """Returns worker_fn(params, wd, key[, cache, epoch]) -> (grads, metrics[, cache])."""
    schedule = dc.schedule()

    def worker_fn(params, wd: WorkerData, key, cache=None, epoch=None):
        if dc.hierarchical:
            widx = (jax.lax.axis_index(dc.group_axis) * dc.group_size
                    + jax.lax.axis_index(dc.node_axis))
        else:
            widx = jax.lax.axis_index(dc.axis_name)
        kw = jax.random.fold_in(key, widx)
        kp = jax.random.fold_in(kw, 1)
        prop_mask, loss_mask = M.lp_masks(kp, wd.train_mask, cfg.lp_rate)
        if not cfg.label_prop:
            prop_mask = jnp.zeros_like(prop_mask)
            loss_mask = wd.train_mask

        cache_out: List = []

        def loss_fn(p):
            logits, nc = _dist_forward(p, cfg, dc, wd, prop_mask, kw, True,
                                       halo_cache=cache, epoch=epoch,
                                       schedule=schedule)
            cache_out.extend(nc)
            ls, correct, cnt = M.loss_and_metrics(logits, wd.labels, loss_mask)
            # Global mean loss: psum both numerator and denominator.
            gls = jax.lax.psum(ls, dc.psum_axes)
            gcnt = jax.lax.psum(cnt, dc.psum_axes)
            return gls / jnp.maximum(gcnt, 1.0), (correct, cnt)

        (loss, (correct, cnt)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads = jax.lax.psum(grads, dc.psum_axes)
        gcorrect = jax.lax.psum(correct, dc.psum_axes)
        gcnt = jax.lax.psum(cnt, dc.psum_axes)
        metrics = {"loss": loss, "train_acc": gcorrect / jnp.maximum(gcnt, 1.0)}
        if use_cache:
            return grads, metrics, cache_out
        return grads, metrics

    return worker_fn


def make_dist_eval(cfg: M.GCNConfig, dc: DistConfig):
    def worker_fn(params, wd: WorkerData):
        prop = wd.train_mask if cfg.label_prop else jnp.zeros_like(wd.train_mask)
        # Eval always uses fp32 fresh halo (accuracy measurement).
        logits, _ = _dist_forward(params, cfg, dc.sync_fp32(), wd, prop,
                                  jax.random.PRNGKey(0), False)
        _, correct, cnt = M.loss_and_metrics(logits, wd.labels, wd.eval_mask)
        return (jax.lax.psum(correct, dc.psum_axes),
                jax.lax.psum(cnt, dc.psum_axes), logits)
    return worker_fn


class DistributedTrainer:
    """Drives the per-worker step via vmap (virtual) or shard_map (real mesh)."""

    def __init__(self, cfg: M.GCNConfig, dc: DistConfig, wd: WorkerData,
                 mode: str = "vmap", mesh=None, seed: int = 0):
        self.cfg, self.dc, self.wd, self.mode = cfg, dc, wd, mode
        self.schedule = dc.schedule()
        self.params = M.init_params(jax.random.PRNGKey(seed), cfg)
        self.opt_state = adamw_init(self.params)
        self.epoch = 0
        self.use_cache = self.schedule.uses_cache
        self._cache = None
        if dc.hierarchical and wd.hier_plan is None:
            raise ValueError(
                "hierarchical DistConfig needs WorkerData built from a "
                "HierPartitionedGraph (wd.hier_plan is None)")
        if not dc.hierarchical and wd.plan is None:
            raise ValueError(
                "WorkerData carries a hierarchical plan; set num_groups/"
                "group_size on DistConfig (wd.plan is None)")
        if dc.agg_backend == "ell" and wd.ell is None:
            raise ValueError(
                "agg_backend='ell' needs the bucketed layout in WorkerData "
                "(wd.ell is None — build it via prepare_distributed, or "
                "fall back to agg_backend='coo')")
        if cfg.model == "gat" and (dc.nparts != 1 or dc.hierarchical
                                   or mode != "shard_map"
                                   or wd.ell_t_slot is None):
            raise ValueError(
                "GAT trains as one worker under shard_map, with the reverse "
                "slot index in WorkerData (prepare_distributed_host(..., "
                "attention=True)); see RunSpec.validate_training")
        worker_step = make_dist_train_step(cfg, dc, use_cache=self.use_cache)
        worker_eval = make_dist_eval(cfg, dc)
        # (params, wd, key[, cache, epoch]): workers map their leading axis
        # of wd and cache; params/key/epoch are replicated.
        step_axes = ((None, 0, None, 0, None) if self.use_cache
                     else (None, 0, None))

        if dc.hierarchical and mode == "vmap":
            # Virtual two-level mesh: workers [P, ...] -> [G, W, ...] and a
            # nested vmap gives the (group_axis, node_axis) named axes.
            G, W = dc.num_groups, dc.group_size
            self.wd = jax.tree_util.tree_map(
                lambda a: a.reshape(G, W, *a.shape[1:]), wd)
            self._step = jax.jit(jax.vmap(jax.vmap(
                worker_step, axis_name=dc.node_axis, in_axes=step_axes),
                axis_name=dc.group_axis, in_axes=step_axes))
            self._eval = jax.jit(jax.vmap(jax.vmap(
                worker_eval, axis_name=dc.node_axis, in_axes=(None, 0)),
                axis_name=dc.group_axis, in_axes=(None, 0)))
        elif mode == "vmap":
            self._step = jax.jit(jax.vmap(
                worker_step, axis_name=dc.axis_name, in_axes=step_axes))
            self._eval = jax.jit(jax.vmap(
                worker_eval, axis_name=dc.axis_name, in_axes=(None, 0)))
        elif mode == "shard_map":
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            if mesh is None:
                raise ValueError("shard_map mode needs a mesh")
            self.mesh = mesh
            # Commit params/opt state to the replicated sharding the
            # updated params will carry from epoch 2 on (they mix with the
            # step's P()-replicated grads); host-resident epoch-1 params
            # would compile a second executable for the same step.
            _rep = NamedSharding(mesh, P())
            self.params = jax.device_put(self.params, _rep)
            self.opt_state = jax.device_put(self.opt_state, _rep)
            if dc.hierarchical:
                # Physical two-level mesh: leading worker dim sharded over
                # (group_axis, node_axis) — e.g. make_hier_worker_mesh.
                data_axes = (dc.group_axis, dc.node_axis)
            else:
                data_axes = dc.axis_name
            self._data_axes = data_axes
            # Worker-axis sharding of wd and the halo cache.
            self._data_sharding = NamedSharding(mesh, P(data_axes))
            # Each device holds its own worker's graph: placed once here,
            # the step never re-shards it from the default device.
            self.wd = jax.device_put(wd, self._data_sharding)
            spec_data = jax.tree_util.tree_map(lambda _: P(data_axes), wd)

            def _squeeze(tree):
                # shard_map keeps the sharded axis as size-1 (vmap strips it)
                return jax.tree_util.tree_map(lambda x: x[0], tree)

            if self.use_cache:
                # Per-stage halo cache: sharded over the worker axis exactly
                # like wd; structure is [layers][delayed stages].
                cache_spec = [tuple(P(data_axes)
                                    for _ in self.schedule.delayed_indices)
                              for _ in range(cfg.num_layers)]

                def step_sm(params, wdata, key, cache, epoch):
                    g, m, c = worker_step(params, _squeeze(wdata), key,
                                          _squeeze(cache), epoch)
                    # restore the size-1 sharded axis on the cache output
                    c = jax.tree_util.tree_map(lambda x: x[None], c)
                    return g, m, c

                self._step = jax.jit(jax.shard_map(
                    step_sm, mesh=mesh,
                    in_specs=(P(), spec_data, P(), cache_spec, P()),
                    out_specs=(P(), P(), cache_spec), check_vma=False))
            else:
                def step_sm(params, wdata, key):
                    return worker_step(params, _squeeze(wdata), key)

                self._step = jax.jit(jax.shard_map(
                    step_sm, mesh=mesh,
                    in_specs=(P(), spec_data, P()),
                    out_specs=(P(), P()), check_vma=False))

            def eval_sm(params, wdata):
                correct, cnt, logits = worker_eval(params, _squeeze(wdata))
                return correct, cnt, logits[None]

            self._eval = jax.jit(jax.shard_map(
                eval_sm, mesh=mesh,
                in_specs=(P(), spec_data),
                out_specs=(P(), P(), P(data_axes)), check_vma=False))
        else:
            raise ValueError(mode)
        self._agg_counts = self._kernel_counts()

    def _kernel_counts(self) -> Dict[str, int]:
        """Real edges (non-zero weights) and kernel slots (padding included)
        of one epoch's bucketed aggregations: the local graph and every
        receive scatter that has edges, each forward and backward in every
        layer; for GAT also ``sddmm.*``, the SDDMM pass over the forward
        layout in every layer. Empty when the step aggregates no bucketed
        layout."""
        if self.dc.agg_backend != "ell":
            return {}
        wd = self.wd
        plans = ([wd.plan] if wd.plan is not None
                 else [wd.hier_plan.intra, wd.hier_plan.inter])
        layouts = [wd.ell, wd.ell_t] + [
            lay for p in plans if p.recv_ell is not None
            for lay in (p.recv_ell, p.recv_ell_t)]
        if self.cfg.model == "gat":
            # Attention runs on the local graph only: the weighted kernel
            # over both layouts, and the SDDMM pass over the forward one.
            layouts = [wd.ell, wd.ell_t]
        workers = int(np.prod(wd.x.shape[:-2]))
        fold = self.mode == "vmap"
        slots = sum(bucketed_slots(lay, workers, fold) for lay in layouts)
        edges = int(_count_nonzero([b.w for lay in layouts for b in lay.buckets]))
        layers = self.cfg.num_layers
        counts = {"agg.edges": layers * edges, "agg.slots": layers * slots}
        if self.cfg.model == "gat":
            counts["sddmm.edges"] = layers * int(
                _count_nonzero([b.w for b in wd.ell.buckets]))
            counts["sddmm.slots"] = layers * bucketed_slots(wd.ell, workers, fold)
        return counts

    def _unreplicate(self, tree):
        if self.mode == "vmap":
            if self.dc.hierarchical:
                return jax.tree_util.tree_map(lambda x: x[0, 0], tree)
            return jax.tree_util.tree_map(lambda x: x[0], tree)
        return tree

    def _ensure_cache(self) -> None:
        """Lazily zero-fill the schedule-owned halo cache (epoch 0 always
        refreshes, so zeros are never read as data)."""
        if not self.use_cache or self._cache is not None:
            return
        # Layer l exchanges features of width dims()[l] (in_dim for the
        # first layer, hidden_dim after). Leading dims mirror wd's
        # stacked worker axes ((P,) flat, (G, W) nested vmap).
        dims = self.cfg.dims()[: self.cfg.num_layers]
        self._cache = self.schedule.init_cache(
            self.wd, dims, lead=self.wd.x.shape[:-2])
        if self.mode == "shard_map":
            # Commit the zero-fill to the same sharding the step
            # returns its cache with; otherwise epoch 2's differently
            # laid-out inputs compile a second executable.
            self._cache = jax.device_put(self._cache, self._data_sharding)

    def _step_args(self, key) -> tuple:
        """Assemble the _step argument tuple."""
        if not self.use_cache:
            return (self.params, self.wd, key)
        self._ensure_cache()
        return (self.params, self.wd, key, self._cache,
                jnp.asarray(self.epoch, jnp.int32))

    # -- checkpoint/resume -------------------------------------------------

    def train_state(self) -> Dict:
        """The resumable state pytree: params, opt state and (for delayed-
        comm schedules) the per-stage halo cache. Every epoch's RNG key is
        derived from the epoch number, so this plus ``epoch`` reproduces
        the uninterrupted trajectory bit-for-bit."""
        state = {"params": self.params, "opt_state": self.opt_state}
        if self.use_cache:
            self._ensure_cache()
            state["cache"] = self._cache
        return state

    def save_train_state(self, manager, meta: Optional[Dict] = None):
        """Snapshot into a :class:`repro.checkpoint.CheckpointManager`
        at step == epoch (atomic write + retention happen inside)."""
        m = dict(meta or {})
        m.setdefault("epoch", self.epoch)
        m.setdefault("mode", self.mode)
        return manager.save(self.train_state(), step=self.epoch, meta=m)

    def _state_shardings(self, template: Dict):
        """Sharding tree matching :meth:`train_state` so a shard_map
        restore lands arrays exactly where the step expects them (params/
        opt replicated, cache sharded over the worker axes) — otherwise
        the next epoch compiles a second executable."""
        if self.mode != "shard_map":
            return None
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        rep = NamedSharding(self.mesh, P())
        sh = {k: jax.tree_util.tree_map(lambda _: rep, v)
              for k, v in template.items() if k != "cache"}
        if "cache" in template:
            sh["cache"] = jax.tree_util.tree_map(lambda _: self._data_sharding,
                                                 template["cache"])
        return sh

    def restore_train_state_from(self, manager, step: Optional[int] = None
                                 ) -> int:
        """Restore from a manager's checkpoint (the newest valid one when
        ``step`` is None) and fast-forward ``self.epoch``; returns the
        restored step. Raises FileNotFoundError when nothing restorable
        exists."""
        from repro.checkpoint.ckpt import restore_train_state
        if step is None:
            valid = manager.valid_steps()
            if not valid:
                raise FileNotFoundError(
                    f"no valid checkpoint under {manager.dir}")
            step = valid[-1]
        template = self.train_state()
        state, manifest = restore_train_state(
            manager.path_for(step), template,
            shardings=self._state_shardings(template))
        self.params = state["params"]
        self.opt_state = state["opt_state"]
        if self.use_cache:
            self._cache = state["cache"]
        self.epoch = int(manifest.get("meta", {}).get("epoch",
                                                      manifest.get("step")
                                                      or step))
        return step

    def lower_step(self, key=None):
        """Lower (without running) one training step — the dry-run hook.

        The halo cache is passed as ShapeDtypeStructs so lowering a
        delayed-comm schedule at production scale never materializes the
        (potentially huge) stale buffers. Under shard_map they carry the
        cache's worker-axis sharding, so the compiled program is the one
        :meth:`train_epoch` dispatches.
        """
        key = key if key is not None else jax.random.PRNGKey(0)
        if self.use_cache and self._cache is None:
            dims = self.cfg.dims()[: self.cfg.num_layers]
            rows = self.schedule.cache_rows(self.wd)
            lead = self.wd.x.shape[:-2]
            sh = (self._data_sharding if self.mode == "shard_map"
                  else None)
            cache = [tuple(jax.ShapeDtypeStruct((*lead, r, f), jnp.float32,
                                                sharding=sh)
                           for r in rows) for f in dims]
            return self._step.lower(self.params, self.wd, key, cache,
                                    jnp.asarray(0, jnp.int32))
        return self._step.lower(*self._step_args(key))

    def train_epoch(self) -> Dict[str, float]:
        with trace.span("epoch"):
            with trace.span("step"):
                key = jax.random.PRNGKey(1000003 + self.epoch)
                args = self._step_args(key)
                if self.use_cache:
                    grads, metrics, cache = self._step(*args)
                    self._cache = cache
                else:
                    grads, metrics = self._step(*args)
                grads = self._unreplicate(grads)
                metrics = self._unreplicate(metrics)
            with trace.span("optimizer"):
                self.params, self.opt_state = adamw_update(
                    grads, self.opt_state, self.params, self.dc.lr)
            self.epoch += 1
            for name, n in self._agg_counts.items():
                trace.count(name, n)
            with trace.span("fetch"):
                return {k: float(v) for k, v in metrics.items()}

    def evaluate(self) -> float:
        correct, cnt, _ = self._eval(self.params, self.wd)
        correct, cnt = self._unreplicate((correct, cnt))
        return float(correct) / max(float(cnt), 1.0)

    def predict(self) -> jax.Array:
        """Eval-mode logits (fp32 sync halo, no dropout) of every worker's
        local rows, with wd's leading worker axes; the program is the one
        :meth:`evaluate` runs."""
        return self._eval(self.params, self.wd)[2]

    def fit(self, epochs: int, log_every: int = 0) -> List[Dict]:
        history = []
        for _ in range(epochs):
            m = self.train_epoch()
            if log_every and (self.epoch % log_every == 0 or self.epoch == epochs):
                m["eval_acc"] = self.evaluate()
                m["epoch"] = self.epoch
                history.append(m)
        return history
