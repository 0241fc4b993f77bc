"""Composable halo-exchange schedules, executed as two-phase LayerPrograms.

The paper's three contributions are orthogonal *axes* of the halo exchange,
not separate exchanges:

  * topology  — flat all_to_all over P workers, or hierarchical two-level
                (fast intra-group all_to_all + group-aggregated inter-group
                pipeline);
  * wire      — fp32, or stochastically quantized Int2/4/8 (§7.3);
  * caching   — sync (fresh halo every epoch) or DistGNN-style delayed
                communication that reuses a stale buffer for cd-1 epochs.

This module makes the composition explicit. An :class:`ExchangeSchedule` is
a sequence of :class:`StageSpec` stages — the single ``flat`` level, or
(``intra``, ``inter``) for the hierarchical exchange — and every stage
independently chooses its wire format (``bits``), caching policy (``cd``),
and *scheduling* (``overlap``), so e.g.

  * ``flat  × Int2 × delayed(3)``                       (DistGNN + quant),
  * ``intra: fp32 sync  |  inter: Int2 delayed(4)``     (fresh fast level,
    stale quantized slow level — the paper-faithful scaling configuration),
  * ``intra: Int2 sync  |  inter: Int2 sync``           (Int2 everywhere)

are all the same code path with different schedule entries.

The issue/finalize protocol (two-phase LayerProgram)
----------------------------------------------------

At 1000s of workers the epoch time is won by hiding the slow inter-group
wire behind the local bucketed aggregation (DistGNN's delayed-aggregation
overlap, MG-GCN's comm/compute pipelining). A layer's exchange therefore
executes in two phases compiled by :meth:`ExchangeSchedule.layer_program`:

  ``issue``     assembles every overlapped stage's send buffer and launches
                its full wire pipeline — the ``inter`` stage first, since
                its collectives are the slow ones — and applies the
                delayed-comm cache refresh to the in-flight receives;
  ``finalize``  scatters the received rows into the local accumulator.

The trainer sequences ``issue -> local bucketed aggregation -> finalize``:
in the traced program the wire collectives have no data dependency on the
local aggregation, and they appear *before* it, so XLA's scheduler is free
to overlap the in-flight collectives with the hot compute (the dry-run
harness verifies the resulting collective order in the lowered HLO —
``launch/hlo_stats.collective_order``). A stage with ``overlap=False``
runs its whole pipeline inside ``finalize`` instead, reproducing the
strictly sequential trace bit-for-bit — the parity fallback. Overlap never
changes values, only op order: both phases compute the same recvs with the
same per-stage PRNG folds.

Execution model per stage (forward):

  assemble_send -> [pre-wire: psum_scatter for ``inter``] -> all_to_all of
  (payload [+ fp32 zero/scale per 4-row quant group]) -> dequantize ->
  [post-wire: all_gather for ``inter``] -> scatter_recv

Every stage's wire pipeline is self-transpose (reduce-scatter^T =
all-gather, all_to_all^T = all_to_all), so ONE quantized
``jax.custom_vjp`` — :func:`quantized_exchange`, parameterized by a static
:class:`StageTopo` — serves flat, intra and inter stages alike. The VJP
splits at the same phase boundary as the forward: the custom rule covers
the wire segment (pre-wire + quantized all_to_all), while the post-wire
all_gather is left to JAX's built-in collective transposes. The backward
pass therefore decomposes into independently schedulable collective
segments — psum_scatter of the cotangent (the all_gather's transpose),
then the re-quantized all_to_all (unbiased per Lemma 1's stochastic
rounding) — instead of one opaque custom-VJP region, giving the scheduler
the same freedom to overlap the backward wire with the backward of the
local aggregation.

Delayed stages own their slice of the per-layer halo cache: the schedule
decides the cache pytree structure (one buffer per delayed stage per
layer), refreshes a stage whenever ``epoch % cd == 0``, and serves the
stop-gradient stale buffer otherwise. Sync stages carry no cache state.
For overlapped stages the refresh select runs in ``issue`` so the stale
epochs keep the same two-phase structure.

Works identically under ``shard_map`` (real meshes) and ``jax.vmap``
(virtual workers), since both implement named-axis collective semantics.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# Module import (not the symbol) so the exchange layer's "single custom
# VJP" invariant stays greppable: the only custom_vjp *defined or bound*
# here is quantized_exchange; the aggregation VJP lives with the kernel.
from repro.kernels import seg_aggregate as segagg
from repro.graph import structure as gstruct
from repro.quant.stochastic import ROW_GROUP, QuantParams, dequantize, quantize

WIRE_BITS = (0, 2, 4, 8)  # 0 = fp32
STAGE_LEVELS = ("flat", "intra", "inter")


# --------------------------------------------------------------------------
# Device-ready halo plans (per-worker slices of graph.remote plans)
# --------------------------------------------------------------------------


class DeviceHaloPlan(NamedTuple):
    """Per-worker slices of graph.remote.HaloPlan, as device arrays.

    Leading axis of each array in the *stacked* plan is the worker axis;
    inside shard_map/vmap each worker sees its own slice (no leading axis).
    """

    send_gather_idx: jax.Array   # [C*R] int32 (C chunks of R wire rows)
    send_gather_mask: jax.Array  # [C*R] bool
    pre_src: jax.Array           # [pre_nnz] int32
    pre_slot: jax.Array          # [pre_nnz] int32
    pre_weight: jax.Array        # [pre_nnz] f32
    recv_row: jax.Array          # [recv_nnz] int32
    recv_dst: jax.Array          # [recv_nnz] int32
    recv_weight: jax.Array       # [recv_nnz] f32
    # Optional degree-bucketed layouts of the receive-side scatter (built
    # when stack_halo_plan knows the owned-row count): forward maps the
    # wire recv buffer into local rows through the same segment-aggregate
    # primitive as the local graph; the transpose drives its custom VJP.
    recv_ell: Optional["segagg.DeviceBucketedEll"] = None
    recv_ell_t: Optional["segagg.DeviceBucketedEll"] = None


def host_recv_bucketed(hp, num_rows: int):
    """Bucketed-ELL (fwd + reverse) of each worker's recv scatter, as host
    *stacked* bucket tuples ([P, ...] numpy, ``stack_bucketed_ells``
    format). This is the exported plan form the multiproc runtime
    publishes through the shared-memory store; :func:`stack_halo_plan`
    device-materializes the same layout for the in-process backends.

    The host plan's padding entries carry weight 0 — they are dropped here
    so they don't inflate row 0's degree class."""
    P = hp.recv_row.shape[0]
    wire_rows = hp.send_gather_idx.shape[-1]
    fwd, rev = [], []
    for p in range(P):
        keep = hp.recv_weight[p] != 0
        csr = gstruct.coo_to_csr(
            hp.recv_row[p][keep], hp.recv_dst[p][keep],
            hp.recv_weight[p][keep], num_rows, wire_rows)
        fwd.append(gstruct.bucketed_ell_from_csr(csr))
        rev.append(gstruct.bucketed_ell_from_csr(gstruct.transpose_csr(csr)))
    return (gstruct.stack_bucketed_ells(fwd),
            gstruct.stack_bucketed_ells(rev))


def _recv_bucketed(hp, num_rows: int):
    fwd, rev = host_recv_bucketed(hp, num_rows)
    return segagg.device_bucketed(fwd), segagg.device_bucketed(rev)


def stack_halo_plan(hp, num_rows: Optional[int] = None) -> DeviceHaloPlan:
    """graph.remote.HaloPlan (host numpy, [P, ...]) -> stacked device plan.

    ``num_rows`` (each worker's padded owned-row count) additionally builds
    the bucketed recv-scatter layouts consumed by the ``ell`` aggregation
    backend; without it the plan only supports the COO scatter path.
    """
    recv_ell = recv_ell_t = None
    if num_rows is not None:
        recv_ell, recv_ell_t = _recv_bucketed(hp, num_rows)
    return DeviceHaloPlan(
        send_gather_idx=jnp.asarray(hp.send_gather_idx, jnp.int32),
        send_gather_mask=jnp.asarray(hp.send_gather_mask),
        pre_src=jnp.asarray(hp.pre_src, jnp.int32),
        pre_slot=jnp.asarray(hp.pre_slot, jnp.int32),
        pre_weight=jnp.asarray(hp.pre_weight),
        recv_row=jnp.asarray(hp.recv_row, jnp.int32),
        recv_dst=jnp.asarray(hp.recv_dst, jnp.int32),
        recv_weight=jnp.asarray(hp.recv_weight),
        recv_ell=recv_ell,
        recv_ell_t=recv_ell_t,
    )


class DeviceHierPlan(NamedTuple):
    """Two DeviceHaloPlan's: intra (rank chunks) + inter (group chunks)."""

    intra: DeviceHaloPlan
    inter: DeviceHaloPlan


def stack_hier_plan(hp, num_rows: Optional[int] = None) -> DeviceHierPlan:
    """graph.remote.HierHaloPlan (host numpy) -> stacked device plan."""
    return DeviceHierPlan(
        intra=stack_halo_plan(hp.intra, num_rows=num_rows),
        inter=stack_halo_plan(hp.inter, num_rows=num_rows),
    )


def assemble_send(h: jax.Array, plan: DeviceHaloPlan) -> jax.Array:
    """Build the [C*R, F] wire buffer: post raws + pre partials (Fig 2 step 4)."""
    raw = jnp.where(plan.send_gather_mask[:, None], h[plan.send_gather_idx], 0.0)
    send = raw.at[plan.pre_slot].add(plan.pre_weight[:, None] * h[plan.pre_src])
    return send


def scatter_recv(acc: jax.Array, recv: jax.Array, plan: DeviceHaloPlan,
                 agg_backend: str = "coo") -> jax.Array:
    """Post-aggregate received rows into the local accumulator (Fig 2 step 6).

    ``agg_backend="ell"`` (with a plan that carries the bucketed layouts)
    routes the scatter through the same segment-aggregate primitive as the
    local graph — dense per-degree-class gathers instead of an edge-order
    scatter-add, forward and backward both.
    """
    if agg_backend == "ell" and plan.recv_ell is not None:
        return acc + segagg.bucketed_aggregate(
            recv, plan.recv_ell, plan.recv_ell_t, acc.shape[0])
    return acc.at[plan.recv_dst].add(plan.recv_weight[:, None] * recv[plan.recv_row])


# --------------------------------------------------------------------------
# Stage topology + the two wire primitives (fp32, quantized)
# --------------------------------------------------------------------------


class StageTopo(NamedTuple):
    """Static description of one stage's collective pipeline.

    ``kind="a2a"``: plain tiled all_to_all over ``wire_axis`` with
    ``wire_chunks`` per-destination chunks (the flat exchange, and the
    intra level of the hierarchical exchange).

    ``kind="grouped"``: psum_scatter over ``shard_axis`` (merging the
    ``shard_size`` workers' additive contributions and sharding the group
    buffer 1/W per worker) -> all_to_all over ``wire_axis`` (the only slow
    traffic) -> all_gather over ``shard_axis`` (the inter level).

    Hashable, so it can ride ``custom_vjp`` as a nondiff argument.
    """

    kind: str            # "a2a" | "grouped"
    wire_axis: str
    wire_chunks: int
    shard_axis: str = ""
    shard_size: int = 1


def _wire_a2a(v: jax.Array, topo: StageTopo) -> jax.Array:
    """Tiled all_to_all of a [rows, F] buffer in ``wire_chunks`` chunks."""
    return jax.lax.all_to_all(
        v.reshape(topo.wire_chunks, -1, v.shape[-1]), topo.wire_axis,
        split_axis=0, concat_axis=0, tiled=False,
    ).reshape(v.shape)


def _pre_wire(x: jax.Array, topo: StageTopo) -> jax.Array:
    """Transform the assembled send buffer into what goes on the wire."""
    if topo.kind == "a2a":
        return x
    rows, feat = x.shape
    s = rows // (topo.wire_chunks * topo.shard_size)
    y = x.reshape(topo.wire_chunks, topo.shard_size, s, feat)
    # Per-group aggregation: partials destined for the same remote row merge
    # here, and the group buffer lands sharded 1/W per worker.
    shard = jax.lax.psum_scatter(y, topo.shard_axis, scatter_dimension=1,
                                 tiled=False)                   # [G, s, F]
    return shard.reshape(topo.wire_chunks * s, feat)


def _post_wire(y: jax.Array, topo: StageTopo) -> jax.Array:
    """Transform the wire recv buffer back into the full recv buffer."""
    if topo.kind == "a2a":
        return y
    feat = y.shape[-1]
    s = y.shape[0] // topo.wire_chunks
    recv = y.reshape(topo.wire_chunks, s, feat)
    full = jax.lax.all_gather(recv, topo.shard_axis, axis=1,
                              tiled=False)                      # [G, W, s, F]
    return full.reshape(topo.wire_chunks * topo.shard_size * s, feat)


def _quantized_wire(w: jax.Array, key, topo: StageTopo, bits: int) -> jax.Array:
    """Quantize a wire-level buffer, all_to_all the payload, dequantize."""
    with jax.named_scope("quantize"):
        q, params = quantize(w, bits, key)
    qr = _wire_a2a(q.astype(jnp.int32), topo)
    # fp32 (zero, scale) ride along — the paper's "params" wire term (Eqn 5).
    zr = _wire_a2a(params.zero[:, None], topo).reshape(-1)
    sr = _wire_a2a(params.scale[:, None], topo).reshape(-1)
    with jax.named_scope("dequantize"):
        return dequantize(qr, QuantParams(zr, sr))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def quantized_exchange(send, key, topo: StageTopo, bits: int):
    """THE quantized wire segment — the exchange layer's single custom VJP.

    Covers the issue-phase half of the pipeline: pre-wire (the psum_scatter
    for ``grouped`` topologies — the merged partials are what crosses the
    network), quantization of the wire buffer, the all_to_all of the int
    payload plus the fp32 (zero, scale) per 4-row quant group, and
    dequantization. The post-wire all_gather (:func:`stage_finalize`) stays
    *outside* the custom rule, so its transpose (a psum_scatter of the
    cotangent) is generated by JAX and schedules independently of the
    backward wire — the VJP splits at the same boundary as the forward's
    issue/finalize phases.
    """
    return _quantized_wire(_pre_wire(send, topo), key, topo, bits)


def _quantized_exchange_fwd(send, key, topo, bits):
    return quantized_exchange(send, key, topo, bits), key


def _quantized_exchange_bwd(topo, bits, key, g):
    # Self-transpose pipeline: the reverse exchange IS the same exchange.
    # ``g`` arrives at wire level (the post-wire all_gather's transpose —
    # a psum_scatter — has already run under JAX's built-in rules), so the
    # cotangent is re-quantized directly and fanned back out through the
    # post-wire after its all_to_all — unbiased per Lemma 1.
    gkey = jax.random.fold_in(key, 0x5BD1)
    return _post_wire(_quantized_wire(g, gkey, topo, bits), topo), None


quantized_exchange.defvjp(_quantized_exchange_fwd, _quantized_exchange_bwd)


def _check_quant_alignment(topo: StageTopo, rows: int) -> None:
    """Quant row groups (4 rows share zero/scale) must not straddle the
    per-destination wire chunks."""
    per_chunk = rows // topo.wire_chunks
    if topo.kind == "grouped":
        per_chunk = rows // (topo.wire_chunks * topo.shard_size)
    if per_chunk % ROW_GROUP:
        raise ValueError(
            f"{topo.kind} stage wire chunk of {per_chunk} rows is not a "
            f"multiple of the quant row group ({ROW_GROUP})")


def stage_issue(send: jax.Array, topo: StageTopo, bits: int,
                key: Optional[jax.Array]) -> jax.Array:
    """Launch one stage's wire pipeline on an assembled send buffer.

    Runs pre-wire + (quantized) all_to_all + dequantize and returns the
    wire-level recv buffer — still sharded 1/W per worker for ``grouped``
    topologies. :func:`stage_finalize` fans it back out.
    """
    if bits == 0:
        return _wire_a2a(_pre_wire(send, topo), topo)
    if key is None:
        raise ValueError("quantized exchange needs a PRNG key")
    _check_quant_alignment(topo, send.shape[0])
    return quantized_exchange(send, key, topo, bits)


def stage_finalize(wire: jax.Array, topo: StageTopo) -> jax.Array:
    """Post-wire fan-out of a wire-level recv buffer (all_gather for
    ``grouped`` topologies, identity for ``a2a``)."""
    return _post_wire(wire, topo)


def stage_exchange(send: jax.Array, topo: StageTopo, bits: int,
                   key: Optional[jax.Array]) -> jax.Array:
    """One stage's full exchange of an assembled send buffer (fp32 or
    quantized): issue + finalize back-to-back."""
    return stage_finalize(stage_issue(send, topo, bits, key), topo)


# --------------------------------------------------------------------------
# Schedule: per-stage (level, bits, caching policy)
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class StageSpec:
    """One exchange stage: a level with its wire format, caching policy and
    scheduling.

    ``bits``    — 0 (fp32) or 2/4/8 (stochastic quantization).
    ``cd``      — 1 = sync (fresh exchange every epoch); cd > 1 = delayed
                  communication: refresh when ``epoch % cd == 0``, serve the
                  stale stop-gradient buffer otherwise (DistGNN's cd-N).
    ``overlap`` — True issues this stage's wire pipeline in the layer's
                  ``issue`` phase, *before* the local bucketed aggregation,
                  so XLA can hide the in-flight collectives behind the hot
                  compute; False runs it sequentially in ``finalize`` (the
                  bit-identical parity fallback). Overlap changes op order
                  only, never values.
    """

    level: str   # "flat" | "intra" | "inter"
    bits: int = 0
    cd: int = 1
    overlap: bool = False

    def __post_init__(self):
        if self.level not in STAGE_LEVELS:
            raise ValueError(f"unknown stage level {self.level!r}")
        if self.bits not in WIRE_BITS:
            raise ValueError(f"bits must be one of {WIRE_BITS}, got {self.bits}")
        if self.cd < 1:
            raise ValueError(f"cd must be >= 1, got {self.cd}")

    @property
    def delayed(self) -> bool:
        return self.cd > 1

    def as_dict(self) -> dict:
        return {"level": self.level, "bits": self.bits,
                "policy": f"delayed({self.cd})" if self.delayed else "sync",
                "overlap": self.overlap}


@dataclass(frozen=True)
class ExchangeSchedule:
    """A sequence of exchange stages plus the axis layout they run on.

    Flat schedules hold exactly one ``flat`` stage over ``axis_name``;
    hierarchical schedules hold (``intra``, ``inter``) over
    (``node_axis``, ``group_axis``) with ``num_groups * group_size ==
    nparts``. Build via :meth:`flat` / :meth:`hierarchical` (or
    ``DistConfig.schedule()`` in the trainer).
    """

    stages: Tuple[StageSpec, ...]
    nparts: int
    axis_name: str = "workers"
    node_axis: str = "node"
    group_axis: str = "group"
    num_groups: int = 0
    group_size: int = 0

    def __post_init__(self):
        levels = tuple(s.level for s in self.stages)
        if levels == ("flat",):
            if self.num_groups or self.group_size:
                raise ValueError("flat schedule must not set num_groups/group_size")
        elif levels == ("intra", "inter"):
            if self.num_groups < 1 or self.group_size < 1:
                raise ValueError(
                    "hierarchical schedule needs num_groups >= 1 and "
                    f"group_size >= 1, got {self.num_groups}x{self.group_size}")
            if self.num_groups * self.group_size != self.nparts:
                raise ValueError(
                    f"num_groups * group_size ({self.num_groups}x"
                    f"{self.group_size}) must equal nparts ({self.nparts})")
        else:
            raise ValueError(
                f"schedule stages must be ('flat',) or ('intra', 'inter'), "
                f"got {levels}")

    # -- constructors ------------------------------------------------------

    @staticmethod
    def flat(nparts: int, bits: int = 0, cd: int = 1,
             axis_name: str = "workers",
             overlap: Optional[bool] = None) -> "ExchangeSchedule":
        """``overlap=None`` keeps the flat exchange sequential (one fast
        all_to_all; nothing slow enough to be worth hiding by default)."""
        return ExchangeSchedule(
            stages=(StageSpec("flat", bits=bits, cd=cd,
                              overlap=bool(overlap)),),
            nparts=nparts, axis_name=axis_name)

    @staticmethod
    def hierarchical(num_groups: int, group_size: int, *,
                     intra_bits: int = 0, inter_bits: int = 0,
                     intra_cd: int = 1, inter_cd: int = 1,
                     node_axis: str = "node",
                     group_axis: str = "group",
                     overlap: Optional[bool] = None) -> "ExchangeSchedule":
        """``overlap=None`` defaults to True: hierarchical schedules exist
        to scale past the slow inter-group wire, and hiding that wire
        behind the local aggregation is where the paper's scheme wins at
        1000s of workers. ``overlap=False`` is the sequential parity
        fallback."""
        overlap = True if overlap is None else overlap
        return ExchangeSchedule(
            stages=(StageSpec("intra", bits=intra_bits, cd=intra_cd,
                              overlap=overlap),
                    StageSpec("inter", bits=inter_bits, cd=inter_cd,
                              overlap=overlap)),
            nparts=num_groups * group_size,
            node_axis=node_axis, group_axis=group_axis,
            num_groups=num_groups, group_size=group_size)

    # -- structure ---------------------------------------------------------

    @property
    def is_hierarchical(self) -> bool:
        return self.stages[0].level != "flat"

    @property
    def psum_axes(self):
        """Axis name(s) spanning all workers, for grad/metric reductions."""
        if self.is_hierarchical:
            return (self.node_axis, self.group_axis)
        return self.axis_name

    @property
    def uses_cache(self) -> bool:
        return any(s.delayed for s in self.stages)

    @property
    def delayed_indices(self) -> Tuple[int, ...]:
        return tuple(i for i, s in enumerate(self.stages) if s.delayed)

    def as_sync(self) -> "ExchangeSchedule":
        """The same schedule with every stage forced to sync (cd=1)."""
        import dataclasses
        return dataclasses.replace(
            self, stages=tuple(dataclasses.replace(s, cd=1)
                               for s in self.stages))

    def topo(self, stage: StageSpec) -> StageTopo:
        if stage.level == "flat":
            return StageTopo("a2a", self.axis_name, self.nparts)
        if stage.level == "intra":
            return StageTopo("a2a", self.node_axis, self.group_size)
        return StageTopo("grouped", self.group_axis, self.num_groups,
                         self.node_axis, self.group_size)

    def plan_for(self, stage: StageSpec, wd) -> DeviceHaloPlan:
        """Pick the stage's device plan off a WorkerData-like carrier (any
        object with ``plan`` / ``hier_plan`` attributes)."""
        if stage.level == "flat":
            if wd.plan is None:
                raise ValueError("flat schedule needs WorkerData.plan")
            return wd.plan
        if wd.hier_plan is None:
            raise ValueError("hierarchical schedule needs WorkerData.hier_plan")
        return wd.hier_plan.intra if stage.level == "intra" else wd.hier_plan.inter

    # -- execution ---------------------------------------------------------

    def layer_program(self, wd, agg_backend: str = "coo") -> "LayerProgram":
        """Compile this schedule against a worker's plans into the
        two-phase :class:`LayerProgram` the trainer sequences as
        ``issue -> local aggregation -> finalize``."""
        return LayerProgram(self, wd, agg_backend=agg_backend)

    def run_layer(self, h: jax.Array, local_agg: jax.Array, wd,
                  key: Optional[jax.Array],
                  cache_entry: Optional[Sequence[jax.Array]] = None,
                  epoch: Optional[jax.Array] = None,
                  agg_backend: str = "coo"
                  ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        """One GCN layer's full exchange in a single call (compatibility
        shim over :meth:`layer_program`): issue + finalize back-to-back
        against an already-computed local aggregation.

        Since ``local_agg`` is already traced by the time this runs, the
        two phases are adjacent and no wire/compute overlap window exists
        — callers wanting the overlap must drive the
        :class:`LayerProgram` phases themselves (the trainer does).
        Values are identical either way.
        """
        prog = self.layer_program(wd, agg_backend=agg_backend)
        return prog.finalize(
            local_agg, prog.issue(h, key, cache_entry=cache_entry,
                                  epoch=epoch))

    # -- cache layout ------------------------------------------------------

    def cache_rows(self, wd) -> Tuple[int, ...]:
        """Recv-buffer row count for each delayed stage (cache shapes)."""
        return tuple(
            self.plan_for(self.stages[i], wd).send_gather_idx.shape[-1]
            for i in self.delayed_indices)

    def init_cache(self, wd, feature_dims: Sequence[int],
                   lead: Tuple[int, ...] = ()) -> List[Tuple[jax.Array, ...]]:
        """Zero halo cache: one buffer per (layer, delayed stage).

        ``feature_dims[l]`` is the width layer ``l`` exchanges; ``lead``
        prefixes the stacked worker dims ((P,) for flat vmap/shard_map,
        (G, W) for the nested hierarchical vmap).
        """
        rows = self.cache_rows(wd)
        return [tuple(jnp.zeros((*lead, r, f)) for r in rows)
                for f in feature_dims]

    # -- accounting --------------------------------------------------------

    def describe(self) -> dict:
        d = {"stages": [s.as_dict() for s in self.stages],
             "nparts": self.nparts}
        if self.is_hierarchical:
            d.update(num_groups=self.num_groups, group_size=self.group_size)
        return d

    def wire_volume_bytes(self, stats, feat_dim: int) -> Dict[str, float]:
        """Per-stage predicted wire bytes per epoch (amortized over cd),
        from a ``graph.remote.CommStats``. This is the prediction the
        comm_volume benchmark checks against the realized plan volumes.

        The cd amortization models an async runtime that skips sends on
        stale epochs; the jit-lowered step executes every stage's
        collectives regardless (see :class:`LayerProgram`), so HLO-parsed
        collective bytes are the *un*-amortized per-epoch figure."""
        return {
            s.level: stats.volume_bytes(
                feat_dim, bits=s.bits or 32,
                stage=None if s.level == "flat" else s.level, cd=s.cd)
            for s in self.stages
        }


# --------------------------------------------------------------------------
# Two-phase LayerProgram: issue the wire, aggregate locally, finalize
# --------------------------------------------------------------------------


class LayerInFlight(NamedTuple):
    """Per-layer state between the ``issue`` and ``finalize`` phases.

    ``recv[si]`` holds stage ``si``'s in-flight (cache-refreshed) recv
    buffer when the stage was issued, else ``None`` — sequential stages run
    their pipeline inside ``finalize`` from the carried ``h``/``key``.
    ``entry[si]`` is the issued stage's new halo-cache entry (``None`` for
    sync or not-yet-run stages).
    """

    h: jax.Array
    key: Optional[jax.Array]
    epoch: Optional[jax.Array]
    cache_entry: Optional[Sequence[jax.Array]]
    recv: Tuple[Optional[jax.Array], ...]
    entry: Tuple[Optional[jax.Array], ...]


class LayerProgram:
    """One layer's exchange schedule compiled into (issue, finalize) phases.

    ``issue`` launches every ``overlap`` stage's wire pipeline — inter
    first, so the slow collectives enter the program earliest — and applies
    the delayed-comm cache refresh to the in-flight receives. ``finalize``
    scatters all receives into the accumulator, running any sequential
    (``overlap=False``) stage's pipeline on the spot, which reproduces the
    pre-overlap trace order bit-for-bit.

    Note on delayed stages under jit: ``epoch`` is a traced value, so the
    lowered program contains (and executes) every stage's collectives on
    stale epochs too — ``jnp.where`` merely selects the stale buffer. A
    real async runtime skips those sends; the per-stage cd amortization in
    :meth:`ExchangeSchedule.wire_volume_bytes` models that runtime, not the
    lowered HLO.
    """

    def __init__(self, schedule: ExchangeSchedule, wd,
                 agg_backend: str = "coo"):
        self.schedule = schedule
        self.agg_backend = agg_backend
        self._stages = tuple(
            (spec, schedule.plan_for(spec, wd), schedule.topo(spec))
            for spec in schedule.stages)
        # Cache-entry slot per delayed stage, in stage order (the cache
        # pytree layout is overlap-agnostic).
        self._cache_slot = {si: ci for ci, si
                            in enumerate(schedule.delayed_indices)}
        # Overlapped stages issue in reverse stage order: the inter stage's
        # slow pipeline enters the program before the intra stage's.
        self._issue_order = tuple(
            si for si in reversed(range(len(self._stages)))
            if self._stages[si][0].overlap)

    def _wire(self, si: int, h: jax.Array, key) -> jax.Array:
        spec, plan, topo = self._stages[si]
        kq = jax.random.fold_in(key, si) if key is not None else None
        return stage_exchange(assemble_send(h, plan), topo, spec.bits, kq)

    def _refresh(self, si: int, recv, cache_entry, epoch):
        """Delayed-comm select: fresh recv on refresh epochs, the stale
        stop-gradient buffer otherwise. Returns (recv, new cache entry)."""
        spec = self._stages[si][0]
        if cache_entry is None or epoch is None:
            raise ValueError(
                f"stage {spec.level!r} is delayed(cd={spec.cd}) "
                "and needs a halo cache + epoch")
        refresh = (epoch % spec.cd) == 0
        stale = jax.lax.stop_gradient(cache_entry[self._cache_slot[si]])
        recv = jnp.where(refresh, recv, stale)
        return recv, jax.lax.stop_gradient(recv)

    def issue(self, h: jax.Array, key: Optional[jax.Array],
              cache_entry: Optional[Sequence[jax.Array]] = None,
              epoch: Optional[jax.Array] = None) -> LayerInFlight:
        """Launch every overlapped stage's wire pipeline (inter first)."""
        n = len(self._stages)
        recv: List[Optional[jax.Array]] = [None] * n
        entry: List[Optional[jax.Array]] = [None] * n
        for si in self._issue_order:
            r = self._wire(si, h, key)
            if self._stages[si][0].delayed:
                r, entry[si] = self._refresh(si, r, cache_entry, epoch)
            recv[si] = r
        return LayerInFlight(h=h, key=key, epoch=epoch,
                             cache_entry=cache_entry,
                             recv=tuple(recv), entry=tuple(entry))

    def finalize(self, local_agg: jax.Array, inflight: LayerInFlight
                 ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        """Scatter all receives into the accumulator (running sequential
        stages' pipelines now). Returns (aggregated output, new cache
        entry — one buffer per delayed stage in stage order, empty for
        all-sync schedules)."""
        acc = local_agg
        new_entry: List[jax.Array] = []
        for si, (spec, plan, _) in enumerate(self._stages):
            r = inflight.recv[si]
            if r is None:
                r = self._wire(si, inflight.h, inflight.key)
                if spec.delayed:
                    r, e = self._refresh(si, r, inflight.cache_entry,
                                         inflight.epoch)
                    new_entry.append(e)
            elif spec.delayed:
                new_entry.append(inflight.entry[si])
            acc = scatter_recv(acc, r, plan, agg_backend=self.agg_backend)
        return acc, tuple(new_entry)
