"""build_session: lower a :class:`~repro.run.spec.RunSpec` onto the live
training stack.

The pipeline every launcher/benchmark/example used to hand-assemble —

  graph source -> features -> normalization -> (flat | hierarchical)
  partition -> ``prepare_distributed`` -> mesh -> ``DistributedTrainer``

— runs here once, stage by stage, and returns a :class:`Session` exposing
the operations the drivers actually perform: ``fit`` / ``train_epoch`` /
``evaluate`` (training), ``lower`` (the dry-run hook), ``comm_stats`` /
``predicted_wire_bytes`` (accounting). The staged helpers
(:func:`build_graph`, :func:`build_partition`) are public so analysis-only
drivers (comm-volume sweeps) reuse the identical construction without
paying for a trainer, and :class:`BuildCache` lets benchmark grids share
the expensive graph/partition stages across spec variants that only differ
downstream (the cache keys on the relevant sub-spec hashes, so a hit is
always semantically identical to a rebuild).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import repro.run.sources as sources  # populates the registries on import
from repro.run.spec import GRAPH_SOURCES, FEATURE_SOURCES, RunSpec
from repro.utils import trace


def stage_hlo_payload_bytes(rows: int, feat: int, bits: int) -> float:
    """One direction's per-device all-to-all payload bytes for a
    ``[rows, feat]`` wire buffer: fp32 rows, or int32 quant holders
    (sub-byte payloads ship in i32 until XLA packs them) plus the two
    fp32 (zero, scale) params per ``ROW_GROUP`` rows when the stage
    quantizes. A partial trailing row group still ships a full (zero,
    scale) pair — ceil-div, not floor."""
    from repro.quant.stochastic import ROW_GROUP

    payload = rows * feat * 4.0
    if bits:
        payload += 2.0 * (-(-rows // ROW_GROUP)) * 4.0
    return payload


def build_graph(spec: RunSpec) -> Tuple[Any, np.ndarray]:
    """(normalized Graph, features [N, F]) for the spec's graph section.

    Features are synthesized on the *raw* graph (labels drive them, not
    edge weights); normalization attaches the aggregation edge weights
    before partitioning so pre-aggregation applies source-side weights —
    the invariant ``prepare_distributed`` documents.
    """
    gs = spec.graph
    g = GRAPH_SOURCES.get(gs.source)(gs)
    x = FEATURE_SOURCES.get(sources.resolve_features(gs))(g, gs)
    if gs.norm == "mean":
        g = g.mean_normalized()
    elif gs.norm == "gcn":
        g = g.gcn_normalized()
    elif _attends_self(spec):
        g = g.add_self_loops()
    return g, x


def _attends_self(spec: RunSpec) -> bool:
    """GAT attends over N(i) and i itself; the mean and gcn norms add the
    self-loops already, ``none`` does not."""
    return spec.model.model == "gat" and spec.graph.norm == "none"


def build_partition(spec: RunSpec, g) -> Any:
    """Partition the (already normalized) graph per the spec: a flat
    ``PartitionedGraph`` or a two-level ``HierPartitionedGraph``, with the
    ``partition.refine`` post-pass (bucket-max hub rebalancing) applied to
    the labels before the halo plans are built."""
    from repro.graph import (build_hierarchical_partitioned_graph,
                             build_partitioned_graph)
    from repro.graph.partition import (partition_graph,
                                       partition_hierarchical,
                                       refine_bucket_max)
    ps = spec.partition
    if ps.hierarchical:
        gsz = ps.resolved_group_size()
        part = None
        if ps.refine == "bucket-max":
            part = partition_hierarchical(g, ps.groups, gsz, seed=ps.seed)
            part = refine_bucket_max(g, part, nparts=ps.nparts,
                                     group_size=gsz, seed=ps.seed)
        return build_hierarchical_partitioned_graph(
            g, ps.groups, gsz, part=part, strategy=ps.strategy, seed=ps.seed)
    part = None
    if ps.refine == "bucket-max":
        part = partition_graph(g, ps.nparts, seed=ps.seed)
        part = refine_bucket_max(g, part, nparts=ps.nparts, seed=ps.seed)
    return build_partitioned_graph(g, ps.nparts, part=part,
                                   strategy=ps.strategy, seed=ps.seed)


def resolve_auto(spec: RunSpec) -> RunSpec:
    """The ``ExecSpec.auto`` resolution path: when ``exec.auto`` names a
    tuner result file (``python -m repro.run.tune --out ...``), swap the
    audited winner's partition + schedule sections into the caller's spec.
    The caller keeps naming its graph/model/exec; the tuner owns the
    performance knobs. Refuses a result tuned for a different graph
    section — a stale auto file must fail loudly, not run the wrong
    schedule silently."""
    import dataclasses

    from repro.run.spec import SpecError
    if not spec.exec.auto:
        return spec
    path = spec.exec.auto
    try:
        with open(path) as f:
            result = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SpecError(f"exec.auto: cannot read tuner result {path!r}: {e}")
    winner = result.get("winner") or {}
    if not winner.get("spec"):
        raise SpecError(f"exec.auto: {path!r} carries no winner.spec "
                        "(re-run repro.run.tune)")
    tuned = RunSpec.from_dict(winner["spec"])
    if tuned.graph.content_hash() != spec.graph.content_hash():
        raise SpecError(
            f"exec.auto: {path!r} was tuned for graph section "
            f"{tuned.graph.content_hash()}, this spec builds "
            f"{spec.graph.content_hash()} — re-tune for this graph")
    return dataclasses.replace(spec, partition=tuned.partition,
                               schedule=tuned.schedule).validate()


def build_mesh(spec: RunSpec):
    """The worker mesh for shard_map execution (None under vmap)."""
    if spec.exec.mode != "shard_map":
        return None
    from repro.launch.mesh import make_hier_worker_mesh, make_worker_mesh
    ps = spec.partition
    if ps.hierarchical:
        return make_hier_worker_mesh(ps.groups, ps.resolved_group_size())
    return make_worker_mesh(ps.nparts)


@dataclass
class BuildCache:
    """Shares the graph/partition stages across sessions whose specs agree
    on those stages (benchmark grids sweeping only schedule/model knobs).
    Keys are content hashes of the contributing sub-specs, so a hit never
    crosses configurations."""

    graphs: Dict[str, Tuple[Any, np.ndarray]] = field(default_factory=dict)
    partitions: Dict[str, Any] = field(default_factory=dict)
    pstats: Dict[str, Dict[str, Any]] = field(default_factory=dict)

    @staticmethod
    def _graph_key(spec: RunSpec) -> str:
        loops = "|self-loops" if _attends_self(spec) else ""
        return spec.graph.content_hash() + loops

    @classmethod
    def _part_key(cls, spec: RunSpec) -> str:
        return f"{cls._graph_key(spec)}|{spec.partition.content_hash()}"

    def graph(self, spec: RunSpec) -> Tuple[Any, np.ndarray]:
        key = self._graph_key(spec)
        if key not in self.graphs:
            self.graphs[key] = build_graph(spec)
        return self.graphs[key]

    def partition(self, spec: RunSpec, g) -> Any:
        key = self._part_key(spec)
        if key not in self.partitions:
            self.partitions[key] = build_partition(spec, g)
        return self.partitions[key]

    def partition_stats(self, spec: RunSpec, g) -> Dict[str, Any]:
        """``partition_stats`` for the spec's labels, cached alongside the
        partition itself (sweep grids re-read it per schedule variant)."""
        key = self._part_key(spec)
        if key not in self.pstats:
            from repro.graph.partition import partition_stats
            self.pstats[key] = partition_stats(g, self.partition(spec, g).part)
        return self.pstats[key]


class Session:
    """A spec lowered onto the live stack: graph, partition, worker data,
    mesh and trainer, plus the driver-facing operations."""

    def __init__(self, spec: RunSpec, g, x, pg, wd, mesh, trainer):
        self.spec = spec
        self.graph = g
        self.x = x
        self.pg = pg
        self.wd = wd
        self.mesh = mesh
        self.trainer = trainer

    # -- training ----------------------------------------------------------

    def fit(self, epochs: Optional[int] = None,
            log_every: Optional[int] = None,
            ckpt_dir: Optional[str] = None,
            resume: bool = False) -> List[Dict]:
        """Train for ``epochs`` (default: the spec's) and return history.

        ``log_every`` falls back to the spec's, whose 0 means "auto"
        (~10 eval points); pass an explicit 0 to skip evals entirely
        (pure-throughput benchmark loops).

        ``ckpt_dir`` turns on periodic checkpointing (atomic snapshots
        every ``spec.exec.ckpt_every`` epochs, default every epoch) and
        ``resume=True`` restores the newest valid checkpoint before
        training — the epoch counter fast-forwards, so a resumed run
        trains only the remaining epochs and reproduces the uninterrupted
        trajectory bit-for-bit (all per-epoch RNG derives from the epoch
        number). Under the multiproc backend the workers snapshot per-rank
        and the supervisor also restores from here on fault recovery.
        """
        e = self.spec.exec
        n = e.epochs if epochs is None else epochs
        le = e.log_every if log_every is None else log_every
        if not le and log_every is None:
            le = max(n // 10, 1)
        if ckpt_dir is None:
            if resume:
                raise ValueError("resume=True needs ckpt_dir")
            return self.trainer.fit(n, log_every=le)

        every = e.ckpt_every if e.ckpt_every else 1
        tr = self.trainer
        save = None
        if hasattr(tr, "configure_ckpt"):
            # Multiproc: workers snapshot per-rank inside train_epoch; the
            # parent only points them at the directory (before spawn) and
            # triggers the restore command on resume.
            tr.configure_ckpt(ckpt_dir, every=every)
            if resume:
                tr.restore_from_ckpt()
        else:
            from repro.checkpoint import CheckpointManager
            mgr = CheckpointManager(ckpt_dir)
            if resume:
                try:
                    tr.restore_train_state_from(mgr)
                except FileNotFoundError as err:
                    raise RuntimeError(
                        f"resume requested but no valid checkpoint under "
                        f"{ckpt_dir}") from err
            # Stamp provenance so a serving deployment can refuse a
            # checkpoint trained on a different graph (serve/server.py).
            meta = {"graph_hash": self.spec.graph.content_hash(),
                    "spec_hash": self.spec.content_hash()}
            save = lambda: tr.save_train_state(mgr, meta=meta)

        history = []
        while tr.epoch < n:
            m = tr.train_epoch()
            if save is not None and (tr.epoch % every == 0 or tr.epoch == n):
                save()
            if le and (tr.epoch % le == 0 or tr.epoch == n):
                m["eval_acc"] = tr.evaluate()
                m["epoch"] = tr.epoch
                history.append(m)
        return history

    def train_epoch(self) -> Dict[str, float]:
        return self.trainer.train_epoch()

    def evaluate(self) -> float:
        return self.trainer.evaluate()

    # -- dry-run -----------------------------------------------------------

    def lower(self, key=None):
        """Lower (without executing) one training step — the dry-run hook."""
        return self.trainer.lower_step(key)

    # -- accounting --------------------------------------------------------

    @property
    def schedule(self):
        return self.trainer.schedule

    def comm_stats(self):
        """The partition's ``CommStats`` (per-strategy/per-stage volumes)."""
        return self.pg.stats

    def partition_stats(self) -> Dict[str, Any]:
        """``graph.partition.partition_stats`` for this session's labels
        (cut fraction, load/size imbalance, padded-slot accounting incl.
        ``agg_slot_imbalance`` and the stacked executed slots) — cached, so
        end-of-run summaries and sweep rows don't re-derive it."""
        if getattr(self, "_pstats", None) is None:
            from repro.graph.partition import partition_stats
            self._pstats = partition_stats(self.graph, self.pg.part)
        return self._pstats

    def predicted_wire_bytes(self, feat_dim: Optional[int] = None
                             ) -> Dict[str, float]:
        """Per-stage predicted wire bytes per epoch under the schedule."""
        f = self.spec.graph.feat_dim if feat_dim is None else feat_dim
        return self.schedule.wire_volume_bytes(self.pg.stats, f)

    def predicted_hlo_wire_bytes(self) -> Dict[str, float]:
        """Per-device all-to-all payload bytes expected in ONE lowered
        step (forward + backward wire), derived from the schedule's
        device plans — the number the compiled module should realize
        exactly. :meth:`predicted_wire_bytes` is the paper's cost model
        (amortized, padding-free, job-level); this is the lowering's
        ground truth, and the auditor's ``predicted-bytes`` rule holds
        the compiled module to it.

        Per stage and layer: ``wire_rows x feat x 4`` bytes each
        direction — fp32 rows, or int32 quant holders (sub-byte
        payloads ship in i32 until XLA packs them) — plus the two fp32
        (zero, scale) params per ``ROW_GROUP`` rows when the stage
        quantizes. The grouped inter stage wires only its 1/W shard.
        """
        cfg = self.trainer.cfg
        feats = cfg.dims()[: cfg.num_layers]
        out: Dict[str, float] = {}
        total = 0.0
        for stage in self.schedule.stages:
            plan = self.schedule.plan_for(stage, self.wd)
            rows = int(plan.send_gather_idx.shape[-1])
            topo = self.schedule.topo(stage)
            if topo.kind == "grouped":
                rows //= topo.shard_size
            stage_bytes = sum(
                2.0 * stage_hlo_payload_bytes(rows, f, stage.bits)
                for f in feats)
            out[stage.level] = stage_bytes
            total += stage_bytes
        out["total"] = total
        return out

    def step_cache_size(self) -> Optional[int]:
        """Compiled executables behind the jitted train step (None when
        this JAX version exposes no counter). The auditor's
        ``retrace-guard`` expects exactly 1 after N epochs."""
        step = getattr(self.trainer, "_step", None)
        if step is None:
            return None  # backends without one jitted step (multiproc)
        if hasattr(step, "_cache_size"):
            return int(step._cache_size())
        return None

    def op_scopes(self) -> Dict[str, Any]:
        """The training step's XLA module name and, for each compiled
        instruction in a program scope, its scope path (``layer0/update``,
        ``layer1/aggregate/local/agg_bwd/k64/bwd``; see
        ``utils.trace.scope_path``), read from the executable a device trace
        shows. Lowers and compiles the step again (a persistent-cache load
        when the cache is on), so call it outside any timed window."""
        return trace.op_scopes(self.trainer.lower_step().compile().as_text())

    def describe(self) -> str:
        return self.spec.describe()

    def close(self) -> None:
        """Release backend resources (multiproc: stop the worker fleet and
        unlink the shared-memory segments). No-op for in-process modes."""
        close = getattr(self.trainer, "close", None)
        if close is not None:
            close()

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def build_session(spec: RunSpec, cache: Optional[BuildCache] = None
                  ) -> Session:
    """Lower ``spec`` end to end and return the live :class:`Session`."""
    from repro.core import DistributedTrainer
    from repro.core.trainer import (_lift_worker_data,
                                    prepare_distributed_host)

    spec = resolve_auto(spec.validate()).validate_training()
    with trace.span("build"):
        with trace.span("graph"):
            g, x = cache.graph(spec) if cache is not None else build_graph(spec)
        with trace.span("partition"):
            pg = (cache.partition(spec, g) if cache is not None
                  else build_partition(spec, g))
        with trace.span("host_data"):
            hwd = prepare_distributed_host(g, x, pg,
                                           attention=spec.model.model == "gat")
        if spec.exec.mode == "multiproc":
            # The host arrays ARE the runtime's shared store; workers device-
            # materialize their own slices, the parent never lifts anything.
            from repro.launch.multiproc import MultiprocRuntime
            runtime = MultiprocRuntime(spec, hwd)
            return Session(spec, g, x, pg, hwd, None, runtime)
        with trace.span("lift"):
            wd = _lift_worker_data(hwd)
        with trace.span("trainer"):
            dc = spec.schedule.to_dist_config(spec.partition, lr=spec.exec.lr)
            cfg = spec.model.to_gcn_config(spec.graph, spec.schedule)
            mesh = build_mesh(spec)
            trainer = DistributedTrainer(cfg, dc, wd, mode=spec.exec.mode,
                                         mesh=mesh, seed=spec.exec.seed)
    return Session(spec, g, x, pg, wd, mesh, trainer)
