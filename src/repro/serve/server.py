"""The online inference server: batched bucketed-ELL ego-net serving.

``build_server(spec)`` is the serving twin of ``run.session.build_session``
— it lowers a :class:`~repro.serve.spec.ServeSpec` into a live
:class:`GNNServer` holding the normalized graph, the partition (for
feature-ownership), the trained parameters (restored through
``CheckpointManager.load_latest()``), and one jit'd layer-stack program
per *shape class*.

Request path (``serve_batch``):

1. each request's k-hop ego-net is extracted (:mod:`repro.serve.egonet`),
2. up to ``serve.batch_size`` ego CSRs merge into ONE block-diagonal
   operator (``graph.structure.block_diag_csrs``) whose degree-bucketed
   layout the existing ``bucketed_aggregate`` kernel consumes directly —
   the growth-2 ladder absorbs the cross-request irregularity, so the
   whole batch is a single dispatch per layer,
3. node features are gathered through the staleness-controlled
   :class:`~repro.serve.cache.FeatureCache`,
4. the batch is padded onto a :class:`ShapeLadder` class — a fixed
   (node-count, per-bucket-row) signature — and run through the
   per-server jit; steady-state serving therefore NEVER retraces: the
   number of compiled programs is bounded by the number of shape classes
   touched, not the number of distinct batches.

Exactness: with full fanout, a served logit is **bit-identical** to the
full-batch forward for the same node. Every link in that chain is
order-preserving — ego rows are sliced verbatim from the global CSR (same
neighbour order ⇒ same ladder K ⇒ same in-bucket reduction order), block-
diagonal packing shifts ids without reordering, shape-class padding only
scatters exact ``+0.0`` into row 0 (the same convention the training
layout uses), and the XLA CPU matmul/layer-norm lowerings are row-stable.
``benchmarks/serving.py`` asserts this with ``np.array_equal`` and the
result is a row of ``experiments/BENCH_serving.json``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layers as L
from repro.core import model as M
from repro.graph.structure import (BucketedEll, block_diag_csrs,
                                   bucketed_ell_from_csr,
                                   degree_bucket_ladder, stack_bucketed_ells)
from repro.kernels import padded_device_bucketed
from repro.kernels.seg_aggregate import bucketed_aggregate, device_bucketed
from repro.serve.cache import FeatureCache
from repro.serve.egonet import EgoNet, extract_ego
from repro.serve.spec import ServeConfig, ServeSpec


class ServeError(RuntimeError):
    """A serving deployment cannot be built or cannot answer (bad
    checkpoint, graph mismatch, malformed request)."""


def _pow2ceil(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class ShapeLadder:
    """Fixed jit signatures for arbitrary request batches.

    A batch's padded signature is a *shape class* ``C`` (node capacity, a
    power of two floored at ``min_nodes``) plus per-bucket row capacities
    that are a PURE FUNCTION of ``C``: edge capacity ``E(C) = C *
    edges_per_node`` (edges_per_node = pow2ceil of the graph's mean
    degree, fixed at server build) and, for every K on the graph's full
    degree ladder,

        R_K(C) = min(C, pow2ceil(max(8, 2 * E(C) // K)))

    — sound because a bucket's rows all have degree > K/2, so ``rows_K *
    K/2 < nnz <= E(C)``. Every ladder K is materialized (empty buckets
    included) so the pytree structure is constant; two batches in the
    same class are bit-for-bit the same jit signature. ``class_for``
    doubles C past node/edge/bucket overflow, so the compiled-program
    count is bounded by the number of classes ever touched (a handful),
    never by batch composition — the retrace guard test pins this.
    """

    def __init__(self, max_degree: int, mean_degree: float,
                 min_nodes: int = 64):
        self.ladder = degree_bucket_ladder(max(1, int(max_degree)))
        self.edges_per_node = _pow2ceil(max(1, int(np.ceil(mean_degree))))
        self.min_nodes = _pow2ceil(max(8, int(min_nodes)))

    def caps(self, c: int) -> List[Tuple[int, int]]:
        e = c * self.edges_per_node
        return [(k, min(c, _pow2ceil(max(8, (2 * e) // k))))
                for k in self.ladder]

    def class_for(self, ell: BucketedEll) -> Tuple[int, List[Tuple[int, int]]]:
        """Smallest class fitting ``ell``; raises if a bucket K is off the
        graph ladder (cannot happen for subgraphs of the build graph)."""
        rows_by_k = {b.k: b.rows.shape[0] for b in ell.buckets}
        off = sorted(set(rows_by_k) - set(self.ladder))
        if off:
            raise ServeError(
                f"batch has degree-bucket K={off} beyond the graph ladder "
                f"{self.ladder} — was the server built on a smaller graph?")
        c = max(self.min_nodes, _pow2ceil(max(1, ell.num_rows)))
        while True:
            caps = self.caps(c)
            cap_by_k = dict(caps)
            if (ell.num_rows <= c
                    and ell.nnz <= c * self.edges_per_node
                    and all(r <= cap_by_k[k]
                            for k, r in rows_by_k.items())):
                return c, caps
            c *= 2


class GNNServer:
    """Answers per-node classification requests from a trained model."""

    def __init__(self, cfg: M.GCNConfig, graph: Any, x: np.ndarray,
                 params: Dict, serve_cfg: Optional[ServeConfig] = None,
                 part: Optional[np.ndarray] = None, home: int = 0):
        self.cfg = cfg
        self.serve_cfg = serve_cfg or ServeConfig()
        self.graph = graph
        self.csr = graph.csr_by_dst()
        self.params = params
        n = graph.num_nodes
        self.labels = (np.asarray(graph.labels, np.int32)
                       if graph.labels is not None
                       else np.zeros(n, np.int32))
        self.train_mask = (np.asarray(graph.train_mask, bool)
                           if graph.train_mask is not None
                           else np.ones(n, bool))
        # Serving-time label propagation mirrors eval: every train label
        # is embedded (single_eval's prop = train_mask convention).
        self.prop_mask = (self.train_mask if cfg.label_prop
                          else np.zeros(n, bool))
        if part is None:
            part = np.zeros(n, np.int32)
        self.cache = FeatureCache(np.asarray(x, np.float32), part, home,
                                  max_staleness=self.serve_cfg.max_staleness)
        deg = self.csr.row_degrees()
        self.ladder = ShapeLadder(
            max_degree=int(deg.max()) if deg.size else 1,
            mean_degree=(self.csr.nnz / max(1, n)),
            min_nodes=self.serve_cfg.min_nodes)
        self.fanouts = self.serve_cfg.resolved_fanouts(cfg.num_layers)
        self._rng = np.random.default_rng(self.serve_cfg.seed)
        # Per-instance jits: the serving program cache is what the retrace
        # guard counts, so it must not be shared across servers (or with
        # the full-batch reference, which jits separately below).
        self._fwd = jax.jit(self._forward)
        self._ref_fwd = jax.jit(self._forward)
        self._ref_logits: Optional[np.ndarray] = None
        self.requests_served = 0
        self.batches_dispatched = 0

    # -- the layer stack, outside the trainer ------------------------------

    def _forward(self, params, x, labels, prop_mask, ell):
        n = x.shape[0]

        def agg(l, h, scores=None):
            # Forward-only: the reverse layout is only consumed by the
            # VJP, so the forward layout stands in for both arguments.
            if scores is not None:  # GAT: h is W x, weighted by attention
                return L.gat_attention(h, *scores, ell)
            return bucketed_aggregate(h, ell, ell, n, use_kernel="auto")

        return M.forward(params, self.cfg, x, labels, prop_mask, agg,
                         train=False)

    # -- request path ------------------------------------------------------

    def extract(self, targets: Sequence[int]) -> EgoNet:
        return extract_ego(self.csr, targets, self.cfg.num_layers,
                           fanouts=self.fanouts, rng=self._rng)

    def _dispatch(self, egos: List[EgoNet]) -> List[np.ndarray]:
        merged = block_diag_csrs([e.csr for e in egos])
        nodes = np.concatenate([e.nodes for e in egos])
        ell = bucketed_ell_from_csr(merged)
        c, caps = self.ladder.class_for(ell)
        dev = padded_device_bucketed(ell, caps)
        f = self.cache.store.shape[1]
        x = np.zeros((c, f), np.float32)
        x[: nodes.shape[0]] = self.cache.gather(nodes)
        labels = np.zeros(c, np.int32)
        labels[: nodes.shape[0]] = self.labels[nodes]
        prop = np.zeros(c, bool)
        prop[: nodes.shape[0]] = self.prop_mask[nodes]
        logits = np.asarray(jax.block_until_ready(self._fwd(
            self.params, jnp.asarray(x), jnp.asarray(labels),
            jnp.asarray(prop), dev)))
        out = []
        off = 0
        for e in egos:
            out.append(logits[off: off + e.num_targets])
            off += e.num_nodes
        self.batches_dispatched += 1
        self.requests_served += len(egos)
        if (self.serve_cfg.refresh_every
                and self.batches_dispatched
                % self.serve_cfg.refresh_every == 0):
            self.cache.refresh()
        return out

    def serve_batch(self, requests: Sequence[Sequence[int]]
                    ) -> List[np.ndarray]:
        """Answer ``requests`` (each a list of target node ids), packing
        up to ``serve.batch_size`` ego-nets per dispatch. Returns one
        ``[num_targets, num_classes]`` logits array per request."""
        if not requests:
            return []
        egos = [self.extract(r) for r in requests]
        out: List[np.ndarray] = []
        b = self.serve_cfg.batch_size
        for i in range(0, len(egos), b):
            out.extend(self._dispatch(egos[i: i + b]))
        return out

    def serve(self, targets: Sequence[int]) -> np.ndarray:
        """One request, one dispatch (the unbatched baseline)."""
        return self._dispatch([self.extract(targets)])[0]

    # -- the bit-parity reference ------------------------------------------

    def full_batch_logits(self) -> np.ndarray:
        """Whole-graph forward on the authoritative feature store — the
        reference the full-fanout served logits must match bit for bit.
        Jitted separately so it never pollutes the serving program cache.
        """
        ell = device_bucketed(
            stack_bucketed_ells([bucketed_ell_from_csr(self.csr)]),
            squeeze=True)
        logits = self._ref_fwd(
            self.params, jnp.asarray(self.cache.store),
            jnp.asarray(self.labels), jnp.asarray(self.prop_mask), ell)
        return np.asarray(jax.block_until_ready(logits))

    def check_parity(self, targets: Sequence[int]) -> bool:
        """True iff serving ``targets`` reproduces the full-batch logits
        bit-identically (only meaningful with full fanout)."""
        served = self.serve(targets)
        if self._ref_logits is None:
            self._ref_logits = self.full_batch_logits()
        return bool(np.array_equal(served,
                                   self._ref_logits[np.asarray(targets)]))

    # -- observability -----------------------------------------------------

    def compiled_programs(self) -> int:
        """Serving programs compiled so far (the retrace-guard metric)."""
        return int(self._fwd._cache_size())

    def stats(self) -> Dict[str, Any]:
        return {
            "requests_served": self.requests_served,
            "batches_dispatched": self.batches_dispatched,
            "compiled_programs": self.compiled_programs(),
            "shape_ladder": {
                "min_nodes": self.ladder.min_nodes,
                "edges_per_node": self.ladder.edges_per_node,
                "degree_ladder": self.ladder.ladder,
            },
            "cache": self.cache.stats(),
        }


# -- spec resolution -------------------------------------------------------


def _restore_params(serve_cfg: ServeConfig, run, cfg: M.GCNConfig) -> Dict:
    """Trained params from ``serve.ckpt`` via the corruption-tolerant
    ``load_latest()`` path, with a clean error on graph mismatch."""
    from repro.checkpoint import CheckpointManager

    mgr = CheckpointManager(serve_cfg.ckpt)
    ck, step = mgr.load_latest()
    if ck is None:
        raise ServeError(
            f"serve.ckpt={serve_cfg.ckpt!r}: no loadable checkpoint "
            "(empty directory, or every snapshot corrupt)")
    meta = ck["manifest"].get("meta", {}) or {}
    want = run.graph.content_hash()
    got = meta.get("graph_hash")
    if got is not None and got != want:
        raise ServeError(
            f"checkpoint at step {step} was trained on graph {got} but "
            f"this server is built on graph {want} — refusing to serve "
            "logits from mismatched parameters")
    # The training state is {"params": ..., "opt_state": ...}; serving
    # restores only the params subtree, matched by key path (extra
    # optimizer leaves in the checkpoint are simply ignored).
    template = {"params": M.init_params(jax.random.PRNGKey(0), cfg)}
    arrays = ck["arrays"]
    leaves = jax.tree_util.tree_leaves_with_path(template)
    out = []
    for p, leaf in leaves:
        key = jax.tree_util.keystr(p)
        if key not in arrays:
            raise ServeError(
                f"checkpoint at step {step} has no parameter leaf {key} — "
                "was it written by a different model config?")
        a = arrays[key]
        if tuple(a.shape) != tuple(leaf.shape):
            raise ServeError(
                f"checkpoint leaf {key}: shape {tuple(a.shape)} != model "
                f"{tuple(leaf.shape)} — serve spec's model section must "
                "match the training run")
        out.append(jnp.asarray(a, leaf.dtype))
    tree = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(template), out)
    return tree["params"]


def build_server(spec: ServeSpec, cache=None) -> GNNServer:
    """Lower a ServeSpec end to end onto a live :class:`GNNServer` (the
    ``build_session`` analogue; ``cache`` is a run.session.BuildCache)."""
    from repro.run.session import build_graph, build_partition

    spec = spec.validate()
    run = spec.run
    if cache is not None:
        g, x = cache.graph(run)
        pg = cache.partition(run, g)
    else:
        g, x = build_graph(run)
        pg = build_partition(run, g)
    cfg = run.model.to_gcn_config(run.graph, run.schedule)
    if spec.serve.ckpt:
        params = _restore_params(spec.serve, run, cfg)
    else:
        params = M.init_params(jax.random.PRNGKey(run.exec.seed), cfg)
    return GNNServer(cfg, g, x, params, serve_cfg=spec.serve,
                     part=np.asarray(pg.part), home=0)
