"""Two-phase LayerProgram (core/exchange.py): overlap-vs-sequential parity.

The issue/finalize refactor changes *op order only* — the overlapped
schedule issues every wire pipeline before the local bucketed aggregation
(inter first), the sequential schedule runs them after — so the acceptance
bar is bit-for-bit equality of losses, parameters and gradients across
{flat, hierarchical} x {fp32, Int2} x {sync, cd>1}, under both the vmap
virtual mesh and the 2-D shard_map mesh, with the backward flowing through
the split quantized custom-VJP. The overlap itself is proved structurally:
the lowered (trace-order) StableHLO issues the wire collectives before the
aggregation dots.

Forward values are bit-for-bit equal. Gradients are equal to a few float32
ulp, not bit for bit: a layer input feeds the local aggregation and every
wire stage, and the backward pass adds those cotangents in reverse program
order, which the overlap changes. The same difference appears with every
op executed eagerly (``jax.disable_jit``), so it is the program's own
reassociation of one sum, not a compiler fusion, and it is confined to the
layers whose input fans out to the wire (the classifier's grads stay
bit-equal).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DistConfig,
    DistributedTrainer,
    GCNConfig,
    prepare_distributed,
)
from repro.core.trainer import make_dist_train_step
from repro.graph import (
    build_hierarchical_partitioned_graph,
    build_partitioned_graph,
    partition_hierarchical,
    sbm_graph,
)
from repro.launch.hlo_stats import collective_order
from repro.launch.mesh import make_hier_worker_mesh

G, W = 2, 2
P = G * W


@pytest.fixture(scope="module")
def setup():
    g = sbm_graph(300, 4, avg_degree=10, homophily=0.85, seed=3)
    rng = np.random.default_rng(5)
    x = rng.integers(0, 4, size=(g.num_nodes, 8)).astype(np.float32)
    gn = g.mean_normalized()
    part = partition_hierarchical(gn, G, W, seed=0)
    hpg = build_hierarchical_partitioned_graph(gn, G, W, part=part, seed=0)
    pgf = build_partitioned_graph(gn, P, part=part, seed=0)
    return gn, x, prepare_distributed(gn, x, hpg), prepare_distributed(gn, x, pgf)


def _cfg():
    return GCNConfig(model="sage", in_dim=8, hidden_dim=16, num_classes=4,
                     num_layers=2, dropout=0.0, label_prop=False)


def _dc(topology, bits, cd, overlap):
    kw = dict(nparts=P, bits=bits, cd=cd, overlap=overlap)
    if topology == "hier":
        kw.update(num_groups=G, group_size=W)
    return DistConfig(**kw)


def _wd(setup, topology):
    return setup[2] if topology == "hier" else setup[3]


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# A few float32 ulp of a leaf's largest value. The reassociated cotangent
# sum (module docstring) moves a result by ~2 eps of the terms it adds;
# those terms partly cancel, so at the scale of the summed gradient the
# grid below reads up to 8.7 eps (and exactly 0 on cd=3's stale epochs,
# where no cotangent flows back through the wire).
ULPS = 16 * np.finfo(np.float32).eps


def _assert_trees_close(a, b):
    """Leafwise |a - b| <= ULPS x max|b|: a few ulp at the leaf's own
    scale (floored at the smallest normal float32, so an all-zero leaf
    must match exactly)."""
    tiny = float(np.finfo(np.float32).tiny)
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        la, lb = np.asarray(la), np.asarray(lb)
        scale = max(tiny, float(np.max(np.abs(lb), initial=0.0)))
        assert float(np.max(np.abs(la - lb), initial=0.0)) <= ULPS * scale


class TestOverlapParity:
    @pytest.mark.parametrize("topology", ["flat", "hier"])
    @pytest.mark.parametrize("bits", [0, 2])
    @pytest.mark.parametrize("cd", [1, 3])
    def test_trajectory_bit_for_bit_vmap(self, setup, topology, bits, cd):
        """Full composition grid, along the overlapped trajectory: at every
        epoch the sequential schedule, stepped from the same state, gives
        the bit-for-bit equal loss and halo cache (the two traces contain
        identical forward ops with identical PRNG folds) and grads within
        a few ulp (the reassociated cotangent sum of the module docstring).
        Each step starts from the same state so that Adam cannot carry one
        step's ulp into the next step's comparison."""
        cfg = _cfg()
        wd = _wd(setup, topology)
        tro = DistributedTrainer(cfg, _dc(topology, bits, cd, True), wd, seed=0)
        trs = DistributedTrainer(cfg, _dc(topology, bits, cd, False), wd, seed=0)
        assert all(s.overlap for s in tro.schedule.stages)
        assert not any(s.overlap for s in trs.schedule.stages)
        for _ in range(4):  # covers the cd=3 refresh epoch 3 + stale epochs
            trs.params, trs._cache = tro.params, tro._cache
            key = jax.random.PRNGKey(tro.epoch)
            out_o = tro._step(*tro._step_args(key))
            out_s = trs._step(*trs._step_args(key))
            _assert_trees_equal(out_o[1:], out_s[1:])
            _assert_trees_close(out_o[0], out_s[0])
            tro.train_epoch()
            trs.epoch = tro.epoch
        trs.params = tro.params
        np.testing.assert_array_equal(tro.evaluate(), trs.evaluate())

    def test_gradient_parity_through_split_vjp(self, setup):
        """Per-worker grads (before the optimizer) match to a few ulp on the
        quantized hierarchical schedule, and the loss bit for bit — the
        backward re-quantized wire runs through the split custom VJP
        (psum_scatter transpose outside, quantized all_to_all inside) in
        both traces."""
        cfg = _cfg()
        wd = setup[2]
        key = jax.random.PRNGKey(7)
        grads, losses = {}, {}
        for overlap in (True, False):
            dc = _dc("hier", 2, 1, overlap)
            step = make_dist_train_step(cfg, dc)
            wd2 = jax.tree_util.tree_map(
                lambda a: a.reshape(G, W, *a.shape[1:]), wd)
            params = __import__("repro.core.model", fromlist=["init_params"]
                                ).init_params(jax.random.PRNGKey(0), cfg)
            fn = jax.jit(jax.vmap(jax.vmap(
                step, axis_name=dc.node_axis, in_axes=(None, 0, None)),
                axis_name=dc.group_axis, in_axes=(None, 0, None)))
            grads[overlap], m = fn(params, wd2, key)
            losses[overlap] = m["loss"]
        _assert_trees_equal(losses[True], losses[False])
        _assert_trees_close(grads[True], grads[False])

    def test_overlap_shard_map_2d_matches_vmap(self, setup):
        """The overlapped hierarchical schedule under the 2-D shard_map
        mesh tracks the nested-vmap virtual mesh (with delayed inter)."""
        cfg = _cfg()
        wd = setup[2]
        dc = DistConfig(nparts=P, num_groups=G, group_size=W, inter_cd=3,
                        overlap=True)
        tr_v = DistributedTrainer(cfg, dc, wd, mode="vmap", seed=0)
        tr_s = DistributedTrainer(cfg, dc, wd, mode="shard_map",
                                  mesh=make_hier_worker_mesh(G, W), seed=0)
        for _ in range(4):
            m_v, m_s = tr_v.train_epoch(), tr_s.train_epoch()
            np.testing.assert_allclose(m_v["loss"], m_s["loss"], rtol=1e-5)

    def test_lowered_shard_map_step_is_the_dispatched_one(self, setup):
        """``lower_step`` before the halo cache exists gives the program
        the first epoch runs: the same lowered module (so a persistent
        compile cache entry written by the AOT compile serves the first
        dispatch), and an executable that accepts the real arguments."""
        cfg = _cfg()
        dc = DistConfig(nparts=P, num_groups=G, group_size=W, inter_cd=3,
                        overlap=True)
        tr = DistributedTrainer(cfg, dc, setup[2], mode="shard_map",
                                mesh=make_hier_worker_mesh(G, W), seed=0)
        lowered = tr.lower_step()
        key = jax.random.PRNGKey(0)
        assert lowered.as_text() == tr._step.lower(
            *tr._step_args(key)).as_text()
        grads, m, _ = lowered.compile()(*tr._step_args(key))
        grads_jit, m_jit, _ = tr._step(*tr._step_args(key))
        assert m["loss"] == m_jit["loss"]
        _assert_trees_equal(grads, grads_jit)


class TestOverlapStructure:
    def test_lowered_order_overlap_vs_sequential(self, setup):
        """Structural proof on the real trainer: the overlapped 2-D
        shard_map step issues the inter-group wire (reduce-scatter first)
        before the first aggregation dot in the lowered module; the
        sequential step does not."""
        cfg = _cfg()
        wd = setup[2]
        orders = {}
        for overlap in (True, False):
            dc = DistConfig(nparts=P, num_groups=G, group_size=W, bits=2,
                            overlap=overlap)
            tr = DistributedTrainer(cfg, dc, wd, mode="shard_map",
                                    mesh=make_hier_worker_mesh(G, W), seed=0)
            orders[overlap] = collective_order(tr.lower_step().as_text())
        assert orders[True]["wire_before_compute"]
        assert orders[True]["inter_wire_before_compute"]
        # Inter-first issue order: the grouped pre-wire psum_scatter over
        # the W-sized node axis opens the wire.
        assert orders[True]["first_wire"]["op"] == "reduce-scatter"
        assert orders[True]["first_wire"]["group_size"] == W
        assert not orders[False]["wire_before_compute"]

    def test_run_layer_compat_matches_phases(self, setup):
        """The run_layer compatibility shim equals explicitly driven
        issue/finalize phases."""
        from repro.core.trainer import _local_aggregate
        wd = setup[2]
        # inter_bits=0: the keyless issue(h, None) below needs an fp32 wire
        # (the hierarchical default is now a quantized inter stage).
        sched = DistConfig(nparts=P, num_groups=G, group_size=W,
                           inter_bits=0, overlap=True).schedule()

        def via_run_layer(h, wd1):
            local = _local_aggregate(h, wd1, "ell")
            out, _ = sched.run_layer(h, local, wd1, None, agg_backend="ell")
            return out

        def via_phases(h, wd1):
            prog = sched.layer_program(wd1, agg_backend="ell")
            inflight = prog.issue(h, None)
            local = _local_aggregate(h, wd1, "ell")
            out, _ = prog.finalize(local, inflight)
            return out

        h = jnp.asarray(np.random.default_rng(0).normal(
            size=(*wd.x.shape[:-1], 8)).astype(np.float32))
        wd2 = jax.tree_util.tree_map(
            lambda a: a.reshape(G, W, *a.shape[1:]), wd)
        h2 = h.reshape(G, W, *h.shape[1:])
        run = lambda f: jax.vmap(jax.vmap(
            f, axis_name="node"), axis_name="group")(h2, wd2)
        np.testing.assert_array_equal(np.asarray(run(via_run_layer)),
                                      np.asarray(run(via_phases)))
