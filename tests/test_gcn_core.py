"""GCN core: model semantics, distributed == single-device equivalence,
quantized communication, convergence (paper Figs 2, 11; §6)."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DistConfig,
    DistributedTrainer,
    GCNConfig,
    init_params,
    prepare_distributed,
    prepare_single,
    train_gcn_single,
)
from repro.core import model as M
from repro.core.trainer import _dist_forward, make_single_agg_fn
from repro.graph import build_partitioned_graph, sbm_graph
from repro.graph.generators import sbm_features


@pytest.fixture(scope="module")
def sbm_setup():
    g = sbm_graph(600, 5, avg_degree=12, homophily=0.85, seed=0)
    x, _ = sbm_features(g, 16, noise=1.5, seed=1)
    return g, x


def _cfg(**kw):
    base = dict(model="sage", in_dim=16, hidden_dim=32, num_classes=5,
                num_layers=2, dropout=0.0, label_prop=False)
    base.update(kw)
    return GCNConfig(**base)


class TestDistributedEquivalence:
    @pytest.mark.parametrize("model", ["gcn", "sage", "gin"])
    def test_dist_forward_equals_single(self, sbm_setup, model):
        """Virtual-worker forward (vmap + halo exchange) must equal the
        single-device full-graph forward exactly (fp32, no dropout/LP)."""
        g, x = sbm_setup
        cfg = _cfg(model=model)
        gn = g.mean_normalized()
        params = init_params(jax.random.PRNGKey(0), cfg)
        data = prepare_single(g, x)
        agg = make_single_agg_fn(data)
        logits_single = M.forward(params, cfg, data.x, data.labels,
                                  jnp.zeros(g.num_nodes, bool), agg)

        nparts = 4
        pg = build_partitioned_graph(gn, nparts, strategy="hybrid", seed=0)
        wd = prepare_distributed(gn, x, pg)
        dc = DistConfig(nparts=nparts, bits=0)

        def worker(p, w):
            logits, _ = _dist_forward(p, cfg, dc, w, jnp.zeros_like(w.train_mask),
                                      None, False)
            return logits
        logits_dist = jax.vmap(worker, axis_name=dc.axis_name,
                               in_axes=(None, 0))(params, wd)
        # reassemble global order
        out = np.zeros((g.num_nodes, cfg.num_classes), np.float32)
        for p in range(nparts):
            out[pg.owned[p]] = np.asarray(logits_dist[p])[: len(pg.owned[p])]
        np.testing.assert_allclose(out, np.asarray(logits_single),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("strategy", ["hybrid", "pre", "post"])
    def test_strategies_agree(self, sbm_setup, strategy):
        """All three remote-graph strategies compute the same aggregation."""
        g, x = sbm_setup
        cfg = _cfg()
        gn = g.mean_normalized()
        params = init_params(jax.random.PRNGKey(1), cfg)
        dc = DistConfig(nparts=3, bits=0)
        outs = {}
        for strat in ("hybrid", strategy):
            pg = build_partitioned_graph(gn, 3, strategy=strat, seed=0)
            wd = prepare_distributed(gn, x, pg)

            def worker(p, w):
                logits, _ = _dist_forward(p, cfg, dc, w,
                                          jnp.zeros_like(w.train_mask), None, False)
                return logits
            lg = jax.vmap(worker, axis_name=dc.axis_name,
                          in_axes=(None, 0))(params, wd)
            out = np.zeros((g.num_nodes, cfg.num_classes), np.float32)
            for p in range(3):
                out[pg.owned[p]] = np.asarray(lg[p])[: len(pg.owned[p])]
            outs[strat] = out
        np.testing.assert_allclose(outs[strategy], outs["hybrid"],
                                   rtol=1e-4, atol=1e-4)


class TestQuantizedComm:
    def test_int2_close_to_fp32_forward(self, sbm_setup):
        g, x = sbm_setup
        cfg = _cfg(norm="layer")  # LayerNorm keeps quantization error bounded
        gn = g.mean_normalized()
        params = init_params(jax.random.PRNGKey(2), cfg)
        pg = build_partitioned_graph(gn, 4, strategy="hybrid", seed=0)
        wd = prepare_distributed(gn, x, pg)

        def run(bits):
            dc = DistConfig(nparts=4, bits=bits)
            def worker(p, w):
                logits, _ = _dist_forward(p, cfg, dc, w,
                                          jnp.zeros_like(w.train_mask),
                                          jax.random.PRNGKey(3), False)
                return logits
            return jax.vmap(worker, axis_name=dc.axis_name,
                            in_axes=(None, 0))(params, wd)

        lg32 = run(0)
        lg8 = run(8)
        lg2 = run(2)
        err8 = float(jnp.abs(lg8 - lg32).max())
        err2 = float(jnp.abs(lg2 - lg32).max())
        scale = float(jnp.abs(lg32).max())
        assert err8 < 0.05 * scale + 1e-3
        assert err2 < 0.8 * scale          # int2 is coarse but bounded
        assert err8 < err2                 # more bits -> closer to fp32

    def test_quantized_halo_grads_flow(self, sbm_setup):
        """Backward through the quantized all_to_all must produce finite,
        non-zero gradients (Lemma 1's unbiased-gradient path)."""
        g, x = sbm_setup
        cfg = _cfg()
        gn = g.mean_normalized()
        params = init_params(jax.random.PRNGKey(4), cfg)
        pg = build_partitioned_graph(gn, 4, strategy="hybrid", seed=0)
        wd = prepare_distributed(gn, x, pg)
        dc = DistConfig(nparts=4, bits=2)

        def worker(p, w, key):
            def loss(pp):
                logits, _ = _dist_forward(pp, cfg, dc, w, jnp.zeros_like(w.train_mask),
                                          key, False)
                ls, _, cnt = M.loss_and_metrics(logits, w.labels, w.train_mask)
                return jax.lax.psum(ls, dc.axis_name) / jnp.maximum(
                    jax.lax.psum(cnt, dc.axis_name), 1.0)
            return jax.grad(loss)(p)
        grads = jax.vmap(worker, axis_name=dc.axis_name,
                         in_axes=(None, 0, None))(params, wd, jax.random.PRNGKey(5))
        leaves = jax.tree_util.tree_leaves(grads)
        assert all(bool(jnp.isfinite(l).all()) for l in leaves)
        total = sum(float(jnp.abs(l).sum()) for l in leaves)
        assert total > 0


class TestTraining:
    def test_single_device_learns(self, sbm_setup):
        g, x = sbm_setup
        cfg = _cfg(model="sage", dropout=0.3, label_prop=True, norm="layer")
        _, hist = train_gcn_single(g, x, cfg, epochs=25, lr=0.01, log_every=25)
        assert hist[-1]["eval_acc"] > 0.85

    def test_single_device_gat_learns(self, sbm_setup):
        """GAT on the single-device path: the bucketed attention op, 4
        concatenated hidden heads and 2 averaged output heads."""
        g, x = sbm_setup
        cfg = _cfg(model="gat", gat_heads=4, gat_out_heads=2, norm="none")
        _, hist = train_gcn_single(g, x, cfg, epochs=25, lr=0.005, log_every=25)
        assert hist[-1]["eval_acc"] > 0.85

    @pytest.mark.parametrize("bits", [0, 2])
    def test_distributed_learns(self, sbm_setup, bits):
        g, x = sbm_setup
        cfg = _cfg(dropout=0.2, label_prop=True, norm="layer")
        gn = g.mean_normalized()
        pg = build_partitioned_graph(gn, 4, strategy="hybrid", seed=0)
        wd = prepare_distributed(gn, x, pg)
        tr = DistributedTrainer(cfg, DistConfig(nparts=4, bits=bits, lr=0.01),
                                wd, mode="vmap", seed=0)
        hist = tr.fit(25, log_every=25)
        assert hist[-1]["eval_acc"] > 0.8, (bits, hist)

    def test_delayed_comm_baseline_runs(self, sbm_setup):
        """DistGNN-style cd-3: stale halo reuse still converges (slower)."""
        g, x = sbm_setup
        cfg = _cfg(dropout=0.0, label_prop=False, norm="layer")
        gn = g.mean_normalized()
        pg = build_partitioned_graph(gn, 4, strategy="hybrid", seed=0)
        wd = prepare_distributed(gn, x, pg)
        tr = DistributedTrainer(cfg, DistConfig(nparts=4, bits=0, cd=3, lr=0.01),
                                wd, mode="vmap", seed=0)
        hist = tr.fit(15, log_every=15)
        assert np.isfinite(hist[-1]["loss"])
        assert hist[-1]["eval_acc"] > 0.5


class TestMaskedLabelProp:
    def test_masks_disjoint(self):
        train = jnp.array([True] * 50 + [False] * 50)
        prop, loss = M.lp_masks(jax.random.PRNGKey(0), train, 0.5)
        assert not bool((prop & loss).any())
        assert bool(((prop | loss) == train).all())

    def test_lp_embedding_changes_forward(self, sbm_setup):
        g, x = sbm_setup
        cfg = _cfg(label_prop=True)
        params = init_params(jax.random.PRNGKey(0), cfg)
        data = prepare_single(g, x)
        agg = make_single_agg_fn(data)
        no_prop = M.forward(params, cfg, data.x, data.labels,
                            jnp.zeros(g.num_nodes, bool), agg)
        with_prop = M.forward(params, cfg, data.x, data.labels,
                              data.train_mask, agg)
        assert float(jnp.abs(no_prop - with_prop).max()) > 1e-4
