"""GAT through the normal path: ``build_session`` -> ``DistributedTrainer``
under ``shard_map`` on one worker, with the attention-weighted bucketed
aggregation. The step's gradient equals JAX's autodiff of the same model
over the dense ELL layout; the attention's spans and counters are in
place; the cases the trainer does not cover are refused."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.seg_aggregate as sa
from repro.core import model as M
from repro.core.layers import gat_aggregate
from repro.graph.structure import ell_from_csr
from repro.run import RunSpec, build_session
from repro.utils import trace

GAT = ["graph.source=sbm", "graph.nodes=512", "graph.classes=5",
       "graph.avg_degree=8", "graph.feat_dim=16", "graph.norm=none",
       "model.model=gat", "model.hidden_dim=32", "model.gat_heads=4",
       "model.gat_out_heads=2", "model.num_layers=3", "model.norm=none",
       "model.dropout=0", "model.label_prop=false", "partition.nparts=1",
       "schedule.agg_backend=ell", "exec.mode=shard_map", "exec.lr=0.005"]


@pytest.fixture(scope="module")
def gat():
    before = trace.snapshot()
    session = build_session(RunSpec().with_overrides(GAT))
    built = trace.snapshot()
    grads, metrics = session.trainer._step(
        *session.trainer._step_args(jax.random.PRNGKey(0)))
    losses = [session.train_epoch()["loss"] for _ in range(5)]
    return {"session": session, "grads": grads, "loss0": float(metrics["loss"]),
            "losses": losses, "epochs": trace.delta(built, trace.snapshot()),
            "build": trace.delta(before, built)}


def test_layers_have_the_published_shapes(gat):
    """Hidden layers: 4 heads concatenated to 32; output: 2 heads of the 5
    classes, averaged; no LayerNorm parameters when norm is none."""
    layers = gat["session"].trainer.params["layers"]
    shapes = [{k: tuple(v.shape) for k, v in p.items()} for p in layers]
    assert shapes[0] == {"w": (16, 32), "a_src": (4, 8), "a_dst": (4, 8), "b": (32,)}
    assert shapes[1] == {"w": (32, 32), "a_src": (4, 8), "a_dst": (4, 8), "b": (32,)}
    assert shapes[2] == {"w": (32, 10), "a_src": (2, 5), "a_dst": (2, 5), "b": (5,)}


def test_graph_attends_to_self(gat):
    s = gat["session"]
    g = s.graph
    assert np.array_equal(np.sort(g.src[g.src == g.dst]), np.arange(g.num_nodes))
    assert s.wd.ell_t_slot is not None
    assert len(s.wd.ell_t_slot) == len(s.wd.ell_t.buckets)


def test_every_attention_parameter_gets_a_gradient(gat):
    for l, p in enumerate(gat["grads"]["layers"]):
        for k in ("w", "a_src", "a_dst", "b"):
            assert float(jnp.abs(p[k]).max()) > 0, (l, k)
            assert bool(jnp.isfinite(p[k]).all()), (l, k)


def test_loss_falls(gat):
    losses = [gat["loss0"]] + gat["losses"]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_step_gradient_is_autodiff_of_the_dense_model(gat):
    """The same model with ``layers.gat_aggregate`` over the max-degree ELL
    layout of the session's graph, differentiated by JAX."""
    s = build_session(RunSpec().with_overrides(GAT))
    tr = s.trainer
    idx, _, valid = (jnp.asarray(a) for a in ell_from_csr(s.pg.local_csr[0]))
    wd = jax.tree_util.tree_map(lambda a: a[0], s.wd)

    def agg(l, wx, scores):
        return gat_aggregate(wx, *scores, idx, valid)

    def loss(p):
        logits = M.forward(p, tr.cfg, wd.x, wd.labels,
                           jnp.zeros_like(wd.train_mask), agg, train=True,
                           dropout_key=jax.random.PRNGKey(0))
        ls, _, cnt = M.loss_and_metrics(logits, wd.labels, wd.train_mask)
        return ls / cnt

    want = jax.grad(loss)(tr.params)
    got, _ = tr._step(*tr._step_args(jax.random.PRNGKey(0)))
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)


def test_epoch_counts_the_sddmm_pass(gat):
    s, c = gat["session"], gat["epochs"]["counters"]
    nnz = s.pg.local_csr[0].nnz
    epochs = gat["epochs"]["spans"]["epoch"]["n"]
    assert c["agg.edges"] == epochs * 3 * 2 * nnz
    assert c["sddmm.edges"] == epochs * 3 * nnz
    assert c["sddmm.edges"] <= c["sddmm.slots"]
    assert c["sddmm.slots"] == epochs * s.trainer._agg_counts["sddmm.slots"]
    assert gat["build"]["spans"]["build/host_data"]["n"] == 1


def _launched_slots(jaxpr, kernel: str) -> int:
    """Slots of every ``pallas_call`` named ``kernel`` in a jaxpr."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            if eqn.params["name"] == kernel:
                gm = eqn.params["grid_mapping"]
                total += int(np.prod(gm.grid)) * gm.block_mappings[0].block_shape[0].block_size
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    total += _launched_slots(sub, kernel)
    return total


def test_slot_counters_match_kernel_launches(monkeypatch):
    # A fresh trainer: a step that ran before was traced on the XLA path.
    tr = build_session(RunSpec().with_overrides(GAT)).trainer
    monkeypatch.setattr(sa, "_use_kernel", lambda policy: True)
    monkeypatch.setattr(sa, "seg_aggregate",
                        functools.partial(sa.seg_aggregate, interpret=True))
    monkeypatch.setattr(sa, "seg_sddmm",
                        functools.partial(sa.seg_sddmm, interpret=True))
    step = jax.make_jaxpr(tr._step)(*tr._step_args(jax.random.PRNGKey(0)))
    counts = tr._agg_counts
    assert counts["agg.slots"] == _launched_slots(step.jaxpr, "seg_aggregate") > 0
    assert counts["sddmm.slots"] == _launched_slots(step.jaxpr, "seg_sddmm") > 0


def test_attention_scopes(gat):
    paths = set(gat["session"].op_scopes()["ops"].values())
    for l in range(3):
        mine = {p for p in paths if p.startswith(f"layer{l}/")}
        assert f"layer{l}/attention" in mine, l
        assert any(p.startswith(f"layer{l}/attention/") for p in mine), l
        assert any(p.startswith(f"layer{l}/aggregate/k") for p in mine), l
        assert any(p.startswith(f"layer{l}/aggregate/agg_bwd/") for p in mine), l
        assert any(p.startswith(f"layer{l}/aggregate/sddmm/") for p in mine), l


@pytest.mark.parametrize("override", [
    "partition.nparts=2", "exec.mode=vmap", "schedule.agg_backend=coo",
])
def test_cases_the_trainer_does_not_cover_are_refused(override):
    from repro.run.spec import SpecError
    spec = RunSpec().with_overrides(GAT + [override])
    with pytest.raises(SpecError, match="model.model=gat trains only as one worker"):
        build_session(spec)
