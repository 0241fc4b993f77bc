"""The program's spans, counters and scopes (``repro.utils.trace``).

Span paths nest, counters add up and snapshots subtract; the spans and
counters add no transfer between host and device; a single-worker step's
compiled ops carry their layer's scope in both directions; and the
kernel-slot counter equals what the kernel wrapper launches.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.seg_aggregate as sa
from repro.run import RunSpec, build_session
from repro.utils import trace

SINGLE = ["graph.nodes=4096", "graph.classes=8", "graph.avg_degree=12",
          "graph.feat_dim=32", "model.hidden_dim=32", "model.num_layers=3",
          "model.dropout=0.5", "model.label_prop=true", "partition.nparts=1",
          "schedule.agg_backend=ell", "exec.mode=shard_map"]


def test_span_paths_nest_and_add_up():
    before = trace.snapshot()
    for _ in range(3):
        with trace.span("outer"):
            with trace.span("inner"):
                pass
            with trace.span("inner"):
                pass
    d = trace.delta(before, trace.snapshot())["spans"]
    assert d["outer"]["n"] == 3 and d["outer/inner"]["n"] == 6
    assert 0 <= d["outer/inner"]["s"] <= d["outer"]["s"]
    row = trace.snapshot()["spans"]["outer/inner"]
    assert 0 <= row["min"] <= row["s"] / row["n"]


def test_span_closes_on_error():
    with pytest.raises(RuntimeError):
        with trace.span("failing"):
            raise RuntimeError("boom")
    with trace.span("after"):
        pass
    assert "after" in trace.snapshot()["spans"]  # not failing/after


def test_counters_and_snapshot_deltas():
    before = trace.snapshot()
    trace.count("test.a")
    trace.count("test.a", 4)
    trace.count("test.b", 2.5)
    mid = trace.snapshot()
    trace.count("test.b", 0.5)
    d1 = trace.delta(before, mid)["counters"]
    d2 = trace.delta(mid, trace.snapshot())
    assert d1["test.a"] == 5 and d1["test.b"] == 2.5
    assert d2["counters"] == {"test.b": 0.5} and d2["spans"] == {}
    # A snapshot is a copy: counting on does not change it.
    assert mid["counters"]["test.b"] - before["counters"].get("test.b", 0) == 2.5


def test_compile_counted_once_per_outermost_event():
    @jax.jit
    def inner_fn(x):
        return jnp.sin(x) * 2

    @jax.jit
    def outer_fn(x):
        return inner_fn(x) + 1

    x = jnp.arange(7.0)
    before = trace.snapshot()
    with trace.span("compiling"):
        outer_fn(x)
    d = trace.delta(before, trace.snapshot())
    c = d["counters"]
    assert c["compile.trace.n[inner_fn]"] == 1 and c["compile.trace.n[outer_fn]"] == 1
    # Tracing outer_fn traced inner_fn inside it: one outermost event.
    assert c["compile.trace.n"] == 1
    assert c["compile.trace.s"] == pytest.approx(c["compile.trace.s[outer_fn]"])
    assert c["compile.backend.n[jit(outer_fn)]"] == 1
    assert d["spans"]["compiling/compile"]["n"] == 3  # trace, lower, compile
    assert d["spans"]["compiling/compile"]["s"] == pytest.approx(
        c["compile.trace.s"] + c["compile.lower.s"] + c["compile.backend.s"])


def test_spans_and_counts_add_no_transfer():
    f = jax.jit(lambda a: a * 2 + 1)
    x = jax.device_put(np.ones((64, 8), np.float32))
    f(x).block_until_ready()
    with jax.transfer_guard("disallow"):
        with trace.span("guarded"):
            with trace.span("call"):
                y = f(x)
            trace.count("guarded.calls")
    assert float(y[0, 0]) == 3.0


@pytest.mark.parametrize("op_name,path", [
    ("jit(step_sm)/jvp(layer0)/aggregate/local/k4/gather", "layer0/aggregate/local/k4"),
    ("jit(step_sm)/transpose(jvp(layer2))/aggregate/local/agg_bwd/k16/"
     "rk,rkf->rf/dot_general", "layer2/aggregate/local/agg_bwd/k16/bwd"),
    ("jit(f)/jvp(layer1)/aggregate/k64/jit(seg_aggregate)/seg_aggregate/pallas_call",
     "layer1/aggregate/k64"),
    ("jit(step_sm)/transpose(jvp(loss))/jit(take_along_axis)/scatter-add", "loss/bwd"),
    ("jit(loss)/jit(_threefry_fold_in)/xor", ""),
    ("jit(step_sm)/jvp()/psum", ""),
])
def test_scope_path(op_name, path):
    assert trace.scope_path(op_name) == path


@pytest.fixture(scope="module")
def single():
    """A 4,096-node single-worker session, its build's spans, one epoch's
    spans and counters, and its step's scopes."""
    before = trace.snapshot()
    session = build_session(RunSpec().with_overrides(SINGLE))
    built = trace.snapshot()
    session.train_epoch()
    epoch = trace.delta(built, trace.snapshot())
    return {"session": session, "build": trace.delta(before, built)["spans"],
            "epoch": epoch, "scopes": session.op_scopes()}


def test_build_and_epoch_spans(single):
    b, e = single["build"], single["epoch"]["spans"]
    for stage in ("graph", "partition", "partition/csr", "partition/ell",
                  "host_data", "lift", "trainer"):
        assert b[f"build/{stage}"]["n"] == 1, stage
    assert b["build/partition/csr"]["s"] <= b["build/partition"]["s"]
    assert all(e[p]["n"] == 1 for p in ("epoch", "epoch/step",
                                        "epoch/optimizer", "epoch/fetch"))


def test_epoch_counts_edges_and_slots(single):
    s, c = single["session"], single["epoch"]["counters"]
    nnz = s.pg.local_csr[0].nnz
    assert c["agg.edges"] == 3 * 2 * nnz  # three layers, forward and backward
    assert c["agg.edges"] <= c["agg.slots"] == s.trainer._agg_counts["agg.slots"]


def _column_steps(ell) -> int:
    """(grid step, slot column) pairs of one aggregation over a
    single-worker layout: each launch's slots over its row tile."""
    return sum(sa.kernel_slots(*b.idx.shape[-2:]) * sa._block_k(b.idx.shape[-1])
               // sa.SLOTS_PER_STEP for b in ell.buckets)


def test_epoch_counts_copies_by_the_kernel_rule(single, copies_by_rule):
    """``agg.copies`` is the kernel's rule counted independently, three
    layers over both layouts; real slots lead every column of a bucket, so
    the round-up wastes at most 7 rows a column and grid step."""
    s, c = single["session"], single["epoch"]["counters"]
    wd = s.trainer.wd
    assert c["agg.copies"] == 3 * (copies_by_rule(wd.ell) + copies_by_rule(wd.ell_t))
    assert c["agg.copies"] == s.trainer._agg_counts["agg.copies"]
    steps = 3 * (_column_steps(wd.ell) + _column_steps(wd.ell_t))
    assert c["agg.edges"] <= c["agg.copies"] <= c["agg.edges"] + 7 * steps


def test_op_scopes_cover_every_layer_both_ways(single):
    scopes = single["scopes"]
    assert scopes["module"] == "jit_step_sm"
    paths = set(scopes["ops"].values())
    for l in range(3):
        mine = {p for p in paths if p.startswith(f"layer{l}/")}
        agg = {p for p in mine if p.startswith(f"layer{l}/aggregate/local/")}
        assert any(p.endswith("/bwd") and "/agg_bwd/" in p for p in agg), l
        assert any(not p.endswith("/bwd") for p in agg), l
        assert f"layer{l}/update" in mine and f"layer{l}/update/bwd" in mine
    # Every op of a degree bucket sits under its layer's aggregation.
    bucket = [p for p in paths if any(q[0] == "k" and q[1:].isdigit()
                                      for q in p.split("/"))]
    assert bucket and all(p.split("/")[1] == "aggregate" for p in bucket)


def _launched_slots(jaxpr) -> int:
    """Slots of every ``pallas_call`` in a jaxpr, nested calls included:
    each grid step works through one block of slot ids."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            gm = eqn.params["grid_mapping"]
            ids = gm.block_mappings[0].block_shape[0].block_size
            total += int(np.prod(gm.grid)) * ids
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    total += _launched_slots(sub)
    return total


@pytest.mark.parametrize("mode", ["vmap", "shard_map"])
def test_slot_counter_matches_kernel_launches(mode, monkeypatch):
    """Two workers, so the receive scatters launch the kernel too; under
    vmap each bucket is one launch over both workers' rows, under
    shard_map each worker's device launches its own."""
    spec = RunSpec().with_overrides(
        [o for o in SINGLE if not o.startswith(("partition.nparts", "exec.mode"))]
        + ["graph.nodes=2048", "partition.nparts=2", f"exec.mode={mode}"])
    tr = build_session(spec).trainer
    monkeypatch.setattr(sa, "_use_kernel", lambda policy: True)
    monkeypatch.setattr(sa, "seg_aggregate",
                        functools.partial(sa.seg_aggregate, interpret=True))
    step = jax.make_jaxpr(tr._step)(*tr._step_args(jax.random.PRNGKey(0)))
    launched = _launched_slots(step.jaxpr) * (2 if mode == "shard_map" else 1)
    assert launched and tr._agg_counts["agg.slots"] == launched
