"""The work counts (``bench.flops`` and the GraphSAGE module's) by hand at
a tiny size, pinned on both cells' configurations, and against the
session's shapes."""

import numpy as np
import pytest

from bench import flops, models
from bench.models import sage


def test_model_flops_by_hand():
    # dims 3 -> 4 -> 2 on 5 nodes and 7 edges: per layer 12*n*fi*fo for the
    # update (forward and backward) and 4*e*fi for the aggregation.
    want = (12 * 5 * 3 * 4 + 4 * 7 * 3) + (12 * 5 * 4 * 2 + 4 * 7 * 4)
    assert sage.model_flops({"nodes": 5, "edges": 7, "dims": [3, 4, 2]}) == want == 1396


# Each cell's configuration at 2,048 nodes: the counts, the model FLOPs
# and the aggregation's FLOPs and bytes that the harness's ``bench.flops``
# gave before the GraphSAGE counts moved to ``bench/models/sage.py``.
PINNED = {
    "products-single-1chip": ((2048, 74922, [100, 256, 256, 47]), 2718865824.0,
                              {"flops": 183409056.0, "bytes": 380441376.0}),
    "papers100m-single-1chip": ((2048, 33852, [128, 256, 256, 172]), 3584710656.0,
                                {"flops": 86661120.0, "bytes": 185432896.0}),
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_work_counts_are_the_parents(tiny_setup, cell):
    from bench import data
    setup = tiny_setup(2048, cell)
    config = setup["config"]
    arch = models.for_config(config, setup["models_dir"])
    graph = data.graph_for(config)
    counts = {"nodes": graph.num_nodes, "edges": graph.src.size + graph.num_nodes,
              "dims": arch.widths(config)}
    counts["kernel_calls"] = [{"set": "local", "edges": counts["edges"],
                               "rows": counts["nodes"], "per_epoch": 2}]
    (nodes, edges, dims), model_flops, agg = PINNED[cell]
    assert (counts["nodes"], counts["edges"], counts["dims"]) == (nodes, edges, dims)
    assert arch.KERNELS == ("seg_aggregate",)
    assert arch.kernel_work("seg_aggregate", counts) == agg == flops.aggregation_work(
        counts["kernel_calls"], dims)
    assert arch.kernel_work("another_kernel", counts) is None
    assert models.epoch_work(arch, counts) == {"flops": model_flops,
                                               "kernels": {"seg_aggregate": agg}}


def test_aggregation_work_by_hand():
    calls = [{"edges": 7, "rows": 5, "per_epoch": 2}]
    got = flops.aggregation_work(calls, [3, 4, 2])
    # f=3: 2*(2*7*3) FLOPs, 2*(4*7*3 + 4*5*3 + 8*7) bytes; f=4 likewise.
    assert got["flops"] == 2 * 2 * 7 * 3 + 2 * 2 * 7 * 4 == 196
    assert got["bytes"] == 2 * (84 + 60 + 56) + 2 * (112 + 80 + 56) == 896


@pytest.mark.parametrize("t_flops,t_bytes,bound", [(2.0, 1.0, "compute"),
                                                   (1.0, 3.0, "memory")])
def test_roofline_share_picks_the_larger_bound(t_flops, t_bytes, bound):
    share, b = flops.roofline_share(t_flops * 10, t_bytes * 100, 6.0, 10, 100)
    assert b == bound
    assert share == pytest.approx(100 * max(t_flops, t_bytes) / 6.0)


def test_counts_match_the_session(tiny_setup):
    """The counts the readers use equal what the session built: every real
    edge (self-loops of the mean aggregator included) is one kernel edge on
    the single-worker path, and the nodes and edges of the model FLOPs are
    the program's graph's."""
    from bench import data, run as R
    from repro.run import RunSpec, build_session

    setup = tiny_setup(512)
    config = setup["config"]
    arch = models.for_config(config, setup["models_dir"])
    session = build_session(RunSpec().with_overrides(R.spec_overrides(
        arch, config, setup["traffic"], data.register_sources(config))))
    graph = data.graph_for(config)
    calls = R.kernel_calls(session)
    local = next(c for c in calls if c["set"] == "local")
    assert local["edges"] == graph.src.size + graph.num_nodes
    assert local["edges"] == session.graph.num_edges
    assert local["rows"] == graph.num_nodes == session.graph.num_nodes
    assert sum(c["edges"] for c in calls if c["set"] != "local") == 0
    real_slots = sum(int((np.asarray(b.w) != 0).sum())
                     for b in session.wd.ell.buckets)
    assert real_slots == local["edges"]
