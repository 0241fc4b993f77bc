"""``bench.flops`` by hand at a tiny size, and against the session's shapes."""

import numpy as np
import pytest

from bench import flops


def test_model_flops_by_hand():
    # dims 3 -> 4 -> 2 on 5 nodes and 7 edges: per layer 12*n*fi*fo for the
    # update (forward and backward) and 4*e*fi for the aggregation.
    want = (12 * 5 * 3 * 4 + 4 * 7 * 3) + (12 * 5 * 4 * 2 + 4 * 7 * 4)
    assert flops.model_flops(5, 7, [3, 4, 2]) == want == 1396


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
    session = build_session(RunSpec().with_overrides(R.spec_overrides(
        config, setup["traffic"], data.register_sources(config))))
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
