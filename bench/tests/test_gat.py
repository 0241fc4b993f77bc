"""The GAT cell (``bench/models/gat.py``): its reference, the program
through ``build_session`` against it over the three checked steps, and its
work counts against a count by hand."""

import jax
import numpy as np
import pytest

from bench import models
from bench import run as R

CELL = "gat-products-single-1chip"
SEED = 2**31 + 11

# At 2,048 nodes on ``SEED`` (CPU): the reference's three losses, and the
# float64 norm of every leaf of the weights, of the first gradient and of
# the parameters after three steps, in ``jax.tree.leaves`` order (per layer
# ``a_dst``, ``a_src``, ``b``, ``w``).
PINNED = {
    "losses": [3.8698151111602783, 2.7532761096954346, 2.316624402999878],
    "weights": [2.7804579016928836, 2.811417278278303, 0.0, 13.492114111046845,
                2.8483447104649096, 2.833760799066367, 0.0, 32.0189362557457,
                3.19883944607012, 3.2274569081903777, 0.0, 21.01004104070003],
    "grad1": [0.034749305645860026, 0.47256686899493, 0.026004378232414124,
              1.2740637114133682, 0.010895952857306532, 0.04351150823573936,
              0.020277056481157706, 0.5764796690726685, 0.01855867636226338,
              0.050505578669246994, 0.03870166720389327, 0.8125951155919543],
    "params": [2.851174061614012, 2.7980005194724247, 0.25190628225906714,
               13.755248859784434, 2.863417114315319, 2.810123358184086,
               0.2294799352484562, 33.30625623485452, 3.2178211893333963,
               3.1922866502396263, 0.04339466196100507, 21.61790765018565],
}

# The program against the reference on the CPU, where both sum in float32
# and differ in the order of the sums alone (the bucketed layout's slot
# order against the reference's neighbour lists, the softmax's max and
# the hand-written backward against JAX's): a few float32 ulps of each
# loss, and of each leaf's norm compounded over three Adam steps.
CPU_TOLERANCE = {"loss_gap": 1e-4, "grad_norm_gap": 1e-4, "update_norm_gap": 1e-3}


def _norms(tree):
    return [float(np.linalg.norm(np.asarray(a, np.float64))) for a in jax.tree.leaves(tree)]


def test_weights_and_reference_are_pinned(tiny_setup):
    from bench import data
    setup = tiny_setup(2048, CELL)
    config = setup["config"]
    arch = models.for_config(config, setup["models_dir"])
    w0 = R.host_tree(arch.make_weights(config, SEED))
    ref = arch.train_steps(data.graph_for(config), w0, config["model"])
    assert ref["losses"] == pytest.approx(PINNED["losses"], rel=1e-6)
    for key, tree in (("weights", w0), ("grad1", ref["grad1"]), ("params", ref["params"])):
        assert _norms(tree) == pytest.approx(PINNED[key], rel=1e-5, abs=1e-7), key


def test_program_follows_the_reference(tiny_setup):
    """``build_session`` with the cell's spec (GAT, one worker, shard_map,
    the bucketed layout) for the three checked steps: every number of
    ``correct`` within the CPU tolerances, and the cell's limits met."""
    result = R.run(tiny_setup(2048, CELL), SEED, 0.5, False, require_tpu=False)
    for name, tol in CPU_TOLERANCE.items():
        assert result["checks"][name]["value"] <= tol, (name, result["checks"])
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0


def test_program_spec(tiny_setup):
    from bench import data
    from repro.run import RunSpec
    setup = tiny_setup(2048, CELL)
    config = setup["config"]
    arch = models.for_config(config, setup["models_dir"])
    spec = RunSpec().with_overrides(R.spec_overrides(
        arch, config, setup["traffic"], data.register_sources(config)))
    assert spec.describe().endswith("[flat 1/hybrid] bits=0 cd=1 agg=ell gat mode=shard_map")
    m = spec.model
    assert (m.hidden_dim, m.gat_heads, m.gat_out_heads, m.norm, m.dropout) == (
        1024, 4, 6, "none", 0.0)
    assert spec.graph.norm == "none" and spec.exec.lr == 0.005


def test_work_counts_by_hand():
    """Four nodes and six edges (two of them, and each node's self-loop,
    counted once: 2 + 4), widths 3 -> 8 -> 8 -> 2 with 2, 2 and 3 heads:
    the attention sums widths 8, 8 and 3 x 2 = 6."""
    gat = models.for_config({"model": {"model": "gat"}})
    dims = gat.Widths([3, 8, 8, 2], [2, 2, 3])
    assert dims.aggregated() == [8, 8, 6]
    calls = [{"set": "local", "edges": 6, "rows": 4, "per_epoch": 2},
             {"set": "flat", "edges": 0, "rows": 0, "per_epoch": 2}]
    counts = {"nodes": 4, "edges": 6, "dims": dims, "kernel_calls": calls}
    # Per layer 6 N fi F (x @ W, three times) + 12 N F (two score dots,
    # three times) + 6 E F (the sum, its transpose, the SDDMM):
    # 576 + 384 + 288, 1536 + 384 + 288, 1152 + 288 + 216.
    assert gat.model_flops(counts) == 5112
    # Twice a layer 2 E F FLOPs; bytes 2 (4 E F + 4 R F + 4 E (1 + H)):
    # F = 8, H = 2: 192 and 784, twice; F = 6, H = 3: 144 and 672.
    assert gat.kernel_work("seg_aggregate", counts) == {"flops": 528, "bytes": 2240}
    # Once a layer 2 E F; bytes 4 E F + 4 R F + 8 E + 4 E H:
    # 96 and 416, twice; 72 and 360.
    assert gat.kernel_work("seg_sddmm", counts) == {"flops": 264, "bytes": 1192}
    assert gat.kernel_work("other", counts) is None
