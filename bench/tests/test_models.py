"""The architecture modules (``bench/models/<model>.py``): GraphSAGE's
weights, reference and spec keys equal what the harness made before they
moved there; a configuration that names a module found only under another
root runs through the harness unedited; a model with no module is
refused by name."""

import copy
import json
import os
import re

import jax
import numpy as np
import pytest

from bench import models
from bench import run as R

SEED = 2**31 + 11

# At 2,048 nodes on ``SEED``, as the harness gave them before the move
# (CPU): the reference's three losses, and the float64 norm of every leaf
# of the weights, of the first gradient and of the parameters after three
# steps, in ``jax.tree.leaves`` order (per layer ``b``, ``ln_bias``,
# ``ln_scale``, ``w_neigh``, ``w_self``; then ``lp_embed``).
PINNED = {
    "products-single-1chip": {
        "losses": [5.4712300300598145, 4.828460216522217, 4.353480339050293],
        "weights": [0.0, 0.0, 10.0, 11.964057894563611, 11.94958860338088,
                    0.0, 0.0, 16.0, 16.03151461146272, 16.024318225645708,
                    0.0, 0.0, 16.0, 8.865551544645479, 8.956153766195927,
                    1.3770615287547359],
        "grad1": [0.1534347261089756, 0.20012011024975096, 0.13060456283219507,
                  0.4092528989874142, 1.8449091043314898, 0.09388184003961969,
                  0.14440441543255753, 0.11821263286828271, 0.5208541269035029,
                  1.7891464982895537, 0.04968912224535794, 0.11617745804925574,
                  0.21317184513067405, 0.46721468787345377, 1.1411562985146295,
                  0.0027469540732142253],
        "params": [0.3117616472562048, 0.1926274182013376, 10.012238823529064,
                   12.484122050615136, 12.339043893388542, 0.3020350660032059,
                   0.30062986530315167, 16.009735183667402, 16.906786465627047,
                   16.788819121186876, 0.13671297510587996, 0.29689583102476846,
                   15.582515907398122, 9.107429137685235, 8.46322305719394,
                   2.0185443997304504],
        "spec": "rs-d81a97cff84b",
    },
    "papers100m-single-1chip": {
        "losses": [6.300443172454834, 6.200852394104004, 5.821067810058594],
        "weights": [0.0, 0.0, 11.313708498984761, 13.000010532277624,
                    13.003062132566782, 0.0, 0.0, 16.0, 16.03151461146272,
                    16.024318225645708, 0.0, 0.0, 16.0, 14.32821649684476,
                    14.354246340593326, 2.958927459471682],
        "grad1": [0.10684301376175227, 0.1348675976798922, 0.11182542053774029,
                  0.41349508449668754, 1.4460499199252932, 0.06866748525503949,
                  0.11041315871283691, 0.08870321924249194, 0.4528434256265474,
                  1.3658300569492114, 0.0446281895222124, 0.08404893404781079,
                  0.16346855220777992, 0.43628882180173134, 1.0110417288181932,
                  0.0018311295235547368],
        "params": [0.1663473802241215, 0.11209892966716116, 11.314148205971765,
                   13.114303889058784, 13.125385896766733, 0.16381040049730555,
                   0.16243171395289324, 16.003269376821454, 16.245084059263082,
                   16.218042153652547, 0.14380292602544548, 0.16866125909114585,
                   15.780573383868013, 14.39333638599604, 14.001805492736926,
                   3.2921037974597374],
        "spec": "rs-f22f37906111",
    },
}


def _norms(tree):
    return [float(np.linalg.norm(np.asarray(a, np.float64))) for a in jax.tree.leaves(tree)]


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_sage_weights_and_reference_are_the_parents(tiny_setup, cell):
    from bench import data
    setup = tiny_setup(2048, cell)
    config = setup["config"]
    arch = models.for_config(config, setup["models_dir"])
    want = PINNED[cell]
    w0 = R.host_tree(arch.make_weights(config, SEED))
    ref = arch.train_steps(data.graph_for(config), w0, config["model"])
    assert ref["losses"] == want["losses"]
    for key, tree in (("weights", w0), ("grad1", ref["grad1"]), ("params", ref["params"])):
        assert _norms(tree) == pytest.approx(want[key], rel=1e-12, abs=0), key


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_program_spec_is_the_parents(cell):
    """The program's spec at the cell's own size, as the harness set it
    before the model's keys moved to its module."""
    from bench import data
    from repro.run import RunSpec
    setup = R.load_cell(cell)
    config = setup["config"]
    arch = models.for_config(config, setup["models_dir"])
    spec = RunSpec().with_overrides(R.spec_overrides(
        arch, config, setup["traffic"], data.register_sources(config)))
    assert spec.content_hash() == PINNED[cell]["spec"]
    assert spec.describe() == (f"{PINNED[cell]['spec']} bench-{config['name']} "
                               "[flat 1/hybrid] bits=0 cd=1 agg=ell sage mode=shard_map")


# GraphSAGE under another name, which logs every call it takes beside its
# own file, and names one kernel more.
TOY = '''
import os

from bench.models import sage

KERNELS = ("seg_aggregate", "toy_kernel")
LOG = os.path.join(os.path.dirname(os.path.abspath(__file__)), "calls.log")


def _log(what):
    with open(LOG, "a") as f:
        f.write(what + "\\n")


def spec(config):
    _log("spec")
    return dict(sage.spec(config), **{"model.model": "sage"})


def widths(config):
    _log("widths")
    return sage.widths(config)


def make_weights(config, seed):
    _log("make_weights")
    return sage.make_weights(config, seed)


def train_steps(graph, params0, model, *args, **kwargs):
    _log("train_steps")
    return sage.train_steps(graph, params0, model, *args, **kwargs)


def model_flops(counts):
    _log("model_flops")
    return sage.model_flops(counts)


def kernel_work(kernel, counts):
    _log("kernel_work " + kernel)
    return sage.kernel_work(kernel, counts)
'''


def _root_with(tmp_path, model: str, nodes: int = 2048):
    """A checkout root of one cell, ``toy-single-1chip``, whose
    configuration names ``model``; the rest is copied from the products
    cell."""
    src = R.load_cell("products-single-1chip")
    bench = json.load(open(os.path.join(R.ROOT, "BENCHMARK.json")))
    cell = dict(src["cell"], name="toy-single-1chip", config="toy-products")
    entry = dict(bench["configs"][0], name="toy-products",
                 file="bench/configs/toy-products.json")
    bench.update(workloads=[cell], configs=[entry])
    config = copy.deepcopy(src["config"])
    config.update(name=f"toy-products-{model}-{nodes}")
    config["model"]["model"] = model
    config["graph"]["nodes"] = nodes
    files = {"BENCHMARK.json": bench,
             "bench/configs/toy-products.json": config,
             "bench/traffic/single-worker.json": src["traffic"],
             "bench/limits/toy-single-1chip.json": {"limits": src["limits"]},
             "bench/peaks.json": {"devices": {"cpu": {"bf16_flops_per_s": 1e12,
                                                      "hbm_bytes_per_s": 1e11}}}}
    for rel, obj in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(obj))
    (tmp_path / "bench" / "models").mkdir()
    return tmp_path


def test_a_model_found_only_under_another_root_runs(tmp_path):
    root = _root_with(tmp_path, "toy")
    (root / "bench" / "models" / "toy.py").write_text(TOY)
    assert not os.path.exists(os.path.join(R.HERE, "models", "toy.py"))
    setup = R.load_cell("toy-single-1chip", root=str(root))
    with pytest.raises(R.NoChip):
        R.run(setup, 2**31 + 7, 0.5, False)
    result = R.run(setup, 2**31 + 7, 0.5, False, require_tpu=False)
    assert result["correct"], result["checks"]
    calls = (root / "bench" / "models" / "calls.log").read_text().split("\n")
    assert set(calls) >= {"spec", "widths", "make_weights", "train_steps", "model_flops",
                          "kernel_work seg_aggregate", "kernel_work toy_kernel"}


def test_a_model_with_no_module_is_refused_by_file(tmp_path, monkeypatch):
    import repro.run
    root = _root_with(tmp_path, "nosuch")
    setup = R.load_cell("toy-single-1chip", root=str(root))
    missing = re.escape(os.path.join(str(root), "bench", "models", "nosuch.py"))
    with pytest.raises(FileNotFoundError, match=missing):
        models.for_config(setup["config"], setup["models_dir"])

    def no_build(spec):
        raise AssertionError("the session was built")

    monkeypatch.setattr(repro.run, "build_session", no_build)
    with pytest.raises(FileNotFoundError, match=missing):
        R.run(setup, 1, 0.5, False, require_tpu=False)
    with pytest.raises(ValueError, match="module name"):
        models.for_config({"model": {"model": "../sage"}})

