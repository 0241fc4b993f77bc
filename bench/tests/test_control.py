"""The lower-precision control: the reference computed in bfloat16, put in
the program's place, must come out as not correct under the cell's limits.
The chip readings at the cell's own size are in PERF.md; this keeps the
control at a size a test run holds, through the path ``calibrate.py``
takes."""

import jax.numpy as jnp


def test_bfloat16_control_is_not_correct(tiny_setup):
    from bench import check, data, models, run as R
    setup = tiny_setup(2048)
    config = setup["config"]
    arch = models.for_config(config, setup["models_dir"])
    graph = data.graph_for(config)
    w0 = R.host_tree(arch.make_weights(config, 2**31 + 11))
    ref = arch.train_steps(graph, w0, config["model"])
    control = arch.train_steps(graph, w0, config["model"], dtype=jnp.bfloat16)
    values = check.readings(control, ref, w0)
    assert not check.judge(values, setup["limits"]), values
