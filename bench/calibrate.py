#!/usr/bin/env python3
"""Readings from which the limits of ``correct`` are set.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... [--control-seeds 1,2,3]

One process builds the cell's session once and, for each seed, puts that
seed's weights in place and drives the three checked epochs through
``Session.train_epoch``, as a run does. Once the program's state is freed
it runs the architecture's reference (``bench/models/<model>.py``) for
each seed and prints, per seed, the three numbers of ``bench.check``:

- ``program``: the program against the float32 reference (the sound
  readings; the lower end of each limit);
- ``control``: the reference computed in bfloat16, put in the program's
  place (``--control-seeds``);
- faults planted in the reference put in the program's place: ``half``
  (the loss over every other training node only) and ``rows`` (every
  16th node's aggregate lost).

A step that returns its state unchanged reads 1 on ``update_norm_gap``
and needs no run. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as R  # noqa: E402


def program_readings(arch, setup, seeds):
    from bench import data
    from repro.run import RunSpec, build_session
    config, traffic = setup["config"], setup["traffic"]
    source = data.register_sources(config)
    session = build_session(RunSpec().with_overrides(
        R.spec_overrides(arch, config, traffic, source)))
    tr = session.trainer
    opt0, params0 = tr.opt_state, tr.params
    out = {}
    for seed in seeds:
        tr.params, tr.opt_state, tr.epoch = params0, opt0, 0
        R.place_weights(tr, arch.make_weights(config, seed))
        out[seed] = R.checked_steps(session)
        R.log(f"program seed {seed}: losses {out[seed]['losses']}")
    return out


def every_16th_row_lost(z):
    import jax.numpy as jnp
    keep = (jnp.arange(z.shape[0]) % 16 != 0)[:, None]
    return jnp.where(keep, z, 0)


def main() -> int:
    import jax.numpy as jnp
    import numpy as np

    from bench import check, data, models
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    ints = lambda s: [int(v) for v in s.split(",") if v]
    seeds, cseeds = ints(args.seeds), ints(args.control_seeds)
    setup = R.load_cell(args.workload)
    config = setup["config"]
    arch = models.for_config(config, setup["models_dir"])
    R.require_chips(setup["cell"]["chips"])
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    prog = program_readings(arch, setup, seeds)
    gc.collect()
    graph = data.graph_for(config)
    model = config["model"]
    half = np.arange(graph.num_nodes) % 2 == 0
    for seed in sorted(set(seeds) | set(cseeds)):
        w0 = R.host_tree(arch.make_weights(config, seed))
        ref = arch.train_steps(graph, w0, model)
        row = {"seed": seed, "ref_losses": ref["losses"]}
        if seed in prog:
            row["program"] = check.readings(prog[seed], ref, w0)
        if seed in cseeds:
            row["control"] = check.readings(
                arch.train_steps(graph, w0, model, dtype=jnp.bfloat16), ref, w0)
            row["half"] = check.readings(
                arch.train_steps(graph, w0, model, loss_keep=half), ref, w0)
            row["rows"] = check.readings(
                arch.train_steps(graph, w0, model, hook=every_16th_row_lost), ref, w0)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
