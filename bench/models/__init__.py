"""One module per architecture: ``bench/models/<model>.py``, where
``<model>`` is the configuration's ``model.model``.

A module holds everything of the benchmark that depends on the
architecture, and nothing of the program:

- ``spec(config)``: the program's ``model.*`` keys and ``graph.norm``;
- ``widths(config)``: each layer's input width, then the output's;
- ``make_weights(config, seed)``: the initial parameters in the program's
  layout, made on the device from the seed;
- ``train_steps(graph, params0, model, steps, dtype, loss_keep, hook)``:
  the plain reference (``bench.reference`` has the shared loop);
- ``KERNELS``: the names of the Pallas kernels that the trace reduces;
- ``model_flops(counts)``: model FLOPs of one epoch;
- ``kernel_work(kernel, counts)``: FLOPs and HBM bytes of one epoch of the
  named kernel, or None.

Adding an architecture needs its module and no edit elsewhere.
"""

from __future__ import annotations

import importlib.util
import os
import re
from types import ModuleType
from typing import Dict

MODELS_DIR = os.path.dirname(os.path.abspath(__file__))


def for_config(config: Dict, models_dir: str = MODELS_DIR) -> ModuleType:
    """The module of the configuration's architecture, loaded from its
    file under ``models_dir``."""
    name = str(config["model"]["model"])
    if not re.fullmatch(r"[A-Za-z0-9_]+", name):
        raise ValueError(f"model.model {name!r} is not a module name")
    path = os.path.join(models_dir, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"configuration {config.get('name')!r} names model {name!r}, "
            f"which has no module: {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"bench_model_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def epoch_work(model: ModuleType, counts: Dict) -> Dict:
    """The yardstick of one epoch that the readers divide: the model's
    FLOPs, and each of its kernels' FLOPs and bytes (None where the module
    does not count that kernel)."""
    return {"flops": model.model_flops(counts),
            "kernels": {k: model.kernel_work(k, counts) for k in model.KERNELS}}
