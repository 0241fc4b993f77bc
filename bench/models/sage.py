"""GraphSAGE, as the paper trains it: the benchmark's side of the model.

Per layer: LayerNorm, dropout, the mean over each node's in-neighbours
and itself, and ``h @ w_self + z @ w_neigh + b``, with ReLU between
layers; before the first layer, masked label propagation adds a learned
class embedding to the features of the nodes whose labels are
propagated. The reference runs it through ``bench.reference``'s shared
loop (key schedule, loss, Adam); it imports nothing of the program.

The work counts are taken from the graph's real nodes and edges and the
published widths, never from padded shapes: a later change that removes
padding then reads as a gain, and this yardstick stays where it is.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, flops, reference

KERNELS = ("seg_aggregate",)


def spec(config: Dict) -> Dict:
    """The program's ``model.*`` keys, and the mean aggregator's norm."""
    m = config["model"]
    return {
        "graph.norm": "mean",
        "model.model": m["model"], "model.hidden_dim": m["hidden_dim"],
        "model.num_layers": m["num_layers"], "model.dropout": m["dropout"],
        "model.norm": m["norm"], "model.label_prop": m["label_prop"],
        "model.lp_rate": m["lp_rate"],
    }


def _dims(model: Dict) -> List[int]:
    return ([int(model["in_dim"])]
            + [int(model["hidden_dim"])] * (int(model["num_layers"]) - 1)
            + [int(model["num_classes"])])


def widths(config: Dict) -> List[int]:
    """Each layer's input width, then the output's."""
    return _dims(config["model"])


def make_weights(config: Dict, seed: int):
    """Initial parameters in the program's layout (LayerNorm scale 1 and
    shift 0, zero bias, Glorot-uniform ``w_self`` and ``w_neigh``, and with
    label propagation a ``[classes, in_dim]`` label embedding drawn from
    N(0, 0.02^2)), made on the device in one jitted call, in float32 as
    they are trained."""
    dims = widths(config)
    lp = bool(config["model"]["label_prop"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, 2 * (len(dims) - 1) + 1)
        layers = []
        for l, (fi, fo) in enumerate(zip(dims[:-1], dims[1:])):
            lim = (6.0 / (fi + fo)) ** 0.5
            layers.append({
                "ln_scale": jnp.ones((fi,), jnp.float32),
                "ln_bias": jnp.zeros((fi,), jnp.float32),
                "b": jnp.zeros((fo,), jnp.float32),
                "w_self": jax.random.uniform(keys[2 * l], (fi, fo), jnp.float32, -lim, lim),
                "w_neigh": jax.random.uniform(keys[2 * l + 1], (fi, fo), jnp.float32, -lim, lim),
            })
        params = {"layers": layers}
        if lp:
            params["lp_embed"] = 0.02 * jax.random.normal(
                keys[-1], (dims[-1], dims[0]), jnp.float32)
        return params

    return make(jax.random.PRNGKey(data.seed_words(seed)))


def neighbour_layout(src: np.ndarray, dst: np.ndarray, n: int):
    """The arrays of the mean over in-neighbours and self: each node's
    in-neighbour lists, its out-neighbour lists (for the backward pass)
    and 1 / (in-degree + 1)."""
    deg = np.bincount(dst, minlength=n).astype(np.float64) + 1.0
    inv = jnp.asarray((1.0 / deg).astype(np.float32))[:, None]
    return (reference.neighbour_lists(dst, src, n),
            reference.neighbour_lists(src, dst, n), inv)


def mean_aggregate(layout):
    """z[v] = mean of x over v's in-neighbours and v itself, with the
    ``neighbour_layout`` arrays (passed in, not baked into the program)."""
    fwd, bwd, inv = layout

    @jax.custom_vjp
    def agg(x):
        return (reference.gather_sum(x, fwd) + x) * inv.astype(x.dtype)

    def agg_fwd(x):
        return agg(x), None

    def agg_bwd(_, g):
        gs = g * inv.astype(g.dtype)
        return (reference.gather_sum(gs, bwd) + gs,)

    agg.defvjp(agg_fwd, agg_bwd)
    return agg


def forward(params, x, labels, prop, keeps, dropout: float, layout, hook=None):
    agg = mean_aggregate(layout)
    h = x
    if "lp_embed" in params:
        h = h + jnp.where(prop[:, None], params["lp_embed"][labels], 0.0)
    layers = params["layers"]
    for l, p in enumerate(layers):
        h = reference.layer_norm(h, p["ln_scale"], p["ln_bias"])
        if keeps is not None:
            h = jnp.where(keeps[l], h / (1.0 - dropout), 0.0)
        z = agg(h) if hook is None else hook(agg(h))
        h = h @ p["w_self"] + z @ p["w_neigh"] + p["b"]
        if l < len(layers) - 1:
            h = jax.nn.relu(h)
    return h


def train_steps(graph, params0, model: Dict, steps: int = 3,
                dtype=jnp.float32, loss_keep: Optional[np.ndarray] = None,
                hook=None) -> Dict:
    """``reference.train_steps`` with GraphSAGE's forward; ``hook`` alters
    each layer's mean aggregate."""
    layout = neighbour_layout(graph.src, graph.dst, graph.num_nodes)
    return reference.train_steps(graph, params0, model, forward, _dims(model)[:-1],
                                 layout, steps, dtype, loss_keep, hook)


def model_flops(counts: Dict) -> float:
    """Model FLOPs of one full-graph training step.

    Per layer with input width ``fi`` and output width ``fo``: the update
    matmuls ``h @ W_self + z @ W_neigh`` are ``2 * 2 * nodes * fi * fo``
    forward, and twice that backward (the gradients of the weights and of
    the inputs); the mean aggregation is ``2 * edges * fi`` forward and the
    same backward. ``edges`` counts each directed edge once, self-loops of
    the mean aggregator included.
    """
    nodes, edges, dims = counts["nodes"], counts["edges"], counts["dims"]
    total = 0.0
    for fi, fo in zip(dims[:-1], dims[1:]):
        total += 3 * 4.0 * nodes * fi * fo
        total += 2 * 2.0 * edges * fi
    return total


def kernel_work(kernel: str, counts: Dict) -> Optional[Dict[str, float]]:
    """The mean aggregation's scalar-weight work, on every layer's input."""
    if kernel != "seg_aggregate":
        return None
    return flops.aggregation_work(counts["kernel_calls"], counts["dims"])
