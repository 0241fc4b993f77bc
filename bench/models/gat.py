"""GAT (Velickovic et al., Graph Attention Networks, ICLR 2018,
arXiv:1710.10903) in the inductive form of its section 3.3: the
benchmark's side of the model.

Per layer and head h, over each node's in-neighbours and itself:
``e_ij = LeakyReLU_0.2(a_dst^h . W^h x_i + a_src^h . W^h x_j)``, ``alpha_ij``
the softmax of ``e_ij`` over j, and ``x'_i = sum_j alpha_ij W^h x_j``. The
hidden layers concatenate their heads and apply ELU; the output layer
averages its heads. The bias follows the concatenation or the mean, and
a layer whose input and output widths agree adds its input before the ELU
(the identity skip of the middle layer). LayerNorm, dropout and label
propagation run as the configuration asks, as in ``sage.py``. The
reference runs through ``bench.reference``'s shared loop (key schedule,
loss, Adam); it imports nothing of the program.

The work counts are taken from the graph's real nodes and edges (each
node's self-loop included) and the published widths, never from padded
shapes.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, reference

KERNELS = ("seg_aggregate", "seg_sddmm")
LEAKY_SLOPE = 0.2
# Rows of one reference block: a block gathers [rows, longest list, width].
SUB_BLOCK = 512


class Widths(list):
    """Each layer's input width, then the output's, as the harness passes
    them to the work counts; beside them, each layer's heads."""

    def __init__(self, dims: List[int], heads: List[int]):
        super().__init__(dims)
        self.heads = heads

    def aggregated(self) -> List[int]:
        """The width each layer's attention sums: the concatenated heads of
        a hidden layer, every head of the output layer before the mean."""
        last = len(self) - 2
        return [self[l + 1] * (self.heads[l] if l == last else 1)
                for l in range(len(self) - 1)]


def spec(config: Dict) -> Dict:
    """The program's ``model.*`` keys and the graph's norm (``none``: the
    attention is the normalisation)."""
    m = config["model"]
    return {
        "graph.norm": config["graph"]["norm"],
        "model.model": m["model"], "model.hidden_dim": m["hidden_dim"],
        "model.num_layers": m["num_layers"], "model.dropout": m["dropout"],
        "model.norm": m["norm"], "model.label_prop": m["label_prop"],
        "model.lp_rate": m["lp_rate"], "model.gat_heads": m["gat_heads"],
        "model.gat_out_heads": m["gat_out_heads"],
    }


def widths(config: Dict) -> Widths:
    """Each layer's input width, then the output's."""
    m = config["model"]
    layers = int(m["num_layers"])
    dims = ([int(m["in_dim"])] + [int(m["hidden_dim"])] * (layers - 1)
            + [int(m["num_classes"])])
    return Widths(dims, [int(m["gat_heads"])] * (layers - 1) + [int(m["gat_out_heads"])])


def make_weights(config: Dict, seed: int):
    """Initial parameters in the program's layout, made on the device in one
    jitted call, in float32: per layer a Glorot-uniform ``w`` of ``[in,
    heads x head width]``, Glorot-uniform ``a_src`` and ``a_dst`` of
    ``[heads, head width]``, a zero bias; LayerNorm scale 1 and shift 0
    where the configuration normalises, and with label propagation a
    ``[classes, in_dim]`` label embedding drawn from N(0, 0.02^2)."""
    dims = widths(config)
    agg = dims.aggregated()
    m = config["model"]
    norm, lp = m["norm"] == "layer", bool(m["label_prop"])

    def glorot(key, shape):
        lim = (6.0 / (shape[0] + shape[1])) ** 0.5
        return jax.random.uniform(key, shape, jnp.float32, -lim, lim)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, 3 * (len(dims) - 1) + 1)
        layers = []
        for l, (fi, fo) in enumerate(zip(dims[:-1], dims[1:])):
            heads = dims.heads[l]
            p = {"b": jnp.zeros((fo,), jnp.float32),
                 "w": glorot(keys[3 * l], (fi, agg[l])),
                 "a_src": glorot(keys[3 * l + 1], (heads, agg[l] // heads)),
                 "a_dst": glorot(keys[3 * l + 2], (heads, agg[l] // heads))}
            if norm:
                p["ln_scale"] = jnp.ones((fi,), jnp.float32)
                p["ln_bias"] = jnp.zeros((fi,), jnp.float32)
            layers.append(p)
        params = {"layers": layers}
        if lp:
            params["lp_embed"] = 0.02 * jax.random.normal(
                keys[-1], (dims[-1], dims[0]), jnp.float32)
        return params

    return make(jax.random.PRNGKey(data.seed_words(seed)))


def attention_layout(graph):
    """Each node's in-neighbours and itself as blocked lists
    (``reference.neighbour_lists``, padded with ``n``), cut into blocks of
    ``SUB_BLOCK`` rows, with each list's destination row beside it (``n``
    on padding); and each node's place in the blocks' concatenated output.
    """
    n = graph.num_nodes
    loops = np.arange(n, dtype=graph.src.dtype)
    classes, place = reference.neighbour_lists(
        np.concatenate([graph.dst, loops]), np.concatenate([graph.src, loops]), n)
    row_at = np.full(sum(int(np.prod(c.shape[:2])) for c in classes), n, np.int32)
    row_at[np.asarray(place)] = np.arange(n)
    blocks, start = [], 0
    for c in classes:
        size = int(np.prod(c.shape[:2]))
        rows = row_at[start:start + size].reshape(-1, SUB_BLOCK)
        blocks.append((jnp.asarray(rows), c.reshape(-1, SUB_BLOCK, c.shape[2])))
        start += size
    return blocks, place


def attend(wx, e_src, e_dst, layout):
    """``out[i, head h] = sum_j alpha_ij^h wx[j, head h]`` over i's list,
    block of rows by block. Each block is recomputed in the backward pass
    (``jax.checkpoint``), so the gathered ``[rows, list, width]`` values of
    one block at a time are held."""
    blocks, place = layout
    n, f = wx.shape
    heads = e_src.shape[1]
    pad = lambda a: jnp.concatenate([a, jnp.zeros((1, a.shape[1]), a.dtype)])
    wxz, esz, edz = pad(wx), pad(e_src), pad(e_dst)

    @jax.checkpoint
    def block(args):
        rows, lists = args
        valid = (lists < n)[..., None]
        e = jax.nn.leaky_relu(edz[rows][:, None, :] + esz[lists], LEAKY_SLOPE)
        alpha = jax.nn.softmax(jnp.where(valid, e, -1e30), axis=1)
        alpha = jnp.where(valid, alpha, 0)
        src = wxz[lists].reshape(*lists.shape, heads, f // heads)
        return jnp.einsum("bwh,bwhd->bhd", alpha, src).reshape(-1, f)

    out = jnp.concatenate([jax.lax.map(block, b).reshape(-1, f) for b in blocks])
    return out[place]


def forward(params, x, labels, prop, keeps, dropout: float, layout, hook=None):
    h = x
    if "lp_embed" in params:
        h = h + jnp.where(prop[:, None], params["lp_embed"][labels], 0.0)
    layers = params["layers"]
    for l, p in enumerate(layers):
        if "ln_scale" in p:
            h = reference.layer_norm(h, p["ln_scale"], p["ln_bias"])
        if keeps is not None:
            h = jnp.where(keeps[l], h / (1.0 - dropout), 0.0)
        heads, dh = p["a_src"].shape
        wx = h @ p["w"]
        whh = wx.reshape(-1, heads, dh)
        z = attend(wx, jnp.einsum("nhd,hd->nh", whh, p["a_src"]),
                   jnp.einsum("nhd,hd->nh", whh, p["a_dst"]), layout)
        if hook is not None:
            z = hook(z)
        last = l == len(layers) - 1
        if last:
            z = z.reshape(-1, heads, dh).mean(axis=1)
        z = z + p["b"]
        if z.shape == h.shape:
            z = z + h
        h = z if last else jax.nn.elu(z)
    return h


def train_steps(graph, params0, model: Dict, steps: int = 3,
                dtype=jnp.float32, loss_keep: Optional[np.ndarray] = None,
                hook=None) -> Dict:
    """``reference.train_steps`` with GAT's forward; ``hook`` alters each
    layer's attention output (all heads, before their mean)."""
    dims = ([int(model["in_dim"])]
            + [int(model["hidden_dim"])] * (int(model["num_layers"]) - 1))
    return reference.train_steps(graph, params0, model, forward, dims,
                                 attention_layout(graph), steps, dtype,
                                 loss_keep, hook)


def model_flops(counts: Dict) -> float:
    """Model FLOPs of one full-graph training step.

    Per layer with input width ``fi`` and aggregated width ``F`` (all
    heads; 282 = 6 x 47 in the output layer, before the mean): ``x @ W``
    is ``2 * nodes * fi * F`` forward and twice that backward; the two
    score dot products ``2 * 2 * nodes * F`` forward and twice that
    backward; the attention-weighted sum ``2 * edges * F`` forward, and
    backward its transpose and the SDDMM, ``2 * edges * F`` each.
    ``edges`` counts each directed edge once, self-loops included. The
    per-edge softmax and LeakyReLU (a few operations per edge and head) are
    left out.
    """
    nodes, edges, dims = counts["nodes"], counts["edges"], counts["dims"]
    total = 0.0
    for fi, f in zip(dims[:-1], dims.aggregated()):
        total += 3 * 2.0 * nodes * fi * f
        total += 3 * 4.0 * nodes * f
        total += 3 * 2.0 * edges * f
    return total


def kernel_work(kernel: str, counts: Dict) -> Optional[Dict[str, float]]:
    """FLOPs and HBM bytes of one epoch of the named kernel.

    ``seg_aggregate``, the attention-weighted sum, runs over each edge set
    of ``counts["kernel_calls"]`` ``per_epoch`` times a layer (forward and
    transposed): ``2 e F`` FLOPs; bytes for the gathered rows (``4 e F``),
    the output rows (``4 r F``), and an int32 id and H float32 weights per
    edge (``4 e (1 + H)``). ``seg_sddmm`` runs once a layer over the local
    graph's forward layout: ``2 e F`` FLOPs; the gathered rows (``4 e F``),
    each destination row's gradient (``4 r F``), an id and an edge weight
    per edge (``8 e``) and H float32 results per edge (``4 e H``).
    """
    dims, calls = counts["dims"], counts["kernel_calls"]
    flops = bytes_ = 0.0
    for f, heads in zip(dims.aggregated(), dims.heads):
        if kernel == "seg_aggregate":
            for c in calls:
                e, r, n = c["edges"], c["rows"], c["per_epoch"]
                flops += n * 2.0 * e * f
                bytes_ += n * (4.0 * e * f + 4.0 * r * f + 4.0 * e * (1 + heads))
        elif kernel == "seg_sddmm":
            c = next(c for c in calls if c["set"] == "local")
            e, r = c["edges"], c["rows"]
            flops += 2.0 * e * f
            bytes_ += 4.0 * e * f + 4.0 * r * f + 8.0 * e + 4.0 * e * heads
        else:
            return None
    return {"flops": flops, "bytes": bytes_}
