"""The plain reference's shared parts: the training loop, its key
schedule, the loss, Adam, LayerNorm and the blocked neighbour gather, in
straightforward ``jax.numpy``. Each architecture's forward lives in
``bench/models/<model>.py`` and runs through ``train_steps`` here.

It imports nothing of the program. It trains on the whole graph at once,
with no partition, no halo exchange, no quantization and no kernel:
masked softmax cross entropy over the training nodes whose labels were
not propagated, and Adam (0.9, 0.999, 1e-8, no weight decay). In float32
every matmul runs at ``"highest"`` precision. The neighbour sums gather
each node's neighbour rows in blocks of nodes of about the same degree,
each block padded to its longest list, so that a layer's gathered rows
never sit in memory at once and few padding rows are read.

Randomness follows the training run's key schedule, which is part of
what the run computes: epoch ``e`` draws from ``PRNGKey(1000003 + e)``,
folded with the worker index (0: one worker holds every node) into the
worker key; that key folded with 1 draws the propagated labels
(Bernoulli at ``lp_rate`` over the nodes), and folded with 104729 it is
split once per layer for that layer's dropout mask (Bernoulli at
``1 - dropout`` over the layer's input).

``dtype=jnp.bfloat16`` runs the same arithmetic with inputs, weights,
activations and optimizer state in bfloat16 at default matmul precision:
the benchmark's lower-precision control.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

NODE_BLOCK = 4096
B1, B2, EPS = 0.9, 0.999, 1e-8


def neighbour_lists(rows: np.ndarray, cols: np.ndarray, n: int):
    """Each row's columns as padded lists, for ``gather_sum``.

    Rows are sorted by length and cut into blocks of ``NODE_BLOCK``; each
    block is padded with ``n`` (a zero row) to its longest list, rounded up
    to a multiple of 8, and blocks of one width form one class. Returns the
    classes as ``[blocks, NODE_BLOCK, width]`` int32 arrays, and for each
    row its place in the classes' concatenated output."""
    order = np.argsort(rows, kind="stable")
    r, c = rows[order], cols[order]
    count = np.bincount(r, minlength=n)
    start = np.concatenate([[0], np.cumsum(count)[:-1]])
    n_pad = -(-n // NODE_BLOCK) * NODE_BLOCK
    by_len = np.concatenate([np.argsort(count, kind="stable"),
                             np.full(n_pad - n, -1)]).reshape(-1, NODE_BLOCK)
    width = np.maximum(-(-count[np.maximum(by_len, 0)].max(axis=1) // 8) * 8, 8)
    classes, placed, place = [], 0, np.empty(n, np.int64)
    for w in np.unique(width):
        members = by_len[width == w].reshape(-1)
        real = members >= 0
        slot = np.full(members.size, -1)
        slot[real] = np.arange(members.size)[real]
        at = np.full(n, -1)
        at[members[real]] = slot[real]
        lists = np.full((members.size, int(w)), n, np.int32)
        mine = at[r] >= 0
        lists[at[r[mine]], np.arange(r.size)[mine] - start[r[mine]]] = c[mine]
        place[members[real]] = placed + slot[real]
        placed += members.size
        classes.append(jnp.asarray(lists.reshape(-1, NODE_BLOCK, int(w))))
    return classes, jnp.asarray(place)


def gather_sum(x, lists):
    """out[r] = sum of x over row r's list, block of rows by block."""
    classes, place = lists
    xz = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    out = jnp.concatenate([
        jax.lax.map(lambda blk: jnp.sum(xz[blk], axis=1), cls).reshape(-1, x.shape[1])
        for cls in classes])
    return out[place]


def layer_norm(h, scale, bias, eps=1e-5):
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
    return (h - mu) / jnp.sqrt(var + eps) * scale + bias


def epoch_draws(epoch, n: int, widths, dropout: float, lp_rate: float):
    """The propagated-label draw ``[n]`` and one keep mask per layer,
    ``[n, width]``, of epoch ``epoch``."""
    kw = jax.random.fold_in(jax.random.PRNGKey(1000003 + epoch), 0)
    sel = jax.random.bernoulli(jax.random.fold_in(kw, 1), lp_rate, (n,))
    kd = jax.random.fold_in(kw, 104729)
    keeps = []
    for f in widths:
        kd, sub = jax.random.split(kd)
        keeps.append(jax.random.bernoulli(sub, 1.0 - dropout, (n, f)))
    return sel, keeps


def mean_ce(logits, labels, mask):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    m = mask.astype(nll.dtype)
    return jnp.sum(nll * m) / jnp.sum(m)


def adam(params, grads, mu, nu, step, lr: float):
    """One Adam update; ``step`` counts from 1."""
    mu = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: B2 * v + (1 - B2) * g * g, nu, grads)
    dtype = jax.tree.leaves(grads)[0].dtype
    c1 = (1 - B1 ** step).astype(dtype)
    c2 = (1 - B2 ** step).astype(dtype)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + EPS)),
        params, mu, nu)
    return params, mu, nu


def train_steps(graph, params0, model: Dict, forward, widths, layout,
                steps: int = 3, dtype=jnp.float32,
                loss_keep: Optional[np.ndarray] = None, hook=None) -> Dict:
    """``steps`` full-graph training steps from ``params0``, the first
    being epoch 0, with the configuration's ``model`` section (``lr``,
    ``dropout``, ``label_prop``, ``lp_rate``).

    ``forward(params, x, labels, prop, keeps, dropout, layout, hook)`` is
    the architecture's, ``widths`` its layers' input widths (one dropout
    mask each) and ``layout`` the graph arrays its forward reads. Returns
    the loss before each update, the first step's gradients, and the
    parameters after the last update, all as host float32 arrays.
    ``loss_keep`` restricts the loss to the nodes it marks and ``hook`` is
    passed to the forward, which alters each layer's aggregate with it:
    the hooks by which the calibration plants faults.
    """
    n = graph.num_nodes
    # Every array goes in as an argument, so that the compiled step holds
    # no graph-sized constants.
    data = {"layout": layout,
            "x": jnp.asarray(graph.x, dtype),
            "labels": jnp.asarray(graph.labels),
            "train": jnp.asarray(graph.train_mask),
            "keep_loss": jnp.asarray(np.ones(n, bool) if loss_keep is None
                                     else loss_keep)}
    dropout = float(model["dropout"])
    lp = bool(model["label_prop"])
    lr = float(model["lr"])
    cast = lambda t: jax.tree.map(lambda a: jnp.asarray(a, dtype), t)

    @jax.jit
    def step(params, mu, nu, k, data):
        labels, train = data["labels"], data["train"]
        sel, keeps = epoch_draws(k - 1, n, widths, dropout, float(model["lp_rate"]))
        prop = train & sel if lp else jnp.zeros_like(train)
        mask = (train & ~sel if lp else train) & data["keep_loss"]
        keeps = keeps if dropout > 0 else None
        loss, grads = jax.value_and_grad(
            lambda p: mean_ce(forward(p, data["x"], labels, prop, keeps, dropout,
                                      data["layout"], hook), labels, mask))(params)
        params, mu, nu = adam(params, grads, mu, nu, k.astype(jnp.float32), lr)
        return params, mu, nu, loss, grads

    precision = "highest" if dtype == jnp.float32 else "default"
    params = cast(params0)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses: List[float] = []
    grad1 = None
    with jax.default_matmul_precision(precision):
        for k in range(1, steps + 1):
            params, mu, nu, loss, grads = step(params, mu, nu, jnp.int32(k), data)
            losses.append(float(loss))
            if grad1 is None:
                grad1 = to_host(grads)
    return {"losses": losses, "grad1": grad1, "params": to_host(params)}


def to_host(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)
