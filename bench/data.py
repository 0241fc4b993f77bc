"""The benchmark's inputs: the graph and its features. The initial weights
are each architecture's (``bench/models/<model>.py``), drawn from the run's
seed through ``seed_words``.

Everything here is made from numbers in a configuration file and a seed,
by the benchmark and not by the program. The program receives the graph
through its graph-source registry (``register_sources``) and the weights
through its trainer's parameters; the plain reference reads the same
arrays. So the reference never takes a table the program built.

The graph is a stochastic block model with planted class labels, the
stand-in the repository uses for the OGB datasets: ``classes`` blocks,
``avg_degree`` undirected edges per node drawn before mirroring, a share
``homophily`` of edges kept inside the source's block, no self-loops and
no duplicate edges. Features are a class centroid plus Gaussian noise.
The graph is fixed by the configuration (its ``graph.seed``), so every
run of a cell compiles the same shapes; the run's seed draws the weights.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import numpy as np


class BenchGraph(NamedTuple):
    num_nodes: int
    src: np.ndarray         # [E] int32, both directions of every edge
    dst: np.ndarray         # [E] int32
    labels: np.ndarray      # [N] int32
    train_mask: np.ndarray  # [N] bool
    x: np.ndarray           # [N, F] float32


@functools.lru_cache(maxsize=2)
def sbm(nodes: int, classes: int, avg_degree: float, homophily: float,
        feat_dim: int, feat_noise: float, train_fraction: float,
        seed: int) -> BenchGraph:
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, classes, size=nodes).astype(np.int32)
    m = int(nodes * avg_degree / 2)
    src = rng.integers(0, nodes, size=m)
    dst = rng.integers(0, nodes, size=m)
    # Homophilous edges land on a uniform member of the source's block.
    same = rng.random(m) < homophily
    order = np.argsort(labels, kind="stable")
    start = np.searchsorted(labels[order], np.arange(classes))
    size = np.bincount(labels, minlength=classes)
    blk = labels[src[same]]
    dst[same] = order[start[blk] + (rng.random(blk.size) * size[blk]).astype(np.int64)]
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = np.unique(np.concatenate([src * nodes + dst, dst * nodes + src]))
    train = rng.random(nodes) < train_fraction
    frng = np.random.default_rng([seed, 1])
    centroids = frng.normal(size=(classes, feat_dim)).astype(np.float32)
    x = centroids[labels] + np.float32(feat_noise) * frng.normal(
        size=(nodes, feat_dim)).astype(np.float32)
    return BenchGraph(nodes, (key // nodes).astype(np.int32),
                      (key % nodes).astype(np.int32), labels, train,
                      x.astype(np.float32))


def graph_for(config: Dict) -> BenchGraph:
    g, m = config["graph"], config["model"]
    return sbm(int(g["nodes"]), int(m["num_classes"]), float(g["avg_degree"]),
               float(g["homophily"]), int(m["in_dim"]), float(g["feat_noise"]),
               float(g["train_fraction"]), int(g["seed"]))


def register_sources(config: Dict) -> str:
    """Make the configuration's graph reachable as the program's graph and
    feature source ``bench-<name>``; returns that name."""
    from repro.graph.structure import Graph
    from repro.run.spec import FEATURE_SOURCES, GRAPH_SOURCES

    name = f"bench-{config['name']}"
    if name not in GRAPH_SOURCES:
        def graph_source(_spec):
            bg = graph_for(config)
            return Graph(bg.num_nodes, bg.src, bg.dst, labels=bg.labels,
                         train_mask=bg.train_mask)

        GRAPH_SOURCES.add(name, graph_source)
        FEATURE_SOURCES.add(name, lambda _g, _spec: graph_for(config).x)
    return name


def seed_words(seed: int) -> int:
    """A 31-bit key for ``jax.random.PRNGKey`` from any whole seed."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0] & 0x7FFFFFFF)
