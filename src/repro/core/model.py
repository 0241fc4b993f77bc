"""GCN model: config, parameter init, forward pass (Fig 2 flow).

The forward is parameterized by ``agg_fn(layer, h) -> z`` so the identical
model runs on a single device (full-graph ELL aggregation) or distributed
(local aggregation + pre/post halo exchange). GAT's aggregation is weighted
by attention computed from the layer's parameters, so a GAT layer calls
``agg_fn(layer, W x, scores=(e_src, e_dst))`` (``layers.gat_layer``). Quantization and masked label
propagation (§6.1) are part of the model flow:

  (1) masked LP: random subset of train labels embedded into the features,
  (2) LayerNorm before every GCN layer (outlier removal for quantization),
  (3) aggregation (+ quantized communication inside ``agg_fn``),
  (4) UPDATE (linear transform / MLP), repeat.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.core import layers as L


@dataclass(frozen=True)
class GCNConfig:
    model: str = "sage"          # gcn | sage | gin | gat
    in_dim: int = 128
    hidden_dim: int = 256        # paper Table 2: 256 (128 for UK-2007-05)
    num_classes: int = 40
    num_layers: int = 3          # paper: three-layer GraphSAGE
    dropout: float = 0.5
    norm: str = "layer"          # LayerNorm before each layer (Table 2)
    label_prop: bool = True      # masked label propagation (§6.1)
    lp_rate: float = 0.5         # fraction of train labels propagated
    quant_bits: int = 0          # 0 = fp32 comm; 2 = paper's Int2 scheme
    gat_heads: int = 4           # GAT hidden layers: heads, concatenated
    gat_out_heads: int = 1       # GAT output layer: heads, averaged

    def dims(self) -> List[int]:
        return [self.in_dim] + [self.hidden_dim] * (self.num_layers - 1) + [self.num_classes]


def init_params(key: jax.Array, cfg: GCNConfig) -> Dict:
    ks = jax.random.split(key, cfg.num_layers + 1)
    dims = cfg.dims()
    last = cfg.num_layers - 1
    params: Dict = {
        "layers": [
            L.init_layer(ks[i], cfg.model, dims[i], dims[i + 1],
                         cfg.gat_out_heads if i == last else cfg.gat_heads,
                         concat=i < last, norm=cfg.norm == "layer")
            for i in range(cfg.num_layers)
        ]
    }
    if cfg.label_prop:
        params["lp_embed"] = (
            jax.random.normal(ks[-1], (cfg.num_classes, cfg.in_dim)) * 0.02
        )
    return params


def lp_masks(
    key: jax.Array, train_mask: jax.Array, rate: float
) -> tuple[jax.Array, jax.Array]:
    """Split train nodes into (propagate labels, compute loss) — §2.5.

    Propagated labels are *excluded* from the loss to avoid label leakage.
    """
    sel = jax.random.bernoulli(key, rate, train_mask.shape)
    prop_mask = train_mask & sel
    loss_mask = train_mask & ~sel
    return prop_mask, loss_mask


def forward(
    params: Dict,
    cfg: GCNConfig,
    x: jax.Array,                    # [N, in_dim] node features
    labels: jax.Array,               # [N] int labels
    prop_mask: jax.Array,            # [N] bool: labels embedded into features
    agg_fn: Callable[..., jax.Array],
    *,
    train: bool = False,
    dropout_key: Optional[jax.Array] = None,
) -> jax.Array:
    h = x
    if cfg.label_prop:
        with jax.named_scope("label_prop"):
            emb = params["lp_embed"][jnp.clip(labels, 0, cfg.num_classes - 1)]
            h = h + jnp.where(prop_mask[:, None], emb, 0.0)
    for l, p in enumerate(params["layers"]):
        with jax.named_scope(f"layer{l}"):
            if cfg.norm == "layer":
                with jax.named_scope("norm"):
                    h = L.layer_norm(h, p["ln_scale"], p["ln_bias"])
            if train and cfg.dropout > 0:
                with jax.named_scope("dropout"):
                    dropout_key, sub = jax.random.split(dropout_key)
                    keep = jax.random.bernoulli(sub, 1.0 - cfg.dropout, h.shape)
                    h = jnp.where(keep, h / (1.0 - cfg.dropout), 0.0)
            if cfg.model == "gat":
                # Attention reads the layer's parameters: the layer calls
                # agg_fn(l, W x, scores=(e_src, e_dst)) itself.
                h = L.gat_layer(p, h, functools.partial(agg_fn, l),
                                last=l == cfg.num_layers - 1)
                continue
            with jax.named_scope("aggregate"):
                z = agg_fn(l, h)
            with jax.named_scope("update"):
                h = L.apply_update(cfg.model, p, h, z)
                if l < cfg.num_layers - 1:
                    h = jax.nn.relu(h)
    return h


def loss_and_metrics(
    logits: jax.Array, labels: jax.Array, loss_mask: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Masked softmax cross entropy. Returns (loss_sum, correct_sum, count)."""
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
        m = loss_mask.astype(jnp.float32)
        loss_sum = jnp.sum(nll * m)
        correct = jnp.sum((jnp.argmax(logits, -1) == labels).astype(jnp.float32) * m)
        return loss_sum, correct, jnp.sum(m)
