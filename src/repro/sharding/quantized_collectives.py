"""Beyond-paper: the paper's quantized-communication scheme applied to
dense-training collectives (DESIGN.md §5, EXPERIMENTS.md §Perf).

The GCN halo exchange quantizes boundary-node features before the
all-to-all (§6). The same mechanism transfers to transformer training:

* ``quantized_psum``      — data-parallel gradient all-reduce as
  int8 reduce-scatter (quantize -> a2a -> local reduce in fp32) followed by
  int8 all-gather. Wire volume drops 4x vs fp32 (8x vs fp32 all-reduce's
  2x factor), at the cost of two quantize/dequantize passes.
* ``quantized_all_to_all`` — MoE dispatch/combine payload quantization
  (the token->expert transfer is the bipartite exchange closest to the
  paper's setting).

Both use the decentralized per-row-group zero/scale format from
repro.quant (fp32 params ride along, Eqn 5) and stochastic rounding, so
the Lemma-1 unbiasedness argument carries over. These are OPTIONS —
never part of the paper-faithful baseline.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.quant.stochastic import QuantParams, dequantize, quantize


def quantized_all_to_all(x: jax.Array, axis_name: str, *, bits: int = 8,
                         key: Optional[jax.Array] = None) -> jax.Array:
    """Tiled all_to_all of a [P*R, F] buffer with quantized payload."""
    p = jax.lax.axis_size(axis_name)
    rows, feat = x.shape
    if (rows // p) % 4:
        raise ValueError("rows per destination must be a multiple of 4")
    if key is None:
        key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
    q, params = quantize(x, bits, key)

    def a2a(v):
        return jax.lax.all_to_all(v.reshape(p, -1, *v.shape[1:]), axis_name,
                                  split_axis=0, concat_axis=0).reshape(v.shape)

    qr = a2a(q.astype(jnp.int32))
    zr = a2a(params.zero[:, None])[:, 0]
    sr = a2a(params.scale[:, None])[:, 0]
    return dequantize(qr, QuantParams(zr, sr))


def quantized_psum(g: jax.Array, axis_name: str, *, bits: int = 8,
                   key: Optional[jax.Array] = None) -> jax.Array:
    """All-reduce built as quantized reduce-scatter + quantized all-gather.

    In the paper's vocabulary the reduce-scatter half is *pre-aggregation*
    (partials reduced before transfer) and the all-gather half is
    *post-aggregation* (raw shards transferred, combined at destination).
    ``g``: any-shape fp32 gradient; flattened internally. Padded to
    (P * 4 * lanes) so row groups align with shards.
    """
    p = jax.lax.axis_size(axis_name)
    if key is None:
        key = jax.random.PRNGKey(1)
    key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
    flat = g.reshape(-1)
    lanes = 128
    chunk = p * 4 * lanes
    pad = (-flat.shape[0]) % chunk
    flat = jnp.pad(flat, (0, pad))
    rows = flat.shape[0] // lanes
    x = flat.reshape(rows, lanes)

    # --- quantized reduce-scatter: quantize shards, a2a, dequant, local sum.
    k1, k2 = jax.random.split(key)
    q, params = quantize(x, bits, k1)

    def a2a(v):
        return jax.lax.all_to_all(v.reshape(p, -1, *v.shape[1:]), axis_name,
                                  split_axis=0, concat_axis=0)

    qr = a2a(q.astype(jnp.int32))                       # [P, rows/P, lanes]
    zr = a2a(params.zero[:, None])[..., 0]              # [P, rows/(4P)]
    sr = a2a(params.scale[:, None])[..., 0]
    deq = jax.vmap(lambda qq, zz, ss: dequantize(qq, QuantParams(zz, ss)))(
        qr, zr, sr)
    shard_sum = deq.sum(axis=0)                          # [rows/P, lanes] fp32

    # --- quantized all-gather of the reduced shard.
    q2, params2 = quantize(shard_sum, bits, k2)
    qg = jax.lax.all_gather(q2.astype(jnp.int32), axis_name)   # [P, rows/P, lanes]
    zg = jax.lax.all_gather(params2.zero, axis_name)
    sg = jax.lax.all_gather(params2.scale, axis_name)
    out = jax.vmap(lambda qq, zz, ss: dequantize(qq, QuantParams(zz, ss)))(
        qg, zg, sg)
    out = out.reshape(-1)[: g.size]
    return out.reshape(g.shape)


def quantized_psum_tree(grads, axis_name: str, *, bits: int = 8,
                        key: Optional[jax.Array] = None):
    """quantized_psum over a gradient pytree (one key fold per leaf)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if key is None:
        key = jax.random.PRNGKey(2)
    out = [quantized_psum(l, axis_name, bits=bits,
                          key=jax.random.fold_in(key, i))
           for i, l in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)
