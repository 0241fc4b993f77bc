"""Fused stochastic-quantize + bit-pack Pallas kernel (paper §7.3).

One pass over a ``(G*4, F)`` row-group tile: compute per-4-row zero/scale,
quantize with precomputed stochastic-rounding noise, and pack ``32/bits``
values into each int32 lane word. Mirrors the paper's fused kernel:

* 4-row grouping ("retrieves 4 rows ... packing four int2 values into one
  int8") — here 4 rows share one (zero, scale) pair and 16 int2 pack into
  one int32 (the TPU lane word).
* reciprocal-multiply instead of the 98-cycle divide (§7.3(3)).
* RNG hoisted out of the kernel (the paper eliminates RNG from the inner
  loop to shorten dependency chains; we pass counter-based uniform bits in).

Dequant kernel unpacks and applies the affine transform in one pass.

Both kernels see their operands in a lane-major layout prepared by the
wrapper (:func:`_lane_major`), so every in-kernel op is elementwise or a
lane reduction: Mosaic refuses the reshapes across the (8, 128) tile and
the rank-1 blocks the row-major form would need.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_GROUP = 4


def _lane_major(a: jax.Array, per_word: int) -> jax.Array:
    """[R, F] -> [per_word, ROW_GROUP, R/ROW_GROUP, F/per_word].

    Element ``(t, i, g, j)`` is ``a[4g + i, j*per_word + t]``: the values
    packed into one word sit on a leading axis (packing is then elementwise
    shifts and ors), and the 4 rows of a group sit on another (the group's
    min/max is elementwise across them plus one lane reduction). No kernel
    reshapes across the (8, 128) tile.
    """
    rows, feat = a.shape
    return a.reshape(rows // ROW_GROUP, ROW_GROUP, feat // per_word,
                     per_word).transpose(3, 1, 0, 2)


def _group_blocks(g: int, block_groups: int) -> int:
    """Row groups per grid step: a multiple of 8 dividing ``g`` (the
    sublane tile of the per-group (zero, scale) column), else all of it."""
    for bg in range(min(block_groups, g) // 8 * 8, 0, -8):
        if g % bg == 0:
            return bg
    return g


def _quant_pack_kernel(x_ref, noise_ref, packed_ref, zero_ref, scale_ref, *,
                       bits: int):
    levels = (1 << bits) - 1
    x = x_ref[...].astype(jnp.float32)           # [pw, 4, bg, fw]
    lo = jnp.min(jnp.min(x, axis=(0, 1)), axis=-1, keepdims=True)  # [bg, 1]
    hi = jnp.max(jnp.max(x, axis=(0, 1)), axis=-1, keepdims=True)
    scale = (hi - lo) * (1.0 / levels)
    # Reciprocal-multiply (no divide in the hot path).
    rcp = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    q = jnp.clip(jnp.floor((x - lo) * rcp + noise_ref[...]), 0, levels)
    q = q.astype(jnp.int32)
    packed = q[0]
    for t in range(1, x.shape[0]):
        packed = packed | jnp.left_shift(q[t], t * bits)
    packed_ref[...] = packed
    zero_ref[...] = lo
    scale_ref[...] = jnp.where(scale > 0, scale, 0.0)


def _dequant_unpack_kernel(packed_ref, zero_ref, scale_ref, out_ref, *,
                           bits: int):
    mask = (1 << bits) - 1
    packed = packed_ref[...]                     # [4, bg, fw]
    scale, zero = scale_ref[...], zero_ref[...]  # [bg, 1]
    for t in range(out_ref.shape[0]):
        q = jax.lax.shift_right_logical(packed, t * bits) & mask
        out_ref[t] = q.astype(jnp.float32) * scale + zero


@functools.partial(jax.jit, static_argnames=("bits", "block_groups", "interpret"))
def quant_pack(
    x: jax.Array,       # [R, F], R % 4 == 0, F % (32/bits) == 0
    noise: jax.Array,   # [R, F] uniform [0,1)
    *,
    bits: int = 2,
    block_groups: int = 64,   # row groups per grid step (256 rows)
    interpret: Optional[bool] = None,
):
    rows, feat = x.shape
    per_word = 32 // bits
    if rows % ROW_GROUP or feat % per_word:
        raise ValueError(f"({rows},{feat}) not aligned to row_group={ROW_GROUP}, per_word={per_word}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    g, fw = rows // ROW_GROUP, feat // per_word
    bg = _group_blocks(g, block_groups)
    data = pl.BlockSpec((per_word, ROW_GROUP, bg, fw), lambda i: (0, 0, i, 0))
    col = pl.BlockSpec((bg, 1), lambda i: (i, 0))
    packed, zero, scale = pl.pallas_call(
        functools.partial(_quant_pack_kernel, bits=bits),
        grid=(g // bg,),
        in_specs=[data, data],
        out_specs=[pl.BlockSpec((ROW_GROUP, bg, fw), lambda i: (0, i, 0)),
                   col, col],
        out_shape=[
            jax.ShapeDtypeStruct((ROW_GROUP, g, fw), jnp.int32),
            jax.ShapeDtypeStruct((g, 1), jnp.float32),
            jax.ShapeDtypeStruct((g, 1), jnp.float32),
        ],
        interpret=interpret,
        name="quant_pack",
    )(_lane_major(x, per_word), _lane_major(noise, per_word))
    return (packed.transpose(1, 0, 2).reshape(rows, fw),
            zero.reshape(g), scale.reshape(g))


@functools.partial(jax.jit, static_argnames=("bits", "feat", "block_groups", "interpret"))
def dequant_unpack(
    packed: jax.Array,  # [R, F*bits/32] int32
    zero: jax.Array,    # [R/4]
    scale: jax.Array,   # [R/4]
    *,
    bits: int = 2,
    feat: int,
    block_groups: int = 64,
    interpret: Optional[bool] = None,
) -> jax.Array:
    rows, fw = packed.shape
    per_word = 32 // bits
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    g = rows // ROW_GROUP
    bg = _group_blocks(g, block_groups)
    col = pl.BlockSpec((bg, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_dequant_unpack_kernel, bits=bits),
        grid=(g // bg,),
        in_specs=[pl.BlockSpec((ROW_GROUP, bg, fw), lambda i: (0, i, 0)),
                  col, col],
        out_specs=pl.BlockSpec((per_word, ROW_GROUP, bg, fw),
                               lambda i: (0, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((per_word, ROW_GROUP, g, fw),
                                       jnp.float32),
        interpret=interpret,
        name="dequant_unpack",
    )(packed.reshape(g, ROW_GROUP, fw).transpose(1, 0, 2),
      zero.reshape(g, 1), scale.reshape(g, 1))
    # [t, i, g, j] -> row 4g + i, column j*per_word + t.
    return out.transpose(2, 1, 3, 0).reshape(rows, feat)
