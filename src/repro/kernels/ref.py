"""Pure-jnp oracles for the Pallas kernels.

These define the semantics the kernels must match (asserted across
shape/dtype sweeps in tests/test_kernels.py).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def seg_aggregate_ref(
    x: jax.Array,      # [N, F] source features
    ell_idx: jax.Array,  # [R, K] int32 source ids per dst-row slot
    ell_w: jax.Array,    # [R, K] f32 edge weights (0 = padding), or [R, K, H]
) -> jax.Array:
    """out[r] = sum_k ell_w[r, k] * x[ell_idx[r, k]] — the paper's index_add/SpMM.

    With ``[R, K, H]`` weights, F holds H equal column groups (heads) and
    head h of row r is weighted by ``ell_w[r, k, h]``."""
    gathered = x[ell_idx]                      # [R, K, F]
    if ell_w.ndim == 2:
        return jnp.einsum("rk,rkf->rf", ell_w.astype(x.dtype), gathered)
    r, k, heads = ell_w.shape
    out = jnp.einsum("rkh,rkhd->rhd", ell_w.astype(x.dtype),
                     gathered.reshape(r, k, heads, -1))
    return out.reshape(r, x.shape[1])


def seg_sddmm_ref(
    g: jax.Array,        # [R, F] destination rows
    x: jax.Array,        # [N, F] source rows
    ell_idx: jax.Array,  # [R, K] int32
    ell_w: jax.Array,    # [R, K] f32 (0 = padding)
    heads: int,
) -> jax.Array:
    """out[r, k, h] = <g[r, head h], x[ell_idx[r, k], head h]>, 0 on
    padding slots: the SDDMM behind an attention-weighted aggregation's
    gradient with respect to its slot weights."""
    r, k = ell_idx.shape
    gathered = x[ell_idx].reshape(r, k, heads, -1)
    dots = jnp.einsum("rhd,rkhd->rkh", g.reshape(r, heads, -1), gathered)
    return jnp.where((ell_w != 0)[..., None], dots, 0.0)


def quant_pack_ref(
    x: jax.Array,        # [R, F] fp32, R % row_group == 0, F % (32//bits) == 0
    noise: jax.Array,    # [R, F] uniform [0,1) stochastic-rounding noise
    bits: int,
    row_group: int = 4,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused per-row-group minmax + stochastic quantize + bit-pack.

    Returns (packed int32 [R, F*bits/32], zero [R/row_group], scale [R/row_group]).
    """
    rows, feat = x.shape
    levels = (1 << bits) - 1
    g = rows // row_group
    xg = x.reshape(g, row_group * feat)
    lo = xg.min(axis=1)
    hi = xg.max(axis=1)
    scale = (hi - lo) / levels
    rcp = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    xs = (x.reshape(g, row_group, feat) - lo[:, None, None]) * rcp[:, None, None]
    q = jnp.clip(jnp.floor(xs + noise.reshape(g, row_group, feat)), 0, levels)
    q = q.astype(jnp.uint32).reshape(rows, feat)
    per_word = 32 // bits
    qw = q.reshape(rows, feat // per_word, per_word)
    shifts = (jnp.arange(per_word, dtype=jnp.uint32) * bits)[None, None, :]
    packed = jnp.sum(qw << shifts, axis=-1, dtype=jnp.uint32).astype(jnp.int32)
    return packed, lo, jnp.where(scale > 0, scale, 0.0)


def dequant_unpack_ref(
    packed: jax.Array,   # [R, F*bits/32] int32
    zero: jax.Array,     # [R/row_group]
    scale: jax.Array,    # [R/row_group]
    bits: int,
    feat: int,
    row_group: int = 4,
) -> jax.Array:
    rows = packed.shape[0]
    per_word = 32 // bits
    pw = packed.astype(jnp.uint32)[:, :, None]
    shifts = (jnp.arange(per_word, dtype=jnp.uint32) * bits)[None, None, :]
    mask = jnp.uint32(levels) if (levels := (1 << bits) - 1) else jnp.uint32(0)
    q = ((pw >> shifts) & mask).reshape(rows, feat).astype(jnp.float32)
    g = rows // row_group
    x = q.reshape(g, row_group, feat) * scale[:, None, None] + zero[:, None, None]
    return x.reshape(rows, feat)
