"""GNN layer definitions (functional, pure-jnp).

UPDATE stages for the message-passing family the paper targets (§3.2): GCN,
GraphSAGE, GIN, GAT. The AGGREGATE stage is supplied by the caller as
``agg_fn`` so the same layer code runs single-device (full-graph ELL) and
distributed (local + pre/post halo) — the paper's observation that these
models differ only in neighbour weighting while the core remains neighbour
aggregation.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from repro.kernels import seg_aggregate as sa


Params = Dict[str, jax.Array]

LEAKY_SLOPE = 0.2   # GAT's LeakyReLU on attention scores
NEG_INF = -1e30     # score of a padding slot before the softmax


def glorot(key, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[-2], shape[-1]
    lim = jnp.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def init_layer(key, model: str, d_in: int, d_out: int, heads: int = 4,
               concat: bool = True, norm: bool = True) -> Params:
    """One layer's parameters; LayerNorm's only with ``norm``. A GAT layer
    has ``heads`` heads that are concatenated to ``d_out`` (``concat``) or
    each ``d_out`` wide and averaged (the output layer)."""
    ks = jax.random.split(key, 8)
    p: Params = {"b": jnp.zeros((d_out,), jnp.float32)}
    if norm:
        p["ln_scale"] = jnp.ones((d_in,), jnp.float32)
        p["ln_bias"] = jnp.zeros((d_in,), jnp.float32)
    if model == "gcn":
        p["w"] = glorot(ks[0], (d_in, d_out))
    elif model == "sage":
        p["w_self"] = glorot(ks[0], (d_in, d_out))
        p["w_neigh"] = glorot(ks[1], (d_in, d_out))
    elif model == "gin":
        p["eps"] = jnp.zeros((), jnp.float32)
        p["w1"] = glorot(ks[0], (d_in, d_out))
        p["b1"] = jnp.zeros((d_out,), jnp.float32)
        p["w2"] = glorot(ks[1], (d_out, d_out))
    elif model == "gat":
        if concat and d_out % heads:
            raise ValueError(f"gat: d_out {d_out} % heads {heads}")
        dh = d_out // heads if concat else d_out
        p["w"] = glorot(ks[0], (d_in, heads * dh))
        p["a_src"] = glorot(ks[1], (heads, dh))
        p["a_dst"] = glorot(ks[2], (heads, dh))
    else:
        raise ValueError(f"unknown model {model!r}")
    return p


def layer_norm(x, scale, bias, eps=1e-5):
    mu = x.mean(axis=-1, keepdims=True)
    var = x.var(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def apply_update(model: str, p: Params, h: jax.Array, z: jax.Array) -> jax.Array:
    """UPDATE(h, z): combine node state with aggregated neighbours."""
    if model == "gcn":
        # Self-loop is part of the normalized adjacency; z already includes h.
        return z @ p["w"] + p["b"]
    if model == "sage":
        return h @ p["w_self"] + z @ p["w_neigh"] + p["b"]
    if model == "gin":
        s = (1.0 + p["eps"]) * h + z
        return jax.nn.relu(s @ p["w1"] + p["b1"]) @ p["w2"] + p["b"]
    raise ValueError(f"apply_update: {model!r} has no linear UPDATE")


def gat_scores(p: Params, wx: jax.Array):
    """The two halves of every edge's attention score, per head: ``e_src[j]
    = a_src^h . W^h x_j`` and ``e_dst[i] = a_dst^h . W^h x_i``, each
    ``[N, H]``, from ``wx = x @ W`` of H column groups."""
    heads, dh = p["a_src"].shape
    whh = wx.reshape(wx.shape[0], heads, dh)
    return (jnp.einsum("nhd,hd->nh", whh, p["a_src"]),
            jnp.einsum("nhd,hd->nh", whh, p["a_dst"]))


def slot_softmax(s: jax.Array, valid: jax.Array) -> jax.Array:
    """The attention weights of one block of rows: LeakyReLU(0.2) of the
    scores ``s`` ``[R, K, H]``, then the softmax over each row's K slots,
    with padding slots (``valid`` ``[R, K, 1]`` False) masked out and
    weighted 0. A row with no real slot weighs nothing."""
    e = jnp.where(valid, jax.nn.leaky_relu(s, LEAKY_SLOPE), NEG_INF)
    p = jnp.where(valid, jnp.exp(e - e.max(axis=1, keepdims=True)), 0.0)
    den = p.sum(axis=1, keepdims=True)
    return p / jnp.where(den > 0, den, 1.0)


def gat_aggregate(
    wx: jax.Array,         # [N, H*dh]
    e_src: jax.Array,      # [N, H]
    e_dst: jax.Array,      # [N, H]
    ell_idx: jax.Array,    # [R, K]
    ell_valid: jax.Array,  # [R, K] bool
) -> jax.Array:
    """GAT's attention-weighted sum over a dense (max-degree) ELL layout,
    differentiated by JAX: ``out[i, head h] = sum_j alpha_ij^h wx[j, head
    h]``. It gathers ``[R, K, H, dh]`` and is the small-graph oracle of
    :func:`gat_attention`."""
    r = ell_idx.shape[0]
    heads = e_src.shape[-1]
    alpha = slot_softmax(e_dst[:r, None, :] + e_src[ell_idx], ell_valid[..., None])
    src = wx.reshape(wx.shape[0], heads, -1)[ell_idx]       # [R, K, H, dh]
    return jnp.einsum("rkh,rkhd->rhd", alpha, src).reshape(r, wx.shape[-1])


def _bucket_alphas(e_src, e_dst, ell):
    """Per degree bucket, the scores ``[Rb, K, H]`` and the attention
    weights: every row's slots sit in one bucket, so each softmax runs over
    that bucket's K slots."""
    with jax.named_scope("attention"):
        out = []
        for b in ell.buckets:
            s = e_dst[b.rows][:, None, :] + e_src[b.idx]
            out.append((s, slot_softmax(s, (b.w != 0)[..., None])))
        return out


def _reverse_order(per_bucket, slot_t):
    """``[Rb, K, H]`` arrays of the forward layout's slots, gathered into
    each reverse bucket's slot order (``graph.structure.reverse_slot_index``;
    its padding index S picks the zero appended here)."""
    heads = per_bucket[0].shape[-1]
    flat = jnp.concatenate([a.reshape(-1, heads) for a in per_bucket]
                           + [jnp.zeros((1, heads), per_bucket[0].dtype)])
    return [flat[s] for s in slot_t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _gat_attention(wx, e_src, e_dst, ell, ell_t, slot_t, use_kernel):
    alphas = [a for _, a in _bucket_alphas(e_src, e_dst, ell)]
    with jax.named_scope("aggregate"):
        return sa.bucketed_weighted_sum(wx, ell, alphas, wx.shape[0],
                                        use_kernel=use_kernel)


def _gat_attention_fwd(wx, e_src, e_dst, ell, ell_t, slot_t, use_kernel):
    # The weights are recomputed in the backward pass from the [N, H]
    # scores: only [N, width] and [N, H] arrays are kept.
    out = _gat_attention(wx, e_src, e_dst, ell, ell_t, slot_t, use_kernel)
    return out, (wx, e_src, e_dst, ell, ell_t, slot_t)


def _gat_attention_bwd(use_kernel, res, g):
    wx, e_src, e_dst, ell, ell_t, slot_t = res
    heads = e_src.shape[-1]
    scored = _bucket_alphas(e_src, e_dst, ell)
    alphas = [a for _, a in scored]
    with jax.named_scope("aggregate"):
        # d wx = A_alpha^T g: the weighted kernel over the reverse layout.
        with jax.named_scope("agg_bwd"):
            dwx = sa.bucketed_weighted_sum(g, ell_t, _reverse_order(alphas, slot_t),
                                           wx.shape[0], use_kernel=use_kernel)
        # d alpha[r, k, h] = <g[row r, head h], wx[idx[r, k], head h]>.
        with jax.named_scope("sddmm"):
            dalphas = sa.bucketed_sddmm(g, wx, ell, heads, use_kernel=use_kernel)
    with jax.named_scope("attention"):
        de_dst = jnp.zeros_like(e_dst)
        ds = []
        for b, (s, a), da in zip(ell.buckets, scored, dalphas):
            de = a * (da - jnp.sum(a * da, axis=1, keepdims=True))
            ds.append(jnp.where(s >= 0, de, LEAKY_SLOPE * de))
            de_dst = de_dst.at[b.rows].add(ds[-1].sum(axis=1))
        de_src = jnp.zeros_like(e_src)
        for b, d in zip(ell_t.buckets, _reverse_order(ds, slot_t)):
            de_src = de_src.at[b.rows].add(d.sum(axis=1))
    return (dwx, de_src, de_dst, sa.zero_cotangents(ell),
            sa.zero_cotangents(ell_t), sa.zero_cotangents(slot_t))


_gat_attention.defvjp(_gat_attention_fwd, _gat_attention_bwd)


def gat_attention(
    wx: jax.Array,       # [N, H*dh]: x @ W
    e_src: jax.Array,    # [N, H]
    e_dst: jax.Array,    # [N, H]
    ell,                 # kernels.seg_aggregate.DeviceBucketedEll (forward)
    ell_t=None,          # its reverse-graph layout (the backward pass)
    slot_t=None,         # graph.structure.reverse_slot_index, on device
    *,
    use_kernel="auto",
) -> jax.Array:
    """GAT's attention-weighted aggregation over the degree-bucketed
    layout: ``out[i, head h] = sum_j alpha_ij^h wx[j, head h]`` over the
    slots of row i, with ``alpha`` the per-bucket :func:`slot_softmax` of
    ``e_dst[i] + e_src[j]``.

    The forward pass runs the per-head weighted kernel over the forward
    layout. The custom VJP runs the same kernel over the reverse layout
    with ``alpha`` moved into its slot order (``slot_t``), the SDDMM kernel
    for ``d alpha``, and the softmax and LeakyReLU backward on ``[slots,
    H]`` arrays; ``d e_src`` is summed through the reverse slot order and
    ``d e_dst`` over each row's slots. No ``[slots, width]`` array is made
    on the kernel path. Forward-only callers may leave ``ell_t`` and
    ``slot_t`` out. ``use_kernel`` as in ``bucketed_aggregate``.
    """
    return _gat_attention(wx, e_src, e_dst, ell, ell_t, slot_t, use_kernel)


def gat_layer(p: Params, h: jax.Array, attend, last: bool) -> jax.Array:
    """One GAT layer (Velickovic et al., ICLR 2018) around ``attend(wx,
    scores=(e_src, e_dst))``, the attention-weighted aggregation: hidden
    layers concatenate their heads, the output layer averages them; then
    the bias, the identity skip where the input and output widths agree,
    and ELU between layers."""
    heads = p["a_src"].shape[0]
    with jax.named_scope("update"):
        wx = h @ p["w"]
    with jax.named_scope("attention"):
        scores = gat_scores(p, wx)
    z = attend(wx, scores=scores)
    with jax.named_scope("update"):
        if last:
            z = z.reshape(z.shape[0], heads, -1).mean(axis=1)
        z = z + p["b"]
        if z.shape == h.shape:
            z = z + h
        return z if last else jax.nn.elu(z)
