"""Blocked-ELL neighbour-aggregation Pallas kernel (paper §4, TPU-adapted).

The paper optimizes ``index_add``/``SpMM`` on CPUs by (1) clustering sources
by sorted destination, (2) loop reordering for register reuse of the
destination row, (3) shape-adaptive vector-register inner kernels, and
(4) 2-D dynamic parallelism. The TPU translation:

* *clustering/sorting* → the host builds a **blocked-ELL** layout: CSR sorted
  by destination is padded to ``K`` neighbour slots per row, so each grid
  step owns a contiguous block of destination rows.
* *register reuse of dst* → the ``(BR, F)`` destination tile is the output
  block, resident in VMEM across every ``K``-chunk grid step that adds into
  it (float32 accumulation).
* *shape-adaptive inner kernel* → the slot chunk ``KC`` is the smallest
  power of two covering ``K`` (at most 16) and ``BR = 1024 / KC``, so every
  grid step gathers 1024 source rows whatever the degree class.
* *2-D parallelism* → grid = (row tiles × K chunks); nnz balance is done at
  partition time (FLOP-based load balancing moved to preprocessing).

Gather: the source features stay in HBM (``memory_space=pl.ANY``). Each
grid step receives its 1024 source-row ids as an SMEM block and issues one
row DMA per slot into a VMEM tile, then accumulates the weighted rows in
slot order. Slots of weight 0 that trail their column are not fetched: for
each of the step's KC slot columns the kernel reduces the weight block to
the row after the column's last nonzero weight and issues copies only up
to there. The layout builder orders each degree bucket's rows by degree,
highest first, so in every row tile each slot's real rows come first and
all the ladder's padding trails. The time of a step goes to issuing copies
and waiting on them on the scalar core, not to moving the rows, so the
waits are taken eight rows at a time. A zero-weight slot before the tail
is still fetched; every slot adds its row through a select on the weight,
so a slot that was not fetched (its VMEM is stale) adds an exact zero, and
the sum is the one a fetch-everything kernel gives for finite inputs. The
one difference is at non-finite inputs: a NaN or Inf in a source row
reached only through zero weights no longer turns the output NaN
(``0 * inf``).
VMEM use is bounded by the tile (``<= 3 x 1024 x F x 4`` bytes), not by the
number of source rows ``N``, so a real partition's feature slab never has
to fit on chip. Rows are DMA'd from a ``[N, 1, F]`` view of the features:
XLA lays that out with a ``(1, 128)`` tile, which makes a single row an
aligned DMA (a row of the ``[N, F]`` view is an eighth of an ``(8, 128)``
tile, which Mosaic refuses to slice).

Widths that are not multiples of 128 (Table 2's 100 and 602) and row counts
that are not multiples of the tile are zero-padded inside the wrapper;
padded slots carry weight 0 and are never fetched. Under ``vmap`` the batch
of workers folds into one call over the concatenated graphs (source ids
offset per worker), so the kernel itself never sees a batch axis.

Degree-bucketed layout (the production hot path)
------------------------------------------------

A single-K ELL pads every row to the *max* degree, which on power-law
graphs inflates memory and FLOPs by orders of magnitude. The production
layout (``graph.structure.bucketed_ell_from_csr``) instead splits rows into
degree classes on a growth-2 ladder K in {1, 2, 4, 8, ...}: a row of degree
d pads to the smallest K >= d, wasting < d slots, so **total padded slots
< 2 x nnz on any graph**. :func:`bucketed_aggregate` runs one
``seg_aggregate`` per bucket — each a dense, perfectly regular
gather/accumulate — and scatters the R (not nnz) bucket outputs into the
destination rows.

Backward pass: aggregation is linear, ``out = A @ x``, so the VJP is
``A^T @ g`` — *another* aggregation, over the reversed graph. The custom
VJP therefore takes a second bucketed layout built from the transposed
CSR (``graph.structure.transpose_csr``) at partition time and runs the
same bucketed kernel over it, instead of letting XLA transpose the
forward gather into the scatter-add access pattern the paper's operator
exists to avoid. The cotangent of the layout arrays is structurally zero
(edge weights are preprocessing constants).

Attention-weighted form (GAT)
-----------------------------

With ``[R, K, H]`` weights the row's F columns are H equal heads, each
padded by the wrapper to whole lanes, and head h of a slot's row is
weighted by its own ``w[r, k, h]``: one row copy per slot serves every
head (``bucketed_weighted_sum``). Its gradient with respect to the
weights is the per-slot, per-head dot product of the destination row's
cotangent with the slot's source row, a sampled dense-dense product
(SDDMM): ``seg_sddmm``, a second ``pallas_call`` with the same fetch loop
and launch shape, named apart so that a trace tells the two kernels apart
(``bucketed_sddmm``). The attention op that drives both, with its own
custom VJP, is ``core.layers.gat_attention``.

Realization policy (``use_kernel``): ``"auto"`` runs the compiled kernel on
a TPU and the XLA reference (``kernels.ref.seg_aggregate_ref``) elsewhere;
``True`` forces the kernel (interpreted off-TPU, for tests); ``False``
forces the reference. Nothing falls back silently: on a TPU the reference
runs only when the caller passes ``False``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref


# Source rows gathered per grid step. The step's row ids arrive as one SMEM
# block of a 1-D int32 array, which XLA tiles in units of 1024.
SLOTS_PER_STEP = 1024
LANES = 128
MAX_BLOCK_K = 16
# Row copies one semaphore wait stands for (a row tile has at least 8 rows).
WAIT_ROWS = 8


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _block_k(k: int) -> int:
    """Slot chunk per grid step: the smallest power of two >= K, capped."""
    return min(MAX_BLOCK_K, 1 << max(k - 1, 0).bit_length())


def _live_rows(w, heads: int = 1):
    """Per slot column, the row after its last one of nonzero weight in
    any head: ``[1, KC]``. The rows from there on are not fetched."""
    row1 = jax.lax.broadcasted_iota(jnp.int32, w.shape[-2:], 0) + 1
    nonzero = (w != 0 if heads == 1
               else jnp.max(jnp.where(w != 0, 1, 0), axis=0) > 0)
    return jnp.max(jnp.where(nonzero, row1, 0), axis=0, keepdims=True)


def _fetch_rows(idx_ref, x_hbm, buf, sem, live, *, block_rows: int,
                block_k: int):
    """Copy the step's source rows into ``buf`` (slot k's rows into
    ``buf[k]``), each slot column up to its ``live`` row, and wait for
    them all."""
    def fetch_column(k):
        """Start slot k's row copies up to its column's last nonzero weight."""
        col = k * block_rows
        n = jnp.max(live[:, k:k + 1])

        def start(r, carry):
            pltpu.make_async_copy(x_hbm.at[pl.ds(idx_ref[col + r], 1)],
                                  buf.at[k, pl.ds(r, 1)], sem.at[0]).start()
            return carry

        jax.lax.fori_loop(0, n, start, 0)
        return n

    def wait(rows):
        # A wait takes the bytes of its descriptor off the semaphore: one
        # wait on `rows` rows of the tile stands for that many row copies.
        def body(i, carry):
            rows_ref = buf.at[0, pl.ds(0, rows)]
            pltpu.make_async_copy(rows_ref, rows_ref, sem.at[0]).wait()
            return carry
        return body

    started = sum(fetch_column(k) for k in range(block_k))
    jax.lax.fori_loop(0, started // WAIT_ROWS, wait(WAIT_ROWS), 0)
    jax.lax.fori_loop(0, started % WAIT_ROWS, wait(1), 0)


def _seg_aggregate_kernel(idx_ref, w_ref, x_hbm, out_ref, buf, sem, *,
                          block_rows: int, block_k: int, heads: int):
    """One (BR, F) destination tile, one chunk of KC neighbour slots.

    ``idx_ref`` holds the chunk's BR*KC source ids slot-major (slot k of
    row r at ``k * BR + r``); ``out_ref`` stays resident across chunks and
    accumulates in float32, slot by slot. With one head ``w_ref`` is the
    ``[BR, KC]`` slot weights; with H heads it is ``[H, BR, KC]`` and head
    h weighs the h-th of the row's H equal, lane-aligned column groups.
    """
    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...]
    _fetch_rows(idx_ref, x_hbm, buf, sem, _live_rows(w, heads),
                block_rows=block_rows, block_k=block_k)
    if heads == 1:
        acc = out_ref[...]
        for k in range(block_k):
            # A slot that was not fetched holds stale VMEM: select, not 0 * row.
            wk = w[:, k:k + 1]
            acc = acc + jnp.where(wk != 0, wk * buf[k].reshape(block_rows, -1), 0.0)
        out_ref[...] = acc
        return
    fh = out_ref.shape[-1] // heads
    for h in range(heads):
        cols = slice(h * fh, (h + 1) * fh)
        acc = out_ref[:, cols]
        for k in range(block_k):
            wk = w[h, :, k:k + 1]
            row = buf[k, :, :, cols].reshape(block_rows, fh)
            acc = acc + jnp.where(wk != 0, wk * row, 0.0)
        out_ref[:, cols] = acc


def _seg_sddmm_kernel(idx_ref, w_ref, x_hbm, g_ref, out_ref, buf, sem, *,
                      block_rows: int, block_k: int, heads: int):
    """Per-slot, per-head dot products of one (BR, KC) block of slots:
    ``out[h, r, k] = <g[r, head h], x[idx[r, k], head h]>``, 0 where the
    layout's weight ``w_ref`` is 0 (padding). The source rows are fetched
    as the aggregation fetches them; ``g_ref`` is the block's (BR, F)
    destination rows."""
    w = w_ref[...]
    _fetch_rows(idx_ref, x_hbm, buf, sem, _live_rows(w),
                block_rows=block_rows, block_k=block_k)
    fh = g_ref.shape[-1] // heads
    col = jax.lax.broadcasted_iota(jnp.int32, (block_rows, block_k), 1)
    for h in range(heads):
        cols = slice(h * fh, (h + 1) * fh)
        gh = g_ref[:, cols]
        acc = jnp.zeros((block_rows, block_k), jnp.float32)
        for k in range(block_k):
            row = buf[k, :, :, cols].reshape(block_rows, fh)
            acc = jnp.where(col == k, jnp.sum(gh * row, axis=1, keepdims=True), acc)
        # A slot that was not fetched holds stale VMEM: select, not 0 * dot.
        out_ref[h] = jnp.where(w != 0, acc, 0.0)


def _vmem_limit(block_rows: int, fp: int, row_words: Optional[int] = None) -> int:
    """Scoped-VMEM request: the gathered rows plus the double-buffered
    blocks that move per row of the tile (``row_words`` float32 words: by
    default the output row and a lane-padded weight row), with headroom for
    Mosaic's own temporaries."""
    if row_words is None:
        row_words = fp + LANES
    need = 4 * (SLOTS_PER_STEP * fp + 2 * block_rows * row_words)
    return max(32 * 2**20, 2 * need)


def _launch_shape(r: int, k: int, block_k: Optional[int]):
    """(KC, BR, padded rows, padded K) of one launch over an [r, k] layout:
    rows round up to the row tile BR = 1024 / KC, K to the chunk KC."""
    kc = _block_k(k) if block_k is None else int(block_k)
    if kc < 1 or SLOTS_PER_STEP % kc or SLOTS_PER_STEP // kc < 8:
        raise ValueError(f"block_k={kc} must be a power of two <= "
                         f"{SLOTS_PER_STEP // 8}")
    br = SLOTS_PER_STEP // kc
    return kc, br, _round_up(r, br), _round_up(k, kc)


def kernel_slots(r: int, k: int) -> int:
    """Neighbour slots one kernel launch over an [r, k] layout works
    through, padding included (0 when the wrapper launches nothing)."""
    if r == 0 or k == 0:
        return 0
    _, _, rp, kp = _launch_shape(r, k, None)
    return rp * kp


def _pad_heads(x, heads: int):
    """``x`` in float32 with each head's columns zero-padded to whole lanes:
    ``[N, H * dh]`` becomes ``[N, H * dhp]``, dhp the multiple of 128 at
    or above dh, so a head is a lane-aligned column group of every row.
    Returns the padded array and its width."""
    n, f = x.shape
    if f % heads:
        raise ValueError(f"width {f} is not a multiple of {heads} heads")
    dhp = _round_up(f // heads, LANES)
    fp = heads * dhp
    if heads == 1:
        return jnp.pad(x.astype(jnp.float32), ((0, 0), (0, fp - f))), fp
    xh = x.astype(jnp.float32).reshape(n, heads, f // heads)
    return jnp.pad(xh, ((0, 0), (0, 0), (0, dhp - f // heads))).reshape(n, fp), fp


def _slot_major_ids(ell_idx, nt: int, br: int, nk: int, kc: int):
    """The padded ``[rp, kp]`` ids as one 1-D array in which step (i, c)
    reads block ``i * nk + c``, slot-major within the block."""
    r, k = ell_idx.shape
    idx = jnp.pad(ell_idx.astype(jnp.int32), ((0, nt * br - r), (0, nk * kc - k)))
    return idx.reshape(nt, br, nk, kc).transpose(0, 2, 3, 1).reshape(-1)


def _seg_aggregate_pallas(x, ell_idx, ell_w, *, block_k: Optional[int],
                          interpret: bool):
    n, f = x.shape
    r, k = ell_idx.shape
    heads = ell_w.shape[2] if ell_w.ndim == 3 else 1
    if r == 0 or k == 0 or n == 0:
        return jnp.zeros((r, f), x.dtype)
    kc, br, rp, kp = _launch_shape(r, k, block_k)
    nt, nk = rp // br, kp // kc
    xp, fp = _pad_heads(x, heads)
    idx = _slot_major_ids(ell_idx, nt, br, nk, kc)
    pad = ((0, rp - r), (0, kp - k)) + ((0, 0),) * (ell_w.ndim - 2)
    w = jnp.pad(ell_w.astype(jnp.float32), pad)
    if heads == 1:
        w = w.reshape(rp, nk, kc).transpose(1, 0, 2)  # [nk, rp, kc]
        w_spec = pl.BlockSpec((None, br, kc), lambda i, c: (c, i, 0))
    else:
        w = w.reshape(rp, nk, kc, heads).transpose(1, 3, 0, 2)  # [nk, H, rp, kc]
        w_spec = pl.BlockSpec((None, heads, br, kc), lambda i, c: (c, 0, i, 0))
    out = pl.pallas_call(
        functools.partial(_seg_aggregate_kernel, block_rows=br, block_k=kc,
                          heads=heads),
        grid=(nt, nk),
        in_specs=[
            pl.BlockSpec((SLOTS_PER_STEP,), lambda i, c: (i * nk + c,),
                         memory_space=pltpu.SMEM),
            w_spec,
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((br, fp), lambda i, c: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, fp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((kc, br, 1, fp), jnp.float32),
                        pltpu.SemaphoreType.DMA((1,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(br, fp, fp + heads * LANES)),
        interpret=interpret,
        name="seg_aggregate",
    )(idx, w, xp.reshape(n, 1, fp))
    if heads == 1:
        return out[:r, :f].astype(x.dtype)
    return out[:r].reshape(r, heads, -1)[:, :, :f // heads].reshape(r, f).astype(x.dtype)


@functools.lru_cache(maxsize=None)
def _batchable(block_k: Optional[int], interpret: bool):
    """``_seg_aggregate_pallas`` with a vmap rule that folds the batch into
    one call: B graphs over B feature slabs are one graph over the
    concatenated slab, with each worker's source ids offset by its slab."""
    fn = jax.custom_batching.custom_vmap(functools.partial(
        _seg_aggregate_pallas, block_k=block_k, interpret=interpret))

    @fn.def_vmap
    def _rule(axis_size, in_batched, x, idx, w):
        x_b, idx_b, w_b = in_batched
        bcast = lambda a, b: a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
        idx, w = bcast(idx, idx_b), bcast(w, w_b)
        r, k = idx.shape[1:]
        if x_b:
            n = x.shape[1]
            idx = idx + (jnp.arange(axis_size, dtype=idx.dtype) * n)[:, None, None]
            x = x.reshape(axis_size * n, x.shape[2])
        out = fn(x, idx.reshape(axis_size * r, k), w.reshape(axis_size * r, *w.shape[2:]))
        return out.reshape(axis_size, r, out.shape[-1]), True

    return fn


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def seg_aggregate(
    x: jax.Array,        # [N, F]
    ell_idx: jax.Array,  # [R, K] int32
    ell_w: jax.Array,    # [R, K] f32 (0 padding), or [R, K, H] per head
    *,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """out[r] = sum_k ell_w[r,k] * x[ell_idx[r,k]] via pallas_call.

    With ``[R, K, H]`` weights F holds H equal column groups (heads) and
    ``out[r, head h] = sum_k ell_w[r,k,h] * x[ell_idx[r,k], head h]``: one
    row copy per slot serves every head. The wrapper pads each head to
    whole lanes (``_pad_heads``), so a head's columns are a lane-aligned
    slice of the tile.

    ``block_k`` overrides the slot chunk per grid step (a power of two up
    to 128; default from K). ``interpret=None`` compiles on a TPU and
    interprets elsewhere; ``True`` interprets only when a caller asks.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _batchable(block_k, bool(interpret))(x, ell_idx, ell_w)


def _seg_sddmm_pallas(g, x, ell_idx, ell_w, *, heads: int, interpret: bool):
    n, f = x.shape
    r, k = ell_idx.shape
    if r == 0 or k == 0 or n == 0:
        return jnp.zeros((r, k, heads), jnp.float32)
    kc, br, rp, kp = _launch_shape(r, k, None)
    nt, nk = rp // br, kp // kc
    xp, fp = _pad_heads(x, heads)
    gp = jnp.pad(_pad_heads(g, heads)[0], ((0, rp - r), (0, 0)))
    w = jnp.pad(ell_w.astype(jnp.float32), ((0, rp - r), (0, kp - k)))
    w = w.reshape(rp, nk, kc).transpose(1, 0, 2)  # [nk, rp, kc]
    out = pl.pallas_call(
        functools.partial(_seg_sddmm_kernel, block_rows=br, block_k=kc,
                          heads=heads),
        grid=(nt, nk),
        in_specs=[
            pl.BlockSpec((SLOTS_PER_STEP,), lambda i, c: (i * nk + c,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, br, kc), lambda i, c: (c, i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((br, fp), lambda i, c: (i, 0)),
        ],
        out_specs=pl.BlockSpec((None, heads, br, kc), lambda i, c: (c, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((nk, heads, rp, kc), jnp.float32),
        scratch_shapes=[pltpu.VMEM((kc, br, 1, fp), jnp.float32),
                        pltpu.SemaphoreType.DMA((1,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(br, fp, 2 * fp + (heads + 1) * LANES)),
        interpret=interpret,
        name="seg_sddmm",
    )(_slot_major_ids(ell_idx, nt, br, nk, kc), w, xp.reshape(n, 1, fp), gp)
    return out.transpose(2, 0, 3, 1).reshape(rp, kp, heads)[:r, :k]


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def seg_sddmm(
    g: jax.Array,        # [R, F] destination rows
    x: jax.Array,        # [N, F] source rows
    ell_idx: jax.Array,  # [R, K] int32
    ell_w: jax.Array,    # [R, K] f32 (0 padding)
    *,
    heads: int,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """out[r, k, h] = <g[r, head h], x[ell_idx[r, k], head h]> via
    pallas_call, 0 on padding slots: the sampled dense-dense product
    (SDDMM) that gives an attention-weighted aggregation's gradient with
    respect to its slot weights. A head is the h-th of F's ``heads`` equal
    column groups. Same fetch loop and launch shape as
    :func:`seg_aggregate`; ``interpret`` as there."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _seg_sddmm_pallas(g, x, ell_idx, ell_w, heads=heads,
                             interpret=bool(interpret))


# --------------------------------------------------------------------------
# Degree-bucketed blocked-ELL aggregation with a fused custom VJP
# --------------------------------------------------------------------------


class DeviceEllBucket(NamedTuple):
    """One degree bucket on device (leading worker axis in stacked form)."""

    rows: jax.Array  # [.., Rb] int32 destination rows (0 on padding)
    idx: jax.Array   # [.., Rb, K] int32 source rows (0 on padding)
    w: jax.Array     # [.., Rb, K] f32 edge weights (0 on padding)


class DeviceBucketedEll(NamedTuple):
    """Device form of ``graph.structure.BucketedEll`` (a pytree, so it
    stacks/maps over the worker axis like any other WorkerData leaf)."""

    buckets: Tuple[DeviceEllBucket, ...]


def device_bucketed(stacked, squeeze: bool = False) -> DeviceBucketedEll:
    """Lift ``graph.structure.stack_bucketed_ells`` output to device arrays.

    ``squeeze=True`` drops the leading worker axis (single-graph use).
    """
    sl = (lambda a: a[0]) if squeeze else (lambda a: a)
    return DeviceBucketedEll(tuple(
        DeviceEllBucket(
            rows=jnp.asarray(sl(rows), jnp.int32),
            idx=jnp.asarray(sl(idx), jnp.int32),
            w=jnp.asarray(sl(w)),
        )
        for _, rows, idx, w in stacked
    ))


def bucketed_slots(ell: DeviceBucketedEll, workers: int, fold: bool) -> int:
    """Kernel slots of one aggregation over a stacked layout of
    ``workers`` worker graphs: under ``vmap`` (``fold``) each bucket is one
    launch over the workers' rows end to end, otherwise one launch per
    worker."""
    total = 0
    for b in ell.buckets:
        rows, k = b.idx.shape[-2:]
        total += (kernel_slots(workers * rows, k) if fold
                  else workers * kernel_slots(rows, k))
    return total


def _use_kernel(policy) -> bool:
    """Resolve the kernel policy: True/False force, "auto" = the compiled
    kernel on a TPU, the XLA reference elsewhere (the interpreted kernel is
    correct but far too slow for a CPU hot path)."""
    if policy == "auto":
        return jax.default_backend() == "tpu"
    return bool(policy)


def _bucket_matvec(x: jax.Array, b: DeviceEllBucket, kernel: bool) -> jax.Array:
    if kernel:
        return seg_aggregate(x, b.idx, b.w)
    return ref.seg_aggregate_ref(x, b.idx, b.w)


def _bucketed_forward(x: jax.Array, ell: DeviceBucketedEll, out_rows: int,
                      kernel: bool, weights=None) -> jax.Array:
    """out[rows_b] += seg_aggregate(x, idx_b, w_b) for every degree bucket,
    with ``weights[b]`` (``[Rb, K]`` or ``[Rb, K, H]``) in place of the
    layout's own ``w_b`` where given.

    Padding bucket rows carry all-zero weights and scatter a zero into row
    0, so the R-row (not nnz-row) scatter is the only irregular access.
    """
    out = jnp.zeros((out_rows, x.shape[-1]), x.dtype)
    for i, b in enumerate(ell.buckets):
        if weights is not None:
            b = b._replace(w=weights[i])
        with jax.named_scope(f"k{b.idx.shape[-1]}"):
            out = out.at[b.rows].add(_bucket_matvec(x, b, kernel))
    return out


def bucketed_weighted_sum(x: jax.Array, ell: DeviceBucketedEll, weights,
                          out_rows: int, *, use_kernel="auto") -> jax.Array:
    """The bucketed aggregation with slot weights the caller computes:
    ``weights[b]`` is ``[Rb, K, H]`` for bucket b, and head h of row r sums
    ``weights[b][r, k, h]`` times head h of source row ``idx_b[r, k]``.
    Linear in ``x`` and in the weights; no VJP of its own (the attention
    op that calls it has one)."""
    return _bucketed_forward(x, ell, out_rows, _use_kernel(use_kernel), weights)


def bucketed_sddmm(g: jax.Array, x: jax.Array, ell: DeviceBucketedEll,
                   heads: int, *, use_kernel="auto"):
    """Per degree bucket, ``[Rb, K, H]`` per-head dot products of each
    row's ``g`` (taken at the bucket's destination rows) with the source
    row of each slot; 0 on padding slots."""
    kernel = _use_kernel(use_kernel)
    out = []
    for b in ell.buckets:
        with jax.named_scope(f"k{b.idx.shape[-1]}"):
            gb = g[b.rows]
            out.append(seg_sddmm(gb, x, b.idx, b.w, heads=heads) if kernel
                       else ref.seg_sddmm_ref(gb, x, b.idx, b.w, heads))
    return out


def zero_cotangents(tree):
    """Symbolic-zero cotangents for a layout pytree (float0 for ints)."""
    return jax.tree_util.tree_map(
        lambda a: np.zeros(np.shape(a), jax.dtypes.float0)
        if jnp.issubdtype(jnp.result_type(a), jnp.integer)
        else jnp.zeros_like(a),
        tree)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _bucketed_aggregate(x, ell, ell_t, out_rows, in_rows, kernel):
    return _bucketed_forward(x, ell, out_rows, kernel)


def _bucketed_aggregate_fwd(x, ell, ell_t, out_rows, in_rows, kernel):
    # Linear in x: the layouts are the only residuals.
    return _bucketed_aggregate(x, ell, ell_t, out_rows, in_rows, kernel), (
        ell, ell_t)


def _bucketed_aggregate_bwd(out_rows, in_rows, kernel, res, g):
    ell, ell_t = res
    # The transpose aggregation IS an aggregation — same bucketed access
    # pattern, reverse-graph layout.
    with jax.named_scope("agg_bwd"):
        dx = _bucketed_forward(g, ell_t, in_rows, kernel)
    return dx, zero_cotangents(ell), zero_cotangents(ell_t)


_bucketed_aggregate.defvjp(_bucketed_aggregate_fwd, _bucketed_aggregate_bwd)


def bucketed_aggregate(
    x: jax.Array,               # [N, F] source features
    ell: DeviceBucketedEll,     # forward layout (rows scatter into out)
    ell_t: DeviceBucketedEll,   # reverse-graph layout (drives the VJP)
    out_rows: Optional[int] = None,  # output rows (default: square, N)
    *,
    use_kernel="auto",          # True | False | "auto" (kernel iff on TPU)
) -> jax.Array:
    """Degree-bucketed blocked-ELL aggregation with a fused custom VJP."""
    rows = int(x.shape[0] if out_rows is None else out_rows)
    return _bucketed_aggregate(x, ell, ell_t, rows, int(x.shape[0]),
                               _use_kernel(use_kernel))
