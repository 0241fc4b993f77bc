"""Public jit'd wrappers around the Pallas kernels.

The kernels compile with Mosaic on a TPU and run in the Pallas interpreter
elsewhere (their ``interpret=None`` default); ``use_kernel=False`` is the
caller's explicit request for the jnp reference.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.quant_pack import dequant_unpack, quant_pack
from repro.kernels.seg_aggregate import (  # noqa: F401  (re-exported API)
    DeviceBucketedEll,
    DeviceEllBucket,
    bucketed_aggregate,
    device_bucketed,
    seg_aggregate,
)


def aggregate(x, ell_idx, ell_w, *, use_kernel: bool = True, **kw):
    """Neighbour aggregation: the Pallas kernel (any shape; the wrapper
    pads), or the jnp reference when the caller passes ``use_kernel=False``.
    """
    if use_kernel:
        return seg_aggregate(x, ell_idx, ell_w, **kw)
    return ref.seg_aggregate_ref(x, ell_idx, ell_w)


def padded_device_bucketed(ell, bucket_caps: Sequence[Tuple[int, int]]
                           ) -> DeviceBucketedEll:
    """Materialize a host ``BucketedEll`` at *fixed* per-bucket shapes.

    ``bucket_caps`` is ``[(k, row_capacity), ...]`` — the full degree
    ladder, every entry present even when the layout has no rows at that
    K, each padded (with rows=0, idx=0, w=0, the zero-scatter-into-row-0
    convention) to its capacity. Two layouts padded with the same caps
    therefore produce pytrees with identical structure AND array shapes,
    which is what lets a serving batch of any composition reuse one
    compiled program per shape class instead of retracing per batch.
    Padding only ever adds exact ``+0.0`` contributions, so it never
    perturbs the aggregation values.
    """
    by_k = {b.k: b for b in ell.buckets}
    unknown = sorted(set(by_k) - {k for k, _ in bucket_caps})
    if unknown:
        raise ValueError(
            f"padded_device_bucketed: layout has bucket K={unknown} absent "
            f"from bucket_caps {sorted(k for k, _ in bucket_caps)} — edges "
            "would be dropped")
    buckets = []
    for k, cap in bucket_caps:
        rows = np.zeros(cap, np.int32)
        idx = np.zeros((cap, k), np.int32)
        w = np.zeros((cap, k), np.float32)
        b = by_k.get(k)
        if b is not None:
            n = b.rows.shape[0]
            if n > cap:
                raise ValueError(
                    f"padded_device_bucketed: bucket K={k} holds {n} rows "
                    f"> capacity {cap} — pick a larger shape class")
            rows[:n] = b.rows
            idx[:n] = b.idx
            w[:n] = b.w
        buckets.append(DeviceEllBucket(rows=jnp.asarray(rows),
                                       idx=jnp.asarray(idx),
                                       w=jnp.asarray(w)))
    return DeviceBucketedEll(tuple(buckets))


def quantize_pack(x, noise, *, bits: int = 2, use_kernel: bool = True):
    per_word = 32 // bits
    rows, feat = x.shape
    aligned = (rows % 4 == 0) and (feat % per_word == 0)
    if use_kernel and aligned:
        return quant_pack(x, noise, bits=bits)
    return ref.quant_pack_ref(x, noise, bits)


def dequantize_unpack(packed, zero, scale, *, bits: int = 2, feat: int,
                      use_kernel: bool = True):
    rows = packed.shape[0]
    if use_kernel and rows % 4 == 0:
        return dequant_unpack(packed, zero, scale, bits=bits, feat=feat)
    return ref.dequant_unpack_ref(packed, zero, scale, bits, feat)
