"""Work counts that architectures share, and the roofline share.

Counted from the graph's real edges and rows and the published layer
widths, never from padded shapes: a later change that removes padding
then reads as a gain, and this yardstick stays where it is. Each
architecture's model FLOPs, and which kernel's work is which, are in its
``bench/models/<model>.py``.
"""

from __future__ import annotations

from typing import Dict, Sequence


def aggregation_work(calls: Sequence[Dict], dims: Sequence[int]) -> Dict[str, float]:
    """FLOPs and HBM bytes of the aggregation kernel over one epoch.

    ``calls`` lists the kernel's edge sets, each ``{"edges", "rows",
    "per_epoch"}``: the real (weight-carrying) edges, the destination rows
    that receive at least one of them, and how many times per layer and
    epoch the set is aggregated (forward and backward count apart, and a
    set read stale on some epochs counts its share). Each aggregation of
    ``e`` edges into ``r`` rows at width ``f`` multiplies and adds once
    per edge and element (``2 e f`` FLOPs) and moves the gathered rows
    (``4 e f`` bytes), the output rows (``4 r f``) and one int32 index and
    one float32 weight per edge (``8 e``).
    """
    flops = bytes_ = 0.0
    for f in dims[:-1]:
        for c in calls:
            n = c["per_epoch"]
            flops += n * 2.0 * c["edges"] * f
            bytes_ += n * (4.0 * c["edges"] * f + 4.0 * c["rows"] * f
                           + 8.0 * c["edges"])
    return {"flops": flops, "bytes": bytes_}


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peak_flops: float, peak_bytes_per_s: float):
    """(share in %, bound): the least time the chip could take, over the
    measured time."""
    t_flops, t_bytes = flops / peak_flops, bytes_ / peak_bytes_per_s
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
