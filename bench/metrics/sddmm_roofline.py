"""The SDDMM kernel's share of its roofline, in %: the least time one chip
could take for its share of an epoch's ``seg_sddmm`` work (FLOPs over peak
FLOP/s or bytes over peak bytes/s, whichever is larger) over the kernel's
device time per epoch. The work is counted by the cell's architecture
module (``bench/models/<model>.py``) from real edges and published
widths."""

import sys

from bench import flops


def read(ctx):
    t = ctx["trace"]
    work = ctx["work"]["kernels"].get("seg_sddmm")
    if not t or "seg_sddmm" not in t["kernel_s"] or work is None:
        return None
    seconds = t["kernel_s"]["seg_sddmm"] / ctx["trace_epochs"]
    p = ctx["peaks"]
    share, bound = flops.roofline_share(
        work["flops"] / ctx["chips"], work["bytes"] / ctx["chips"], seconds,
        p["bf16_flops_per_s"], p["hbm_bytes_per_s"])
    print(f"sddmm_roofline: {bound} bound; {work['flops']:.6g} FLOPs, "
          f"{work['bytes']:.6g} bytes per epoch over {ctx['chips']} chip(s)",
          file=sys.stderr)
    return share
