"""Device milliseconds per epoch of the ``seg_sddmm`` kernel's operations
in the trace (the attention's backward pass), averaged over the chips."""


def read(ctx):
    t = ctx["trace"]
    if not t or "seg_sddmm" not in t["kernel_s"]:
        return None
    return 1e3 * t["kernel_s"]["seg_sddmm"] / ctx["trace_epochs"]
