"""Device milliseconds per epoch of the ``seg_aggregate`` kernel's
operations in the trace, averaged over the chips."""


def read(ctx):
    t = ctx["trace"]
    if not t or "seg_aggregate" not in t["kernel_s"]:
        return None
    return 1e3 * t["kernel_s"]["seg_aggregate"] / ctx["trace_epochs"]
