"""Device nanoseconds of the ``seg_sddmm`` kernel per slot it works
through: the kernel's device time per traced epoch over the program's
``sddmm.slots`` counter per epoch (its total over the ``epoch`` span's
count)."""

from bench import program


def read(ctx):
    t = ctx["trace"]
    epoch, slots = program.span("epoch"), program.counter("sddmm.slots")
    if not t or "seg_sddmm" not in t["kernel_s"] or not epoch or not slots:
        return None
    kernel_s = t["kernel_s"]["seg_sddmm"] / ctx["trace_epochs"]
    return 1e9 * kernel_s / (slots / epoch["n"])
