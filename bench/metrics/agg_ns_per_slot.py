"""Device nanoseconds of the ``seg_aggregate`` kernel per slot it works
through: the kernel's device time per traced epoch over the program's
``agg.slots`` counter per epoch (its total over the ``epoch`` span's
count). A kernel bound by issuing one row copy per slot reads the same
whatever the width."""

from bench import program


def read(ctx):
    t = ctx["trace"]
    epoch, slots = program.span("epoch"), program.counter("agg.slots")
    if not t or "seg_aggregate" not in t["kernel_s"] or not epoch or not slots:
        return None
    kernel_s = t["kernel_s"]["seg_aggregate"] / ctx["trace_epochs"]
    return 1e9 * kernel_s / (slots / epoch["n"])
