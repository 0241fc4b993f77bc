"""Model FLOPs of an epoch over ``epoch_s`` over the cell's chips' peak
bfloat16 FLOP/s, in %. The FLOPs are counted by ``bench.flops`` from the
graph's nodes and edges and the published widths."""

from bench import flops


def read(ctx):
    c = ctx["counts"]
    work = flops.model_flops(c["nodes"], c["edges"], c["dims"])
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * work / ctx["timing"]["epoch_s"] / peak
