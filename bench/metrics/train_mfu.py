"""Model FLOPs of an epoch over ``epoch_s`` over the cell's chips' peak
bfloat16 FLOP/s, in %. The FLOPs are counted by the cell's architecture
module (``bench/models/<model>.py``) from the graph's nodes and edges and
the published widths."""


def read(ctx):
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * ctx["work"]["flops"] / ctx["timing"]["epoch_s"] / peak
