"""Row copies the aggregation kernel starts per real edge: the program's
counters ``agg.copies`` (per grid step and slot column, the row after the
last nonzero weight, rounded up to the kernel's start group) over
``agg.edges`` (non-zero weights), both summed over every epoch's bucketed
aggregations, forward and backward. 1.0 would mean no copy is started for
a slot of weight 0. A program without the copy counter reads nothing."""

from bench import program


def read(ctx):
    copies, edges = program.counter("agg.copies"), program.counter("agg.edges")
    if not copies or not edges:
        return None
    return copies / edges
