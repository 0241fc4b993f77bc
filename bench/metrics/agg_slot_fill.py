"""Share of the aggregation kernel's slots, in %, that hold a real edge:
the program's counters ``agg.edges`` (non-zero weights) over ``agg.slots``
(slots the kernel launches, padding included), both summed over every
epoch's bucketed aggregations, forward and backward. A layout change
moves it."""

from bench import program


def read(ctx):
    edges, slots = program.counter("agg.edges"), program.counter("agg.slots")
    if not slots:
        return None
    return 100.0 * edges / slots
