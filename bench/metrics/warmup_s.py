"""Seconds of the warm-up epochs, which compile the step or load it from
the persistent cache (host clock)."""


def read(ctx):
    return ctx["timing"]["warmup_s"]
