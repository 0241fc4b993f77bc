"""Seconds the program spent tracing, lowering and compiling (a load from
the persistent cache included) inside its own spans: the ``<span>/compile``
rows the program's compile listener adds. The build and the epochs are
the program's spans; the window compiles nothing, so this is set-up
time. The benchmark's own programs (weights, reference) are outside
every span and left out."""

from bench import program


def read(ctx):
    t = program.tables()
    if t is None:
        return None
    return sum(row["s"] for path, row in t["spans"].items()
               if path.endswith("/compile"))
