"""Seconds of the host build's partition stage: the program's
``build/partition`` span, which holds the CSR sorts (``csr``) and the
bucketed layouts (``ell``)."""

from bench import program


def read(ctx):
    s = program.span("build/partition")
    return None if s is None else s["s"]
