"""Seconds of the host build: graph, partition, worker data, device lift
(host clock around ``build_session``)."""


def read(ctx):
    return ctx["timing"]["host_build_s"]
