"""Share of the traced window, in %, in which no operation ran on the
device (1 minus the union of operation intervals), averaged over chips."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    return 100.0 * t["idle_share"]
