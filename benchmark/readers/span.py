"""A per-layer metric that is one of the benchmark's own clock readings."""


def read(spec, ctx):
    return ctx["spans"].get(spec["key"])
