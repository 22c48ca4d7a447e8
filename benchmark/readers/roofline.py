"""Least time the chip could take for a part of the traced launch's work,
as a share of the time it took. ``work`` names the part of the work model;
``kernels`` the device operations whose time it is held against (name
patterns), or, left out, the whole traced launch from mark to mark."""
from harness import workmodel, xtrace


def read(spec, ctx):
    trace, work = ctx.get("trace"), ctx.get("work")
    if not trace or not work:
        return None
    if "kernels" in spec:
        took = xtrace.kernel_seconds(trace, spec["kernels"])
    else:
        took = trace["window_s"]
    if not took:
        return None
    return 100.0 * workmodel.least_seconds(work[spec["work"]],
                                           ctx["peak"]) / took
