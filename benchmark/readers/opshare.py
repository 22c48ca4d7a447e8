"""Share of the traced launch's device busy time spent in the operations
whose base name contains one of the spec's ``ops`` patterns: summed device
seconds of those operations (mean over the device planes, as
``harness/xtrace.py:reduce`` gives every operation) over ``busy_s``.
Returns None without a trace; 0 where the trace has no such operation."""
from harness import xtrace


def read(spec, ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    took = xtrace.kernel_seconds(trace, spec["ops"]) or 0.0
    return 100.0 * took / trace["busy_s"]
