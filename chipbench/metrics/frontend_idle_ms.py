"""Front end: device idle time per step that lies inside the program's
front-end spans (the chip waiting on the program's host code).  Mean over
the chips."""

from chipbench import scopes, xplane


def read(trace, ctx):
    s = scopes.for_trace(trace)
    if not s.instrumented:
        return None
    fronts = [(f.start_ns, f.end_ns) for f in scopes.front_spans(trace, s)]
    per_dev = {}
    for d in trace.ops:
        idle = xplane.idle_gaps(trace, d)
        per_dev[d] = (xplane.length(idle)
                      - xplane.length(xplane.subtract(idle, fronts)))
    return xplane.per_step_ms(trace, per_dev)
