"""Exchanges: the part of the exchange time per step during which no
other op runs on that chip; the busiest chip's."""

from chipbench import xplane


def read(trace, ctx):
    per_dev = {}
    for d in trace.ops:
        ex = xplane.exchange_intervals(trace, d)
        if ex:
            others = [(o.start_ns, o.end_ns) for o in trace.device_ops(d)
                      if o.category not in xplane.EXCHANGE]
            per_dev[d] = xplane.length(xplane.subtract(ex, others))
    if not per_dev:
        return None
    return xplane.per_step_ms(trace, per_dev, how="max")
