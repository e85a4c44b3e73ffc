"""Exchanges: device time per step of the collective ops on the busiest
chip (the union of their intervals, so overlapping chunks count once)."""

from chipbench import xplane


def read(trace, ctx):
    per_dev = {d: xplane.length(xplane.exchange_intervals(trace, d))
               for d in trace.ops}
    if not any(per_dev.values()):
        return None
    return xplane.per_step_ms(trace, per_dev, how="max")
