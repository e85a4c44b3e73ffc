"""Device: the share of the traced window in which no op ran, mean over
the chips: 1 - (union of op intervals) / window."""

from chipbench import xplane


def read(trace, ctx):
    if not trace.ops or trace.window_ns <= 0:
        return None
    busy = [xplane.busy_ns(trace, d) for d in trace.ops]
    return 100.0 * (1.0 - sum(busy) / len(busy) / trace.window_ns)
