"""Local 1D stages: device time per step of copies and transposes (the
"data formatting" ops).  Mean over the chips."""

from chipbench import xplane


def read(trace, ctx):
    per_dev = xplane.category_ns(trace, xplane.RELAYOUT)
    if not any(per_dev.values()):
        return None
    return xplane.per_step_ms(trace, per_dev)
