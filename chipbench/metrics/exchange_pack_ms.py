"""Exchanges: device time per step of the ops in the program's
``repro_exchange`` scope other than the collectives themselves (chunk
slices, reshapes and concatenations around them); the busiest chip's."""

from chipbench import scopes, xplane


def read(trace, ctx):
    s = scopes.for_trace(trace)
    if not any(scopes.scope_ns(trace, s, scopes.EXCHANGE).values()):
        return None
    per_dev = scopes.scope_ns(trace, s, scopes.EXCHANGE,
                              skip_categories=xplane.EXCHANGE)
    return xplane.per_step_ms(trace, per_dev, how="max")
