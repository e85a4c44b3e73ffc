"""r2c pack/unpack: device time per step of the ops in the program's
``repro_r2c`` scope (the even/odd pack, the unpack's gathers,
concatenation and half twiddle; a fusion counts by its root).  Mean over
the chips."""

from chipbench import scopes, xplane


def read(trace, ctx):
    s = scopes.for_trace(trace)
    if not s.instrumented:
        return None
    return xplane.per_step_ms(trace, scopes.scope_ns(trace, s, scopes.R2C))
