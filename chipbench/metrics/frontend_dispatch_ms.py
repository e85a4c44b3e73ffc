"""Front end: host time per step from the call into the public front end
until it returns (before the wait for the result), by the host clock."""


def read(trace, ctx):
    d = ctx["dispatch_s"]
    return sum(d) / len(d) * 1e3 if d else None
