"""Front end: host time per step inside the program's front-end spans
(``repro.fftn`` and its kin) less their ``repro.execute`` child, the
executor's dispatch: the front end's own glue."""

from chipbench import scopes


def read(trace, ctx):
    s = scopes.for_trace(trace)
    if not s.instrumented:
        return None
    own = sum(scopes.self_ns(f, s) for f in scopes.front_spans(trace, s))
    return own / trace.steps / 1e6
