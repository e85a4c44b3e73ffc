"""Local 1D stages: device time per step of the fused four-step kernel, the
ops in the program's ``repro_dft_fused`` scope (the Pallas kernel's custom
call).  Layout copies that XLA puts around the kernel sometimes carry its
``op_name``; they count in ``relayout_ms``, not here.  Mean over the
chips."""

from chipbench import scopes, xplane

FUSED = "repro_dft_fused"


def read(trace, ctx):
    s = scopes.for_trace(trace)
    per_dev = scopes.scope_ns(trace, s, FUSED,
                              skip_categories=xplane.RELAYOUT)
    if not any(per_dev.values()):
        return None
    return xplane.per_step_ms(trace, per_dev)
