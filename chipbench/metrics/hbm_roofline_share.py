"""Device: the least time one step could take on a chip over the chip's
busy time per step.  The least time is the larger of the bytes bound
(each input element read once, each output element written once, per
chip, at the peak HBM bandwidth) and the operations bound (benchFFT's
5 N log2 N for c2c, 2.5 N log2 N for r2c, at the bf16 peak)."""

from chipbench import work, xplane


def read(trace, ctx):
    busy = [xplane.busy_ns(trace, d) for d in trace.ops]
    if not busy or not any(busy) or trace.steps == 0:
        return None
    least_s, _bound = work.least_step_seconds(ctx["work"], ctx["peaks"])
    busy_per_step_s = sum(busy) / len(busy) / trace.steps / 1e9
    return 100.0 * least_s / busy_per_step_s
