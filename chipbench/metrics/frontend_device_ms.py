"""Front end: device time per step in programs other than the executor's
(the eager pad, crop and convert dispatches), split by XLA module name.
Mean over the chips."""

from chipbench import xplane

#: the executors' compiled programs (``api._execute_local[_inverse]`` and
#: ``dfft.execute_*``) are the only modules whose names hold this
EXECUTOR = "execute"


def read(trace, ctx):
    per_dev = {d: sum(o.dur_ns for o in trace.device_ops(d)
                      if EXECUTOR not in o.module)
               for d in trace.ops}
    if not any(per_dev.values()):
        return None
    return xplane.per_step_ms(trace, per_dev)
