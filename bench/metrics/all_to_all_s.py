"""all_to_all_s (s, mean per solve): device time of the all-to-all ops
in the profiler trace of the window, mean over the devices."""
from trace_reduce import op_family, op_seconds

#: the op's HLO name: ``all_to_all.N`` in TPU traces
ALL_TO_ALL = ("all_to_all", "all-to-all")


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.trace.window()
    per_dev = [sum(s for name, s in op_seconds(ops, lo, hi).items()
                   if op_family(name) in ALL_TO_ALL)
               for ops in run.trace.devices.values()]
    if not any(per_dev):
        return None
    return sum(per_dev) / len(per_dev) / len(run.calls)
