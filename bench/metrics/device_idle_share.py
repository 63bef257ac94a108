"""device_idle_share (%): the share of the traced window in which no op
ran on the device, from the profiler trace, mean over the devices."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.trace.window()
    busy = run.trace.busy_per_device()
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
