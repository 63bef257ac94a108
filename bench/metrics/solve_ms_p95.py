"""solve_ms_p95 (ms): the 95th percentile of the latency of every call
of the window, from the front-door call to both outputs ready; a call
that failed counts with the time it took to fail."""
import numpy as np


def read(run):
    if len(run.calls) < 2:
        return None
    lat = [c.t_ret - c.t_call for c in run.calls]
    return float(np.percentile(lat, 95)) * 1e3
