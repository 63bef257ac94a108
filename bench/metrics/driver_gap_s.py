"""driver_gap_s (s, mean per solve): host time of the staged driver: from
the first stage attempt's start to the last one's end, less the stage
programs' device-synced walls. It holds the runner lookup and dispatch,
the fatal-counter readback after each stage, spec building and the
driver's bookkeeping. With ``frontdoor_host_s`` and the stage walls it
adds up to the call's latency."""
from harness import mean_per_call


def read(run):
    return mean_per_call(run, lambda c: c.spans[-1][2] - c.spans[0][1]
                         - sum(wall for _, _, _, wall in c.spans))
