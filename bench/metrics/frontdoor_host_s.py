"""frontdoor_host_s (s, mean per solve): host time of the front door
outside the staged driver's stage attempts: from the call to the first
stage attempt, plus from the last attempt's end to the call's return."""
from harness import mean_per_call


def read(run):
    return mean_per_call(run, lambda c: (c.spans[0][1] - c.t_call)
                         + (c.t_ret - c.spans[-1][2]))
