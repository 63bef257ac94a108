"""rank_rate (Melem/s): list elements of every solve completed in the
window over the time from the window's first call to the last solve's
``block_until_ready``."""


def read(run):
    if not run.done:
        return None
    span = run.t_last - run.calls[0].t_call
    return run.n * len(run.done) / span / 1e6
