"""setup_s (s): process start to the first timed call: JAX and device
start-up, compile or cache load, the instance pool, one warm-up solve."""


def read(run):
    return run.setup_s
