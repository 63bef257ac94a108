"""Rehearsal of a benchmark cell on the CPU at a tiny size.

  python3 bench/rehearse.py --workload list-2x2.n20x4-loop

Drives the same harness as ``run.py`` (set-up, closed loop, the span
recorder and the profiler, the comparison with the reference) on XLA's
CPU backend, with as many host devices as the cell has chips and
``--elements-per-pe`` elements per device. It prints whether the
answers were correct and which metric readers found something to read,
never a metric's value: a CPU run measures no device.
"""
import argparse
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def rehearse(workload: str, *, seed: int = 1, seconds: float = 1.0,
             elements_per_pe: int = 1024, pool: int = 3,
             trace: bool = True, entry=None):
    """(correct, compared, names of the metrics whose readers found a
    value) of one CPU run. Needs JAX on the CPU with at least as many
    devices as the cell's chips."""
    import jax

    cell = harness.load_cell(workload)
    devices = jax.devices()
    if devices[0].platform != "cpu" or len(devices) < cell.chips:
        raise RuntimeError(f"a rehearsal of {workload} needs "
                           f"{cell.chips} CPU devices, found {devices}")
    run = harness.run_cell(cell, seed=seed, seconds=seconds,
                           devices=devices[:cell.chips],
                           t_start=time.perf_counter(), trace=trace,
                           elements_per_pe=elements_per_pe, pool=pool,
                           entry=entry)
    correct, compared = harness.check(cell, run)
    specs = cell.per_layer if trace else cell.end_to_end
    found = sorted(harness.read_metrics(specs, run))
    if trace and run.trace.devices:
        harness.breakdown(run)
    return correct, compared, found


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--elements-per-pe", type=int, default=1024)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args()
    chips = harness.load_cell(args.workload).chips
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               f"--xla_force_host_platform_device_count="
                               f"{chips}").strip()
    correct, compared, found = rehearse(
        args.workload, seed=args.seed, seconds=args.seconds,
        elements_per_pe=args.elements_per_pe, trace=args.trace == 1)
    print(f"rehearsal of {args.workload} on the CPU: correct={correct}; "
          f"compared {compared}; readers with a value: {found}")
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main())
