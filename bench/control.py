"""The controls of the comparison that decides ``correct``, on the chip
at the cell's own size (not part of a benchmark run).

  python3 bench/control.py --workload list-1chip.n20-loop \\
      --seeds 11,12,13 --seconds 3

For each control of ``controls.CONTROLS`` and each seed, one short run
of the cell's closed loop with the control in the program's place,
compared with the reference as a benchmark run compares the program.
Prints one JSON line per run: the control, the seed, ``correct`` and
the compared numbers. Every control must read ``correct`` false.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import controls  # noqa: E402
import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    harness.configure_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"FAIL: {cell.name} needs {cell.chips} TPU chips, found "
              f"{devices}", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    all_failed = True
    for name, kw in controls.CONTROLS.items():
        for seed in (int(s) for s in args.seeds.split(",")):
            entry = controls.Jumping(devices, **kw)
            run = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                                   devices=devices,
                                   t_start=time.perf_counter(), entry=entry)
            correct, compared = harness.check(cell, run)
            all_failed &= not correct
            print(json.dumps({"control": name, "seed": seed,
                              "n": run.n, "calls": len(run.calls),
                              "correct": correct, "compared": compared}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
