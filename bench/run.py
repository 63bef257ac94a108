"""One run of one benchmark cell of the list-ranking system, on TPU.

  python3 bench/run.py --workload list-1chip.n20-loop --seed 7 \\
      --seconds 10 --trace 0

Runs from the root of a checkout and needs the chips the cell asks for:
where JAX finds no TPU, or fewer chips, it exits non-zero and prints no
result. Set-up (compile or cache load, the instance pool, one warm-up
solve) is timed from process start; then the cell's closed loop runs
for ``--seconds``, and every answer is compared with the plain
reference. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` records the program's spans and a profiler trace of the
window and reports its per-layer metrics, with the device's busy time
and a breakdown.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``compared``, each compared number beside its
limit. The last lines of standard error give the same numbers.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime's logs go under the run's TMPDIR, not a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    cell = harness.load_cell(args.workload)
    harness.configure_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        log(f"FAIL: no TPU: JAX found {dev.platform!r} devices")
        return 2
    if len(devices) < cell.chips:
        log(f"FAIL: {cell.name} needs {cell.chips} chips, JAX found "
            f"{len(devices)}")
        return 2

    trace = args.trace == 1
    run = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           devices=devices[:cell.chips], t_start=T_START,
                           trace=trace)
    attempts = [c.counters["attempts"] for c in run.done]
    log(f"set-up: {run.setup_s:.3f} s; {run.setup_compiles}")
    log(f"window: {len(run.calls)} calls of n={run.n} in "
        f"{run.t_last - run.t_first:.3f} s; compiles in the window: "
        f"{run.window_compiles}; attempts per solve: "
        f"max {max(attempts, default=0)}, escalated "
        f"{sum(a > 1 for a in attempts)}")
    lat = sorted((c.t_ret - c.t_call, c.index) for c in run.calls)
    between = run.t_last - run.t_first - sum(t for t, _ in lat)
    log(f"latency ms: min {lat[0][0] * 1e3:.3f}, median "
        f"{lat[len(lat) // 2][0] * 1e3:.3f}, max {lat[-1][0] * 1e3:.3f}; "
        f"slowest calls (index: ms) "
        + ", ".join(f"{k}: {t * 1e3:.1f}" for t, k in lat[-3:])
        + f"; harness time between calls {between:.3f} s; garbage "
        f"collections per generation {run.gc.count}, taking "
        f"{[round(t, 4) for t in run.gc.seconds]} s")
    for c in run.calls:
        if c.error is not None:
            log(f"call {c.index} failed: {c.error}")

    correct, compared = harness.check(cell, run)
    result = {
        "correct": correct,
        "attempted": len(run.calls),
        "failed": len(run.calls) - len(run.done),
        "metrics": harness.read_metrics(
            cell.per_layer if trace else cell.end_to_end, run),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": run.peak_bytes},
    }
    if trace:
        busy = run.trace.busy_per_device()
        lo, hi = run.trace.window()
        result["device"]["busy_s"] = sum(busy) / max(len(busy), 1)
        result["device"]["window_s"] = hi - lo
        result["breakdown"] = harness.breakdown(run)
    result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
