"""Benchmark subprocess worker: runs one list-ranking configuration on
``p`` virtual devices and prints a JSON result line.

Separate process per measurement because the device count must be set
before jax initializes (and compile memory is returned to the OS).

argv: a single JSON object, e.g.
  {"p": 8, "mesh": [2,4], "n_per_pe": 16384, "gamma": 1.0,
   "algorithm": "srs", "srs_rounds": 2, "contraction": true,
   "indirection": "direct|grid|topo", "iters": 3, "instance": "list"}
"""
import json
import os
import sys

spec = json.loads(sys.argv[1])
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={spec['p']}")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.core.listrank import (IndirectionSpec, ListRankConfig,  # noqa
                                 analysis, instances, rank_list_with_stats)
from repro.obs import json_safe_stats  # noqa: E402

MACHINES = {"supermuc": analysis.SUPERMUC, "tpu": analysis.TPU_V5E_ICI,
            "intra": analysis.INTRA_NODE}


def main():
    enable_compile_cache()
    rows, cols = spec.get("mesh") or (1, spec["p"])
    mesh = make_mesh((rows, cols), ("row", "col"))
    n = spec["n_per_pe"] * spec["p"]
    inst = spec.get("instance", "list")
    if inst == "list":
        succ, rank = instances.gen_list(n, gamma=spec.get("gamma", 1.0),
                                        seed=spec.get("seed", 1))
    elif inst == "euler_local":
        succ, rank, _ = instances.gen_euler_tour(n // 2 + 1, seed=1,
                                                 locality=True)
        succ, rank = instances.pad_to_multiple(succ, rank, spec["p"])
    elif inst == "euler_random":
        succ, rank, _ = instances.gen_euler_tour(n // 2 + 1, seed=1,
                                                 locality=False)
        succ, rank = instances.pad_to_multiple(succ, rank, spec["p"])
    else:
        raise ValueError(inst)

    delta = instances.locality_fraction(succ, spec["p"])
    cfg = ListRankConfig(
        algorithm=spec.get("algorithm", "srs"),
        srs_rounds=spec.get("srs_rounds", 2),
        local_contraction=spec.get("contraction", True),
        ruler_fraction=spec.get("ruler_fraction", 1 / 32),
        machine=MACHINES[spec.get("machine", "supermuc")],
        avoid_reversal=spec.get("avoid_reversal", True))
    ind = {"direct": None,
           "grid": IndirectionSpec.grid(("row", "col")),
           "topo": IndirectionSpec.topology(("col",), ("row",))}[
               spec.get("indirection", "direct")]

    # warmup (compile) + timed iterations, paper methodology: discard
    # the first run, report mean of the rest
    times = []
    stats = None
    for it in range(spec.get("iters", 3) + 1):
        t0 = time.time()
        s, r, stats = rank_list_with_stats(succ, rank, mesh, cfg=cfg,
                                           indirection=ind,
                                           seed=spec.get("seed", 1))
        jax.block_until_ready(s)
        dt = time.time() - t0
        if it > 0:
            times.append(dt)

    out = {
        "wall_s_mean": float(np.mean(times)),
        "wall_s_min": float(np.min(times)),
        "wall_s_max": float(np.max(times)),
        "delta_locality": delta,
        "n": n,
        # stats carry int counters plus strings (scales_log), tuples
        # (stage_log) and nested dicts (recovery) — the obs layer owns
        # the canonical JSON-safe conversion
        "stats": json_safe_stats(stats),
    }
    print("RESULT " + json.dumps(out))


if __name__ == "__main__":
    main()
