"""treealg benchmark: tree statistics per tree family + the batched
front door's invocation economics.

Per tree family (GNM-BFS-like random attachment, RGG2D-BFS-like
windowed attachment — the paper's two Euler-tour models):

  * device tour construction + full ``tree_stats`` wall time,
  * the tour's locality fraction delta (EXPERIMENTS.md table), and
  * the **modeled 24576-core time** projected from the counted
    rounds/messages with SuperMUC alpha-beta constants (`_common`),
    the same methodology as every other harness here.

Batch scenario (the serving story): B same-size trees solved one by
one versus through ``solve_forest`` (ONE tour build + ONE batched mesh
solve). The batched path must cost a single solver invocation and beat
the sequential wall time.

Output: ``name,us_per_call,derived`` CSV lines (harness contract) and
benchmarks/results/treealg.json. Standalone:

  BENCH_QUICK=1 python benchmarks/treealg_bench.py
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).parent
QUICK = os.environ.get("BENCH_QUICK", "0") == "1"
P_BENCH = 4 if QUICK else 8
MESH = (2, 2) if QUICK else (2, 4)
N_NODES = 1 << 10 if QUICK else 1 << 14
B_TREES = 6 if QUICK else 8
N_SMALL = 200 if QUICK else 400
ITERS = 1 if QUICK else 3
P_MODEL = 24576

os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={P_BENCH}")
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402
import jax  # noqa: E402

from _common import modeled_large_p  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.core import treealg  # noqa: E402
from repro.core.listrank import ListRankConfig, instances  # noqa: E402

AXES = ("row", "col")
FAMILIES = [("gnm", False), ("rgg2d", True)]


def make_parent(n, seed, locality):
    return instances.gen_tree_parents(n, seed=seed, locality=locality)


def timed(fn, iters):
    fn()  # warmup / compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.min(times))


def main():
    enable_compile_cache()
    mesh = make_mesh(MESH, AXES)
    cfg = ListRankConfig(srs_rounds=2, local_contraction=True)
    results = {"quick": QUICK, "p": P_BENCH, "n_nodes": N_NODES,
               "p_model": P_MODEL, "families": [], "batch": {}}
    print("name,us_per_call,derived")

    for fam, locality in FAMILIES:
        parent = make_parent(N_NODES, seed=1, locality=locality)
        succ_d, _, _ = treealg.build_tour(parent, mesh, cfg=cfg)
        succ_np = np.asarray(jax.device_get(succ_d))
        delta = instances.locality_fraction(succ_np, P_BENCH)
        wall_tour = timed(
            lambda: jax.block_until_ready(
                treealg.build_tour(parent, mesh, cfg=cfg)[0]), ITERS)
        st_holder = {}

        def solve():
            st_holder["st"] = treealg.tree_stats(parent, mesh, cfg=cfg)

        wall_stats = timed(solve, ITERS)
        stats = st_holder["st"].stats
        modeled = modeled_large_p(stats, P_BENCH, P_MODEL, d=1)
        row = dict(family=fam, n_nodes=N_NODES, delta_locality=delta,
                   wall_tour_s=wall_tour, wall_stats_s=wall_stats,
                   rounds=stats["rounds"] // P_BENCH,
                   pd_rounds=stats["pd_rounds"] // P_BENCH,
                   chase_msgs=stats["chase_msgs"],
                   attempts=stats["attempts"],
                   modeled_24576_s=modeled)
        results["families"].append(row)
        print(f"treealg/{fam}/tree_stats,{wall_stats * 1e6:.1f},"
              f"modeled_s={modeled:.5f};delta={delta:.2f};"
              f"rounds={row['rounds']}")

    # batched front door vs one-by-one solves (same-size trees, so the
    # sequential baseline amortizes its compile and the comparison is
    # pure per-invocation cost + rounds)
    parents = [make_parent(N_SMALL, seed=10 + b, locality=bool(b % 2))
               for b in range(B_TREES)]

    def seq():
        for q in parents:
            treealg.tree_stats(q, mesh, cfg=cfg)

    def batched():
        treealg.solve_forest(parents, mesh, cfg=cfg)

    wall_seq = timed(seq, ITERS)
    wall_batch = timed(batched, ITERS)
    speedup = wall_seq / max(wall_batch, 1e-9)
    results["batch"] = dict(n_trees=B_TREES, n_small=N_SMALL,
                            wall_seq_s=wall_seq, wall_batch_s=wall_batch,
                            speedup=speedup, batched_invocations=1,
                            seq_invocations=B_TREES)
    print(f"treealg/batch/solve_forest,{wall_batch * 1e6:.1f},"
          f"speedup={speedup:.2f};trees={B_TREES};invocations=1_vs_"
          f"{B_TREES}")

    out_dir = HERE / "results"
    out_dir.mkdir(exist_ok=True)
    dst = out_dir / ("treealg_quick.json" if QUICK else "treealg.json")
    dst.write_text(json.dumps(results, indent=1))
    print(f"# wrote {dst}")

    # acceptance guards: the RGG2D-like tour must show the locality the
    # instance model promises, every solve must land on attempt 1, and
    # batching B trees must beat B sequential solves.
    fams = {r["family"]: r for r in results["families"]}
    assert fams["rgg2d"]["delta_locality"] > fams["gnm"]["delta_locality"], \
        "RGG2D-like tour lost its locality edge"
    assert all(r["attempts"] == 1 for r in results["families"]), \
        "capacity retries fired on a default config"
    assert speedup > 1.0, \
        f"batched front door slower than sequential ({speedup:.2f}x)"


if __name__ == "__main__":
    main()
