"""graphalg benchmark: connectivity + spanning-forest statistics per
instance family, and the end-to-end graph_stats pipeline economics.

Per edge-list family (GNM-like random, RGG2D-like windowed — with and
without multiple components):

  * connected_components and full graph_stats wall time,
  * the hooking-round count and message volume (the §2.6 quantities
    for the *second* communication pattern the repo now exercises),
  * the edge list's PE-crossing fraction (EXPERIMENTS.md connectivity
    table) and the **modeled 24576-core time** projected from counted
    rounds/messages with SuperMUC alpha-beta constants (`_common`),
  * the traced collective footprint of the one-program pipeline
    (count must be instance-independent; recorded in the artifact).

Output: ``name,us_per_call,derived`` CSV lines (harness contract) and
benchmarks/results/graphalg.json. Standalone:

  BENCH_QUICK=1 python benchmarks/graphalg_bench.py
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
N_NODES = 1 << 9 if QUICK else 1 << 12
EDGE_FACTOR = 2
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
from repro.core import graphalg  # noqa: E402
from repro.core.listrank import ListRankConfig, instances  # noqa: E402

AXES = ("row", "col")
FAMILIES = [
    ("gnm", dict(locality=False)),
    ("rgg2d", dict(locality=True)),
    ("gnm_multi", dict(locality=False, num_components=8)),
    ("rgg2d_multi", dict(locality=True, num_components=8)),
]


def timed(fn, iters):
    fn()  # warmup / compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.min(times))


def cross_fraction(edges, p, n):
    m = n // p
    return float(np.mean(edges[:, 0] // m != edges[:, 1] // m))


def main():
    enable_compile_cache()
    mesh = make_mesh(MESH, AXES)
    cfg = ListRankConfig(srs_rounds=1, local_contraction=True)
    results = {"quick": QUICK, "p": P_BENCH, "n_nodes": N_NODES,
               "edge_factor": EDGE_FACTOR, "p_model": P_MODEL,
               "families": []}
    print("name,us_per_call,derived")

    footprints = set()
    for fam, kw in FAMILIES:
        n = N_NODES
        e = EDGE_FACTOR * n
        edges = instances.gen_graph_edges(n, e, seed=1, **kw)
        delta = cross_fraction(edges, P_BENCH, n)

        wall_cc = timed(lambda: graphalg.connected_components(
            edges, n, mesh, cfg=cfg), ITERS)
        holder = {}

        def solve():
            holder["gs"] = graphalg.graph_stats(edges, n, mesh, cfg=cfg)

        wall_stats = timed(solve, ITERS)
        st = holder["gs"].stats
        # fold the graph pipeline's own traffic into the §2.6
        # projection: cc_msgs/tour_msgs are globally-summed like
        # chase_msgs, and each hooking round costs ~8 comm legs (label
        # gather 2, proposals 1, confirmation 1, ~2 shortcut gathers =
        # 4) plus ~6 legs of tour build + finalization per run —
        # rounds in modeled_large_p are per-PE, hence the P_BENCH
        # factor on the replicated cc_rounds counter.
        aug = dict(st)
        aug["rounds"] = st["rounds"] + \
            (8 * st["cc_rounds"] + 6) * P_BENCH
        aug["chase_msgs"] = st["chase_msgs"] + st["cc_msgs"] \
            + st["tour_msgs"]
        modeled = modeled_large_p(aug, P_BENCH, P_MODEL, d=1)
        fp = graphalg.pipeline_collective_footprint(edges, n, mesh, cfg=cfg)
        footprints.add(fp["all_to_all"][0])
        row = dict(
            family=fam, n_nodes=n, n_edges=e,
            cross_fraction=delta,
            n_components=int(holder["gs"].n_components),
            wall_cc_s=wall_cc, wall_stats_s=wall_stats,
            cc_rounds=st["cc_rounds"], cc_msgs=st["cc_msgs"],
            solve_rounds=st["rounds"] // P_BENCH,
            attempts=st["attempts"],
            a2a_count=fp["all_to_all"][0],
            a2a_bytes=fp["all_to_all"][1],
            modeled_24576_s=modeled)
        results["families"].append(row)
        print(f"graphalg/{fam}/cc,{wall_cc * 1e6:.1f},"
              f"rounds={st['cc_rounds']};cross={delta:.2f}")
        print(f"graphalg/{fam}/graph_stats,{wall_stats * 1e6:.1f},"
              f"modeled_s={modeled:.5f};a2a={fp['all_to_all'][0]};"
              f"comps={row['n_components']}")

    out_dir = HERE / "results"
    out_dir.mkdir(exist_ok=True)
    dst = out_dir / ("graphalg_quick.json" if QUICK else "graphalg.json")
    dst.write_text(json.dumps(results, indent=1))
    print(f"# wrote {dst}")

    # acceptance guards: the RGG2D-like families must show the locality
    # the instance model promises, every pipeline must land on attempt
    # 1 with its capacities as derived, and the one-program collective
    # count must be instance-independent (the coalescing invariant).
    fams = {r["family"]: r for r in results["families"]}
    assert fams["rgg2d"]["cross_fraction"] < fams["gnm"]["cross_fraction"], \
        "RGG2D-like edges lost their locality edge"
    assert all(r["attempts"] == 1 for r in results["families"]), \
        "capacity retries fired on a default config"
    assert len(footprints) == 1, \
        f"collective count varies across instances: {footprints}"


if __name__ == "__main__":
    main()
