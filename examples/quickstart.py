"""Quickstart: rank a distributed list with the paper's algorithm.

  PYTHONPATH=src python examples/quickstart.py

On CPU it runs on 8 virtual PEs unless XLA_FLAGS says otherwise
(``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for a 2x2 grid);
on a TPU every chip is one PE (1 chip: a 1x1 grid, the 2x2 host: 2x2).

Builds the paper's List(n, gamma) instance, runs sparse-ruling-set with
spawning (2 rounds + pointer-doubling base case, local contraction on,
reversal avoided via the §2.5 postprocess) on a device mesh, verifies
against the sequential oracle, and prints the stats that reproduce the
paper's analytical predictions.
"""
import os
import sys

# virtual PEs for the demo (must precede the jax import)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import math

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_pe_mesh
from repro.core.listrank import (IndirectionSpec, ListRankConfig, analysis,
                                 instances, rank_list_seq,
                                 rank_list_with_stats)


def main():
    enable_compile_cache()
    mesh = make_pe_mesh()
    p = mesh.size
    n = 1 << 16
    print(f"ranking a {n}-element random list on {p} PEs "
          f"(grid indirection {mesh.shape['row']}x{mesh.shape['col']})")
    succ, rank = instances.gen_list(n, gamma=1.0, seed=0)

    cfg = ListRankConfig(srs_rounds=2, local_contraction=True,
                         ruler_fraction=1 / 32)
    succ_out, rank_out, stats = rank_list_with_stats(
        succ, rank, mesh, cfg=cfg,
        indirection=IndirectionSpec.grid(("row", "col")))

    s_ref, r_ref = rank_list_seq(succ, rank)
    assert np.array_equal(np.asarray(succ_out), s_ref)
    assert np.array_equal(np.asarray(rank_out), r_ref)
    print("matches the sequential oracle")

    r_total = p * max(4, int(n / p / 32))
    print(f"chase rounds:    {stats['rounds'] // p} "
          f"(paper predicts ~n/r+1 = {n / r_total + 1:.0f})")
    print(f"subproblem size: {stats['sub_size']} "
          f"(paper predicts ~r ln(n/r) = "
          f"{r_total * math.log(n / r_total):.0f})")
    print(f"chase messages:  {stats['chase_msgs']} "
          f"(2 hops x ~one per element)")
    print(f"r* from the cost model (SuperMUC constants): "
          f"{analysis.r_star(n, p, 2, analysis.SUPERMUC)}")

    # same run, parameters derived from the §2.6 cost model instead of
    # hand-set: ruler_fraction=None -> per-level r* (tuner.level_plan)
    auto = cfg.with_(ruler_fraction=None)
    _, rank_auto, stats_auto = rank_list_with_stats(
        succ, rank, mesh, cfg=auto,
        indirection=IndirectionSpec.grid(("row", "col")))
    assert np.array_equal(np.asarray(rank_auto), r_ref)
    print(f"auto-tuned (ruler_fraction=None): "
          f"rounds {stats_auto['rounds'] // p} vs {stats['rounds'] // p} "
          f"fixed, rulers {stats_auto['rulers']} vs {stats['rulers']}")


if __name__ == "__main__":
    main()
