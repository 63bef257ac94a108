"""Distributed connectivity and spanning forests — the graphalg front
door: raw edge lists in, components + rooted forests + per-node tree
statistics out, with list ranking as the subroutine throughout.

  PYTHONPATH=src python examples/connectivity.py

Generates multi-component random graphs (GNM-like and RGG2D-like),
runs connected_components / spanning_forest / the end-to-end
graph_stats pipeline (hooking rounds -> unrooted Euler tour -> two
in-program list-ranking solves -> closed-form statistics, ONE jitted
mesh program), verifies against a host union-find, and answers
ancestor queries from the pre/postorder numbers without any further
communication.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.core import graphalg, treealg  # noqa: E402
from repro.core.listrank import ListRankConfig, instances  # noqa: E402


def union_find(n, edges):
    parent = np.arange(n)

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for a, b in edges:
        ra, rb = find(int(a)), find(int(b))
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
    return np.array([find(v) for v in range(n)])


def main():
    enable_compile_cache()
    p = len(jax.devices())
    mesh = make_mesh((p,), ("pe",))
    cfg = ListRankConfig(srs_rounds=1, local_contraction=True)

    n, e = 1 << 11, 1 << 12
    for fam, kw in [("gnm", dict(locality=False, num_components=6)),
                    ("rgg2d", dict(locality=True, num_components=4))]:
        edges = instances.gen_graph_edges(n, e, seed=42, **kw)
        labels, st = graphalg.connected_components(edges, n, mesh, cfg=cfg)
        assert np.array_equal(labels, union_find(n, edges)), fam
        print(f"{fam}: n={n} E={e} -> {np.unique(labels).size} components "
              f"in {st['cc_rounds']} hooking rounds "
              f"({st['cc_msgs']} messages), verified vs union-find")

    # end to end: edges -> rooted forest -> Euler tour -> statistics,
    # one jitted mesh program
    edges = instances.gen_graph_edges(n, e, seed=7, locality=True,
                                      num_components=3)
    gs = graphalg.graph_stats(edges, n, mesh, cfg=cfg)
    print(f"graph_stats: {gs.n_components} components, "
          f"max depth {gs.depth.max()}, attempts={gs.stats['attempts']}")

    # the emitted forest is a first-class treealg input
    st = treealg.tree_stats(gs.parent, mesh, cfg=cfg)
    assert np.array_equal(st.depth, gs.depth)
    assert np.array_equal(st.preorder, gs.preorder)
    print("treealg.tree_stats on the emitted forest: identical statistics")

    # ancestor queries are closed-form over pre/postorder — no solves
    rng = np.random.default_rng(0)
    u = rng.integers(0, n, 5)
    for x in u:
        lo, hi = gs.subtree_interval(int(x))
        anc = gs.is_ancestor(gs.parent[x], x)
        print(f"  node {x}: subtree preorder interval [{lo}, {hi}], "
              f"parent-is-ancestor={bool(anc)}")
        assert bool(anc)
    print("connectivity example OK")


if __name__ == "__main__":
    main()
