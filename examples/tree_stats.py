"""Distributed tree statistics via the treealg subsystem — the paper's
motivating Euler-tour application, now a first-class engine instead of
a host-side postprocess (contrast examples/euler_tour.py, which derives
the same quantities by hand from a raw ranked tour).

  PYTHONPATH=src python examples/tree_stats.py

Builds a forest of random trees, constructs the Euler tours ON DEVICE
(two packed exchange rounds over the mesh), ranks both tour weightings
in ONE batched mesh solve, and reads depth / subtree size / preorder /
postorder for every node of every tree — then re-roots one tree and
verifies everything against a DFS oracle.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.core import treealg  # noqa: E402
from repro.core.listrank import ListRankConfig, instances  # noqa: E402


def dfs_stats(parent):
    sys.setrecursionlimit(1000000)
    n = len(parent)
    children = [[] for _ in range(n)]
    for c in range(n):
        if parent[c] != c:
            children[parent[c]].append(c)
    depth = np.zeros(n, np.int64)
    size = np.ones(n, np.int64)
    pre = np.zeros(n, np.int64)
    post = np.zeros(n, np.int64)
    for r in [c for c in range(n) if parent[c] == c]:
        cp, cs = [0], [0]

        def dfs(u, d):
            depth[u] = d
            pre[u] = cp[0]
            cp[0] += 1
            for v in children[u]:
                dfs(v, d + 1)
                size[u] += size[v]
            post[u] = cs[0]
            cs[0] += 1

        dfs(r, 0)
    return depth, size, pre, post


def main():
    enable_compile_cache()
    p = len(jax.devices())
    mesh = make_mesh((p,), ("pe",))
    cfg = ListRankConfig(srs_rounds=2, local_contraction=True)

    # a batch of independent trees of mixed size/model — the serving
    # scenario: many small queries, one solver invocation
    sizes = [257, 1024, 93, 511, 2048]
    parents = [instances.gen_tree_parents(n, seed=i, locality=bool(i % 2))
               for i, n in enumerate(sizes)]
    print(f"forest of {len(sizes)} trees, {sum(sizes)} nodes, p={p}")

    stats_list = treealg.solve_forest(parents, mesh, cfg=cfg)
    solve = stats_list[0].stats
    print(f"one batched solve: attempts={solve['attempts']}, "
          f"chase rounds={solve['rounds'] // p}, "
          f"messages={solve['chase_msgs']}")
    for i, (q, st) in enumerate(zip(parents, stats_list)):
        d, s, pre, post = dfs_stats(q)
        assert np.array_equal(st.depth, d), f"depth mismatch tree {i}"
        assert np.array_equal(st.subtree_size, s), f"size mismatch {i}"
        assert np.array_equal(st.preorder, pre), f"preorder mismatch {i}"
        assert np.array_equal(st.postorder, post), f"postorder mismatch {i}"
        print(f"  tree {i}: n={q.shape[0]:5d} max depth={st.depth.max():3d} "
              f"mean subtree={st.subtree_size.mean():7.1f}  verified")

    # re-root the largest tree at its deepest node (edge orientation)
    big = int(np.argmax(sizes))
    deepest = int(np.argmax(stats_list[big].depth))
    newp = treealg.root_tree(parents[big], deepest, mesh, cfg=cfg)
    d2, _, _, _ = dfs_stats(newp)
    assert d2[deepest] == 0
    assert d2.max() >= stats_list[big].depth.max()
    print(f"re-rooted tree {big} at node {deepest}: new height {d2.max()} "
          f"(was {stats_list[big].depth.max()})  verified")
    print("tree_stats example OK")


if __name__ == "__main__":
    main()
