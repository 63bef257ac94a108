"""Euler-tour tree computations on top of distributed list ranking —
the paper's motivating application family (§1) and its tree-rooting
future-work direction.

  PYTHONPATH=src python examples/euler_tour.py

Generates a random tree, builds its Euler tour (one list element per
arc), ranks the tour with SRS, and derives from the ranks alone:
  - each node's depth,
  - each node's subtree size,
  - a rooting of the tree (parent pointers) w.r.t. node 0.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.core.listrank import (ListRankConfig, instances,
                                 rank_list_with_stats)


def main():
    enable_compile_cache()
    p = len(jax.devices())
    mesh = make_mesh((p,), ("pe",))
    n_nodes = 4097
    succ, rank, arcs = instances.gen_euler_tour(n_nodes, seed=3,
                                                locality=True)
    succ, rank = instances.pad_to_multiple(succ, rank, p)
    n_arcs = arcs.shape[0]
    print(f"tree with {n_nodes} nodes -> Euler tour of {n_arcs} arcs")

    cfg = ListRankConfig(srs_rounds=2, local_contraction=True)
    _, rank_out, stats = rank_list_with_stats(succ, rank, mesh, cfg=cfg)
    # rank = #arcs after this arc in the tour; position from the front:
    pos = (n_arcs - 1) - np.asarray(rank_out)[:n_arcs]

    # arc ids: down(c) = 2(c-1), up(c) = 2(c-1)+1 (instances.py layout)
    down_pos = np.full(n_nodes, -1)
    up_pos = np.full(n_nodes, -1)
    for c in range(1, n_nodes):
        down_pos[c] = pos[2 * (c - 1)]
        up_pos[c] = pos[2 * (c - 1) + 1]

    # subtree size: arcs strictly between down(c) and up(c) are the
    # subtree's internal arcs: (up - down - 1) arcs = 2*(size-1)
    size = np.ones(n_nodes, np.int64)
    size[1:] = (up_pos[1:] - down_pos[1:] - 1) // 2 + 1
    size[0] = n_nodes
    # depth: number of enclosing (down, up) intervals; equivalently
    # depth(c) = #down-arcs before down(c) minus #up-arcs before down(c)
    order = np.argsort(pos)
    delta = np.where(order % 2 == 0, 1, -1)  # even arc ids are "down"
    depth_at = np.cumsum(delta)  # depth after traversing the arc
    depth = np.zeros(n_nodes, np.int64)
    for c in range(1, n_nodes):
        depth[c] = depth_at[down_pos[c]]
    # rooting: parent = the other endpoint of the down arc
    parent = np.zeros(n_nodes, np.int64)
    for c in range(1, n_nodes):
        parent[c] = arcs[2 * (c - 1)][0]

    # verify against a BFS ground truth
    import collections
    adj = collections.defaultdict(list)
    for c in range(1, n_nodes):
        adj[parent[c]].append(c)
    truth_depth = np.zeros(n_nodes, np.int64)
    q = collections.deque([0])
    while q:
        u = q.popleft()
        for w in adj[u]:
            truth_depth[w] = truth_depth[u] + 1
            q.append(w)
    assert np.array_equal(depth, truth_depth), "depth mismatch"
    assert size[0] == n_nodes and (size >= 1).all()
    print(f"depth/subtree-size verified (max depth {depth.max()}, "
          f"mean subtree {size.mean():.1f})")
    print(f"list-ranking rounds: {stats['rounds'] // p}, "
          f"messages: {stats['chase_msgs']}")


if __name__ == "__main__":
    main()
