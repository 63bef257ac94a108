"""graphalg subsystem tests (single-device mesh; the 8-PE matrix runs
in tests/_subprocess_smoke.py suite "graphalg"): connected components and spanning forests
against a host union-find across the instance families, the end-to-end
graph_stats pipeline against per-node DFS recomputation and against
treealg on the emitted parent array, the closed-form ancestor/interval
query layer, and the pipeline's pinned collective footprint."""
import numpy as np
import pytest
from _graph_oracles import check_spanning_forest, union_find_labels
from _tree_oracles import dfs_stats

from repro.launch.mesh import make_mesh
from repro.core import graphalg, treealg
from repro.core.listrank import ListRankConfig, instances


def mesh1():
    return make_mesh((1,), ("pe",))


CFG = ListRankConfig(srs_rounds=1, local_contraction=False)

#: name -> (n, E, gen kwargs): GNM-like, RGG2D-like, multi-component
#: variants of both, plus the degenerate single-edge/empty/singleton
#: corners the acceptance criteria call out.
FAMILIES = [
    ("gnm", 48, 80, dict(locality=False)),
    ("rgg2d", 48, 80, dict(locality=True)),
    ("gnm_multi", 60, 70, dict(locality=False, num_components=4)),
    ("rgg2d_multi", 60, 70, dict(locality=True, num_components=3)),
    ("tree", 33, 32, dict(locality=False)),
    ("sparse_multi", 24, 12, dict(locality=False, num_components=12)),
]


def family_edges(n, e, seed, kw):
    return instances.gen_graph_edges(n, e, seed=seed, **kw)


# --------------------------------------------------------------------------
# connected components vs union-find
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name,n,e,kw", FAMILIES)
def test_connected_components_matches_union_find(name, n, e, kw):
    edges = family_edges(n, e, seed=len(name), kw=kw)
    labels, stats = graphalg.connected_components(edges, n, mesh1(),
                                                  cfg=CFG)
    np.testing.assert_array_equal(labels, union_find_labels(n, edges))
    assert stats["attempts"] == 1
    assert stats["cc_unconverged"] == 0


def test_connected_components_degenerate_inputs():
    # empty graph: all singletons
    labels, _ = graphalg.connected_components(
        np.zeros((0, 2), np.int64), 5, mesh1(), cfg=CFG)
    np.testing.assert_array_equal(labels, np.arange(5))
    # single edge
    labels, _ = graphalg.connected_components(
        np.array([[3, 1]]), 5, mesh1(), cfg=CFG)
    np.testing.assert_array_equal(labels, [0, 1, 2, 1, 4])
    # self-loops and duplicates change nothing
    labels, _ = graphalg.connected_components(
        np.array([[2, 2], [3, 1], [1, 3], [3, 1]]), 4, mesh1(), cfg=CFG)
    np.testing.assert_array_equal(labels, [0, 1, 2, 1])


def test_rejects_bad_edges():
    with pytest.raises(ValueError, match="out of range"):
        graphalg.connected_components(np.array([[0, 9]]), 4, mesh1(),
                                      cfg=CFG)
    with pytest.raises(ValueError, match="\\(E, 2\\)"):
        graphalg.connected_components(np.zeros((3,), np.int64), 4, mesh1(),
                                      cfg=CFG)


# --------------------------------------------------------------------------
# spanning forest: real graph edges, min-id roots, spans the components
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name,n,e,kw", FAMILIES)
def test_spanning_forest_valid(name, n, e, kw):
    edges = family_edges(n, e, seed=7 + len(name), kw=kw)
    parent, labels, stats = graphalg.spanning_forest(edges, n, mesh1(),
                                                     cfg=CFG)
    assert check_spanning_forest(n, edges, parent, labels) == []
    assert stats["forest_edges"] == n - np.unique(labels).size


def test_spanning_forest_feeds_treealg():
    """The tentpole integration contract: the emitted parent array is a
    valid treealg input — solve_forest/tree_stats consume it directly,
    and root_tree re-roots a component of it."""
    edges = family_edges(40, 70, seed=11, kw=dict(locality=True))
    parent, labels, _ = graphalg.spanning_forest(edges, 40, mesh1(),
                                                 cfg=CFG)
    st = treealg.tree_stats(parent, mesh1(), cfg=CFG)
    d, s, pre, post = dfs_stats(parent)
    np.testing.assert_array_equal(st.depth, d)
    np.testing.assert_array_equal(st.preorder, pre)
    # re-root the (single) component at an arbitrary non-root node
    assert np.unique(labels).size == 1
    newp = treealg.root_tree(parent, 17, mesh1(), cfg=CFG)
    e_old = {frozenset((c, int(parent[c]))) for c in range(40)
             if parent[c] != c}
    e_new = {frozenset((c, int(newp[c]))) for c in range(40)
             if newp[c] != c}
    assert e_old == e_new and newp[17] == 17


# --------------------------------------------------------------------------
# graph_stats end to end
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name,n,e,kw", FAMILIES)
def test_graph_stats_matches_dfs(name, n, e, kw):
    edges = family_edges(n, e, seed=23 + len(name), kw=kw)
    gs = graphalg.graph_stats(edges, n, mesh1(), cfg=CFG)
    assert check_spanning_forest(n, edges, gs.parent, gs.components) == []
    depth, size, pre, post = dfs_stats(gs.parent)
    np.testing.assert_array_equal(gs.depth, depth)
    np.testing.assert_array_equal(gs.subtree_size, size)
    np.testing.assert_array_equal(gs.preorder, pre)
    np.testing.assert_array_equal(gs.postorder, post)


def test_graph_stats_matches_treealg_on_emitted_forest():
    """depth/subtree/pre/postorder of the one-program pipeline must be
    bit-identical to running treealg.tree_stats on the forest it
    emitted (two independent derivations of the same statistics)."""
    edges = family_edges(52, 90, seed=31, kw=dict(num_components=2))
    gs = graphalg.graph_stats(edges, 52, mesh1(), cfg=CFG)
    st = treealg.tree_stats(gs.parent, mesh1(), cfg=CFG)
    np.testing.assert_array_equal(gs.depth, st.depth)
    np.testing.assert_array_equal(gs.subtree_size, st.subtree_size)
    np.testing.assert_array_equal(gs.preorder, st.preorder)
    np.testing.assert_array_equal(gs.postorder, st.postorder)
    np.testing.assert_array_equal(gs.components, st.root_of)


def test_graph_stats_isolated_nodes():
    gs = graphalg.graph_stats(np.array([[5, 6]]), 8, mesh1(), cfg=CFG)
    np.testing.assert_array_equal(gs.components, [0, 1, 2, 3, 4, 5, 5, 7])
    np.testing.assert_array_equal(gs.parent, [0, 1, 2, 3, 4, 5, 5, 7])
    np.testing.assert_array_equal(gs.depth, [0, 0, 0, 0, 0, 0, 1, 0])
    np.testing.assert_array_equal(gs.subtree_size, [1, 1, 1, 1, 1, 2, 1, 1])
    np.testing.assert_array_equal(gs.preorder, [0, 0, 0, 0, 0, 0, 1, 0])
    np.testing.assert_array_equal(gs.postorder, [0, 0, 0, 0, 0, 1, 0, 0])


def test_graph_stats_query_layer():
    edges = family_edges(36, 50, seed=41, kw=dict(num_components=3))
    gs = graphalg.graph_stats(edges, 36, mesh1(), cfg=CFG)
    n = gs.n_nodes
    # reference ancestor matrix by parent walking
    ref = np.zeros((n, n), bool)
    for x in range(n):
        w = x
        while True:
            ref[w, x] = True
            if gs.parent[w] == w:
                break
            w = int(gs.parent[w])
    got = gs.is_ancestor(np.arange(n)[:, None], np.arange(n)[None, :])
    np.testing.assert_array_equal(got, ref)
    # subtree intervals: v in subtree(u) <=> pre[v] in [lo_u, hi_u]
    # (same component)
    lo, hi = gs.subtree_interval(np.arange(n))
    for u in range(n):
        inside = gs.same_component(u, np.arange(n)) & \
            (gs.preorder >= lo[u]) & (gs.preorder <= hi[u])
        np.testing.assert_array_equal(inside, ref[u])
    # component helpers
    assert gs.n_components == np.unique(gs.components).size
    np.testing.assert_array_equal(
        gs.component_size(np.arange(n)),
        np.bincount(gs.components, minlength=n)[gs.components])


@pytest.mark.parametrize("variant", ["unpacked", "doubling"])
def test_graph_stats_transport_and_algorithm_variants(variant):
    """The pipeline rides the exchange layer and the full solver, so
    the unpacked wire path and the pointer-doubling algorithm must
    produce the identical result."""
    cfg = (CFG.with_(wire_packing=False) if variant == "unpacked"
           else CFG.with_(algorithm="doubling"))
    edges = family_edges(30, 45, seed=2, kw=dict(locality=False))
    ref = graphalg.graph_stats(edges, 30, mesh1(), cfg=CFG)
    got = graphalg.graph_stats(edges, 30, mesh1(), cfg=cfg)
    np.testing.assert_array_equal(got.parent, ref.parent)
    np.testing.assert_array_equal(got.depth, ref.depth)
    np.testing.assert_array_equal(got.preorder, ref.preorder)


# --------------------------------------------------------------------------
# the coalescing invariant: pinned collective footprint
# --------------------------------------------------------------------------

def test_pipeline_collective_count_static():
    """Acceptance criterion: graph_stats runs as one jitted mesh
    program whose collective count is pinned by jaxpr inspection. The
    hooking/shortcut/solver loops are while_loops, so the traced
    count must be static — identical across instance sizes — and every
    mesh-crossing primitive must be accounted for."""
    mesh = mesh1()
    small = graphalg.pipeline_collective_footprint(
        family_edges(32, 48, seed=1, kw=dict(locality=False)), 32, mesh,
        cfg=CFG)
    large = graphalg.pipeline_collective_footprint(
        family_edges(128, 256, seed=2, kw=dict(locality=True,
                                               num_components=2)),
        128, mesh, cfg=CFG)
    assert {k: c for k, (c, _) in small.items()} \
        == {k: c for k, (c, _) in large.items()}
    assert small["all_to_all"][0] > 0
    # volume scales with the instance while the count stays flat
    assert large["all_to_all"][1] > small["all_to_all"][1]
    # the cc-only prefix traces strictly fewer collectives
    cc_only = graphalg.pipeline_collective_footprint(
        family_edges(32, 48, seed=1, kw=dict(locality=False)), 32, mesh,
        cfg=CFG, mode="cc")
    assert cc_only["all_to_all"][0] < small["all_to_all"][0]
