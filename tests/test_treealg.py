"""treealg subsystem tests (single-device mesh; multi-PE in
tests/_subprocess_smoke.py suite "treealg"): device tour vs the instances.py oracle, tree
statistics vs per-node DFS recomputation on every instance family, the
re-rooting orientation, and the batched front door's two contracts —
one solver invocation per batch, and a per-round collective count
identical to a single-instance solve (jaxpr inspection)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _tree_oracles import dfs_stats
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.core import treealg
from repro.core.listrank import (ListRankConfig, instances, introspect,
                                 rank_list_seq)
from repro.core.listrank import api as api_lib
from repro.core.listrank.exchange import MeshPlan
from repro.core.listrank.instances import gen_tree_parents
from repro.core.treealg import batch as batch_lib


def mesh1():
    return make_mesh((1,), ("pe",))


CFG = ListRankConfig(srs_rounds=1, local_contraction=False)


# --------------------------------------------------------------------------
# device tour construction vs the host oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,locality,num_trees,seed", [
    (2, False, 1, 0), (64, False, 1, 1), (64, True, 1, 2),
    (65, True, 3, 3), (128, False, 9, 4), (5, False, 5, 5),
])
def test_build_tour_matches_oracle(n, locality, num_trees, seed):
    parent = gen_tree_parents(n, seed, locality, num_trees)
    succ, w, n_pad = treealg.build_tour(parent, mesh1(), cfg=CFG)
    succ_np = np.asarray(jax.device_get(succ))[:2 * n]
    np.testing.assert_array_equal(
        succ_np, treealg.oracle_tour(n, parent).astype(np.int32))
    # unit weights: 1 on tour arcs, 0 on terminals/dummies
    w_np = np.asarray(jax.device_get(w))[:2 * n]
    np.testing.assert_array_equal(w_np, (succ_np != np.arange(2 * n)))


@pytest.mark.parametrize("variant", ["unpacked", "pallas_pack"])
def test_build_tour_transport_variants(variant):
    """The construction rides the exchange layer, so both wire paths
    must produce the identical tour."""
    cfg = (CFG.with_(wire_packing=False) if variant == "unpacked"
           else CFG.with_(use_pallas_pack=True))
    parent = gen_tree_parents(60, 5)
    succ, _, _ = treealg.build_tour(parent, mesh1(), cfg=cfg)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(succ))[:120],
        treealg.oracle_tour(60, parent).astype(np.int32))


def test_build_tour_weighted_weights():
    parent = gen_tree_parents(50, 7)
    succ, w, _ = treealg.build_tour(parent, mesh1(), cfg=CFG, weighted=True)
    succ_np = np.asarray(jax.device_get(succ))[:100]
    w_np = np.asarray(jax.device_get(w))[:100]
    idx = np.arange(100)
    term = succ_np == idx
    np.testing.assert_array_equal(w_np[term], 0)
    np.testing.assert_array_equal(w_np[~term & (idx % 2 == 0)], 1)
    np.testing.assert_array_equal(w_np[~term & (idx % 2 == 1)], -1)


def test_build_tour_rejects_bad_input():
    with pytest.raises(ValueError):
        treealg.build_tour(np.array([5, 0], np.int64), mesh1(), cfg=CFG)
    with pytest.raises(ValueError):
        treealg.build_tour(np.zeros(0, np.int64), mesh1(), cfg=CFG)
    forest = np.array([0, 1, 1], np.int64)
    with pytest.raises(ValueError, match="single-tree"):
        treealg.build_tour(forest, mesh1(), cfg=CFG, cut_at=2)


# --------------------------------------------------------------------------
# tree statistics vs the DFS oracle, per instance family
# --------------------------------------------------------------------------

FAMILIES = [
    ("gnm", 101, dict(locality=False)),       # GNM-BFS-like
    ("rgg2d", 102, dict(locality=True)),      # RGG2D-BFS-like
    ("gnm_forest", 103, dict(locality=False, num_trees=6)),
    ("rgg2d_forest", 104, dict(locality=True, num_trees=4)),
]


@pytest.mark.parametrize("name,seed,kw", FAMILIES)
def test_tree_stats_matches_dfs(name, seed, kw):
    parent = gen_tree_parents(120, seed=seed, **kw)
    st = treealg.tree_stats(parent, mesh1(), cfg=CFG)
    depth, size, pre, post = dfs_stats(parent)
    np.testing.assert_array_equal(st.depth, depth)
    np.testing.assert_array_equal(st.subtree_size, size)
    np.testing.assert_array_equal(st.preorder, pre)
    np.testing.assert_array_equal(st.postorder, post)


@pytest.mark.parametrize("name,seed,kw", FAMILIES)
def test_single_stat_fast_paths(name, seed, kw):
    parent = gen_tree_parents(90, seed=seed + 50, **kw)
    depth, size, _, _ = dfs_stats(parent)
    np.testing.assert_array_equal(
        treealg.node_depth(parent, mesh1(), cfg=CFG), depth)
    np.testing.assert_array_equal(
        treealg.subtree_size(parent, mesh1(), cfg=CFG), size)


def test_preorder_postorder_wrappers():
    parent = gen_tree_parents(60, 3, num_trees=2)
    _, _, pre, post = dfs_stats(parent)
    np.testing.assert_array_equal(
        treealg.preorder(parent, mesh1(), cfg=CFG), pre)
    np.testing.assert_array_equal(
        treealg.postorder(parent, mesh1(), cfg=CFG), post)


def test_singleton_trees():
    parent = np.arange(8, dtype=np.int64)  # 8 isolated roots
    st = treealg.tree_stats(parent, mesh1(), cfg=CFG)
    np.testing.assert_array_equal(st.depth, 0)
    np.testing.assert_array_equal(st.subtree_size, 1)
    np.testing.assert_array_equal(st.preorder, 0)
    np.testing.assert_array_equal(st.postorder, 0)


def test_weighted_int32_roundtrip_exact():
    """±1 int32 weights through the full solver are bit-exact (the
    chase_leaves weight-dtype plumbing): compare to the sequential
    oracle on a weighted device-built tour."""
    parent = gen_tree_parents(80, 11, locality=True)
    succ, w, n_pad = treealg.build_tour(parent, mesh1(), cfg=CFG,
                                        weighted=True)
    succ_np = np.asarray(jax.device_get(succ))
    w_np = np.asarray(jax.device_get(w))
    from repro.core.listrank import rank_list_with_stats
    s_ref, r_ref = rank_list_seq(succ_np, w_np)
    s, r, _ = rank_list_with_stats(succ_np, w_np, mesh1(), cfg=CFG)
    assert np.asarray(r).dtype == np.int32
    np.testing.assert_array_equal(np.asarray(s), s_ref)
    np.testing.assert_array_equal(np.asarray(r), r_ref)


# --------------------------------------------------------------------------
# re-rooting (edge orientation)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,new_root,seed", [
    (2, 1, 0), (40, 17, 1), (40, 0, 2), (100, 99, 3), (77, 38, 4),
])
def test_root_tree(n, new_root, seed):
    parent = gen_tree_parents(n, seed)
    newp = treealg.root_tree(parent, new_root, mesh1(), cfg=CFG)
    assert newp[new_root] == new_root
    # same edge set, and a valid rooting (depths consistent)
    e_old = {frozenset((c, int(parent[c]))) for c in range(n)
             if parent[c] != c}
    e_new = {frozenset((c, int(newp[c]))) for c in range(n) if newp[c] != c}
    assert e_old == e_new
    depth, _, _, _ = dfs_stats(newp)
    assert depth[new_root] == 0 and (depth[np.arange(n) != new_root] > 0).all()


# --------------------------------------------------------------------------
# batched front door
# --------------------------------------------------------------------------

@pytest.mark.parametrize("parent", [
    [1, 0, 0],        # 2-cycle (collapses to spurious fixed points
                      # under jumping — the regression case)
    [1, 2, 0],        # 3-cycle
    [0, 2, 3, 1],     # root plus a cycle hanging off it
])
def test_roots_and_sizes_rejects_cycles(parent):
    with pytest.raises(ValueError, match="cycle"):
        treealg.roots_and_sizes(np.asarray(parent, np.int64))


def test_batch_rejects_out_of_range_ids():
    """Out-of-range ids must fail loudly BEFORE packing — after the
    offset relabeling they would silently alias into a neighboring
    instance's id window."""
    good = instances.gen_list(16, 1.0, seed=0)
    bad_succ = np.array([0, 5], np.int32)  # 5 out of range for n=2
    with pytest.raises(ValueError, match="out of range"):
        treealg.pack_instances([good, (bad_succ, np.zeros(2, np.int32))])
    with pytest.raises(ValueError, match="out of range"):
        treealg.solve_forest([np.array([0, 2]), np.array([0, 0, 1])],
                             mesh1(), cfg=CFG)


def test_pack_instances_int32_overflow_guard():
    """Offset relabeling must refuse batches whose packed ids would
    wrap int32 — checked on shapes BEFORE any elementwise work, so the
    boundary case costs no memory (broadcast views carry no data)."""
    big = np.broadcast_to(np.int32(0), (1 << 29,))
    zeros = np.broadcast_to(np.int32(0), (1 << 29,))
    with pytest.raises(ValueError, match="overflows the int32"):
        treealg.pack_instances([(big, zeros)] * 4)  # 2^31 ids
    # the guard threshold itself, exactly at the boundary
    limit = batch_lib.PACKED_ID_LIMIT
    batch_lib._check_packed_size(limit, "t")  # fits
    with pytest.raises(ValueError, match="split the batch"):
        batch_lib._check_packed_size(limit + 1, "t")
    # solve_forest guards the *arc* id space (2x the packed nodes)
    with pytest.raises(ValueError, match="overflows the int32"):
        treealg.solve_forest([np.broadcast_to(np.int64(0), (1 << 30,))],
                             mesh1(), cfg=CFG)


def test_is_ancestor_and_subtree_interval():
    """Closed-form ancestor/interval queries from pre/postorder —
    checked against explicit parent walking on a forest."""
    parent = gen_tree_parents(70, seed=13, num_trees=3)
    st = treealg.tree_stats(parent, mesh1(), cfg=CFG)
    n = st.n_nodes
    ref = np.zeros((n, n), bool)
    for x in range(n):
        w = x
        while True:
            ref[w, x] = True
            if parent[w] == w:
                break
            w = int(parent[w])
    got = st.is_ancestor(np.arange(n)[:, None], np.arange(n)[None, :])
    np.testing.assert_array_equal(got, ref)
    # scalar form + the subtree preorder interval
    lo, hi = st.subtree_interval(np.arange(n))
    for u in range(0, n, 7):
        assert bool(st.is_ancestor(u, u))
        inside = (st.root_of == st.root_of[u]) & \
            (st.preorder >= lo[u]) & (st.preorder <= hi[u])
        np.testing.assert_array_equal(inside, ref[u])
    # module-level function is the shared implementation
    np.testing.assert_array_equal(
        treealg.is_ancestor(st.preorder, st.postorder, st.root_of,
                            np.arange(n)[:, None], np.arange(n)[None, :]),
        ref)


def test_chase_wire_words_dtype_invariant():
    """The modeled-volume constant is weight-dtype independent: every
    supported dtype packs to one 32-bit wire word (api.chase_leaves)."""
    assert api_lib.chase_wire_words(jnp.int32) \
        == api_lib.chase_wire_words(jnp.float32) == api_lib.CHASE_WIRE_WORDS


def test_pack_unpack_roundtrip():
    batch = [instances.gen_list(33, 1.0, seed=s, num_lists=2) for s in
             range(3)]
    succ, rank, offsets = treealg.pack_instances(batch)
    assert succ.shape[0] == 99 and offsets[-1] == 99
    out = treealg.unpack_results(succ, rank, offsets)
    for (s0, r0), (s1, r1) in zip(batch, out):
        np.testing.assert_array_equal(s0, s1)
        np.testing.assert_array_equal(r0, r1)


def test_rank_lists_matches_oracle_and_single_invocation(monkeypatch):
    batch = [instances.gen_list(64, 1.0, seed=s) for s in range(3)]
    batch.append(instances.gen_random_lists(96, num_lists=4, seed=7,
                                            weighted=True))
    calls = []
    real = batch_lib.rank_list_with_stats

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(batch_lib, "rank_list_with_stats", spy)
    results, stats = treealg.rank_lists_with_stats(batch, mesh1(), cfg=CFG)
    assert len(calls) == 1, "batch must cost ONE solver invocation"
    for (s_in, r_in), (s_out, r_out) in zip(batch, results):
        s_ref, r_ref = rank_list_seq(s_in, r_in)
        np.testing.assert_array_equal(s_out, s_ref)
        np.testing.assert_array_equal(r_out, r_ref)


def test_solve_forest_matches_per_tree():
    parents = [gen_tree_parents(n, seed=n) for n in (5, 16, 41, 64)]
    out = treealg.solve_forest(parents, mesh1(), cfg=CFG)
    for q, st in zip(parents, out):
        depth, size, pre, post = dfs_stats(q)
        np.testing.assert_array_equal(st.parent, q)
        np.testing.assert_array_equal(st.depth, depth)
        np.testing.assert_array_equal(st.subtree_size, size)
        np.testing.assert_array_equal(st.preorder, pre)
        np.testing.assert_array_equal(st.postorder, post)


def solver_collective_counts(n, mesh, cfg):
    """all_to_all (etc.) counts of the traced solver program for an
    n-element instance — the quantity the batched front door must keep
    flat versus a single-instance solve."""
    pe_axes = tuple(mesh.axis_names)
    plan = MeshPlan.from_mesh(mesh, pe_axes, None,
                              wire_packing=cfg.wire_packing)
    m = n // plan.p
    specs = api_lib.build_specs(cfg, plan, m, n, term_bound=8)
    fn = functools.partial(api_lib._solve_sharded, plan=plan, cfg=cfg,
                           specs=specs, m=m)
    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=(P(pe_axes), P(pe_axes), P()),
        out_specs=(P(pe_axes), P(pe_axes), P()), check_vma=False)
    succ = jnp.arange(n, dtype=jnp.int32)
    rank = jnp.zeros(n, jnp.int32)
    return introspect.collective_counts(mapped, succ, rank, jnp.int32(0))


def test_batched_solve_collective_count_equals_single():
    """Acceptance criterion: packing B instances into one solve keeps
    the per-round collective count of the mesh program identical to a
    single-instance solve — batching costs volume, never startups."""
    mesh = mesh1()
    single = solver_collective_counts(256, mesh, CFG)
    batched = solver_collective_counts(4 * 256, mesh, CFG)  # B=4 packed
    assert batched == single
    assert single.get("all_to_all", 0) > 0
