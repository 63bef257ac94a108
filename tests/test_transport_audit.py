"""Transport-layer audit: nothing may bypass the Transport abstraction.

The simshard backend only emulates collectives that go through
``MeshPlan``'s transport delegates; a raw ``lax.psum(.., axis_names)``
buried in an algorithm module would trace fine on a mesh and still work
under vmap TODAY — but it would dodge the simulated-collective markers
(silently corrupting every collective-count pin) and any future
transport (e.g. a ppermute-based torus backend). The audit found these
bypass sites when the abstraction was introduced: ``api.py`` (restore/
reversal miss counts, stats reduction), ``srs.py`` (chase/gather
convergence psums), ``doubling.py`` (pending psum + the 4-array
all-gather base case), ``treealg/euler.py`` (tour stats),
``graphalg/cc.py`` (hooking loop) and ``graphalg/frontdoor.py``
(pipeline stats). Each gets (a) a static source scan proving it stays
fixed and (b) an executing simshard regression through that exact path.
"""
import pathlib
import re

import numpy as np
import pytest

from repro.core.listrank import (ListRankConfig, IndirectionSpec, instances,
                                 rank_list_seq, rank_list_with_stats,
                                 sim_mesh)

SRC = pathlib.Path(__file__).parent.parent / "src" / "repro" / "core"

#: the only module allowed to touch lax collectives (the backends live
#: there); everything else must go through plan.psum/all_to_all/...
ALLOWED = {"listrank/transport.py"}

_COLLECTIVE_RE = re.compile(
    r"lax\s*\.\s*(psum|all_to_all|all_gather|axis_index|ppermute|pmax|pmin"
    r"|reduce_scatter)\s*\(")

CFG = ListRankConfig(srs_rounds=1, local_contraction=True)


def _scan_for_collectives(root: pathlib.Path, allowed: set) -> list[str]:
    offenders = []
    for f in sorted(root.rglob("*.py")):
        rel = f.relative_to(root).as_posix()
        if rel in allowed:
            continue
        for i, line in enumerate(f.read_text().splitlines(), 1):
            if _COLLECTIVE_RE.search(line.split("#")[0]):
                offenders.append(f"{rel}:{i}: {line.strip()}")
    return offenders


def test_no_collective_bypasses_in_core():
    """Static scan: no raw lax collective calls outside transport.py."""
    assert _scan_for_collectives(SRC, ALLOWED) == []


def test_no_collectives_in_obs_layer():
    """The observability/telemetry layer is host code plus per-PE jnp
    arithmetic: zero lax collectives anywhere under src/repro/obs, so
    the telemetry plane cannot add collectives to any traced program
    (the zero-added-collectives rule, pinned live below)."""
    assert _scan_for_collectives(SRC.parent / "obs", set()) == []


@pytest.mark.telemetry
@pytest.mark.parametrize("p", (8, 256))
def test_stage_collective_counts_identical_telemetry_on_off(p):
    """cfg.telemetry=True compiles a different program (extra per-PE
    outputs) but may not add a single collective: the per-stage traced
    collective counts, solve output bytes, and integer counters are
    identical to the telemetry-off run at small and large p."""
    n = 8 * p
    s, r = instances.gen_list(n, gamma=1.0, seed=9)
    cfg = ListRankConfig(srs_rounds=1, local_contraction=True)
    out = {}
    for tag, c in (("off", cfg), ("on", cfg.with_(telemetry=True))):
        sf, rf, stats = rank_list_with_stats(
            s, r, sim_mesh(p), cfg=c, seed=1, stage_counters=True,
            term_bound=1)
        stats.pop("telemetry", None)
        out[tag] = (np.asarray(sf).tobytes(), np.asarray(rf).tobytes(),
                    stats["stage_collectives"],
                    {k: v for k, v in stats.items() if isinstance(v, int)})
    assert out["on"] == out["off"]
    assert any(dict(c).get("all_to_all", 0) > 0
               for _, c in out["on"][2])


def _solve_and_check(succ, rank, mesh, cfg, **kw):
    s_ref, r_ref = rank_list_seq(succ, rank)
    s, r, stats = rank_list_with_stats(succ, rank, mesh, cfg=cfg, **kw)
    assert np.array_equal(np.asarray(s), s_ref), stats
    assert np.array_equal(np.asarray(r), r_ref), stats
    return stats


def test_api_restore_psums_simshard():
    """api._restore_local miss counts (former lax.psum x2) — the local-
    contraction restore path must converge under simshard."""
    succ, rank = instances.gen_list(512, gamma=0.5, seed=31)
    st = _solve_and_check(succ, rank, sim_mesh(16), CFG)
    assert st["undelivered"] == 0


def test_api_reversal_psums_simshard():
    """_reverse_instance / route_until_done pendings (former lax.psum)
    — the faithful Algorithm-1 reversal preprocessing under simshard."""
    succ, rank = instances.gen_list(512, gamma=1.0, seed=32)
    _solve_and_check(succ, rank, sim_mesh(16),
                     CFG.with_(avoid_reversal=False))


def test_api_reversal_on_tours_simshard():
    """Faithful Algorithm-1 reversal on Euler-tour instances — both
    tree models, a ±1-weighted forest, and a grid-indirection variant
    (the coverage the deleted subprocess matrix carried since PR 3,
    now in-process)."""
    rev = CFG.with_(avoid_reversal=False)
    cases = [
        (dict(seed=41, locality=False), rev, sim_mesh(8), None),
        (dict(seed=42, locality=True, weighted=True, num_trees=5),
         rev.with_(srs_rounds=2), sim_mesh(8), None),
        (dict(seed=43, locality=False), rev,
         sim_mesh((2, 4), ("row", "col")),
         IndirectionSpec.grid(("row", "col"))),
    ]
    for kw, cfg, mesh, ind in cases:
        s, r, _ = instances.gen_euler_tour(257, **kw)
        s, r = instances.pad_to_multiple(s, r, 8)
        _solve_and_check(s, r, mesh, cfg, indirection=ind)


def test_srs_grid_indirection_psums_simshard():
    """srs chase/gather convergence psums over a 2-hop grid plan on a
    2D virtual mesh (single-axis hops of a multi-axis axis set)."""
    succ, rank = instances.gen_list(512, gamma=1.0, seed=33)
    _solve_and_check(succ, rank, sim_mesh((4, 8), ("row", "col")), CFG,
                     indirection=IndirectionSpec.grid(("row", "col")))


def test_srs_topology_indirection_simshard():
    """Topology-aware indirection through the FULL solver (intra-node
    hop first): the end-to-end coverage the deleted subprocess matrix's
    'srs1 topo' case carried, now on the virtual mesh."""
    succ, rank = instances.gen_list(512, gamma=1.0, seed=44)
    _solve_and_check(succ, rank, sim_mesh((4, 8), ("row", "col")), CFG,
                     indirection=IndirectionSpec.topology(("col",),
                                                          ("row",)))


def test_doubling_allgather_base_simshard():
    """doubling.allgather_solve (former 4x lax.all_gather over the
    tuple of PE axes — the one collective whose vmap batching rule
    rejects multi-axis gathers outright, decomposed inside the
    simshard_all_gather marker)."""
    succ, rank = instances.gen_random_lists(512, num_lists=5, seed=34,
                                            weighted=True)
    _solve_and_check(succ, rank, sim_mesh((2, 8), ("row", "col")),
                     CFG.with_(base_case="allgather"))


def test_doubling_pending_psum_simshard():
    succ, rank = instances.gen_list(512, gamma=1.0, seed=35)
    _solve_and_check(succ, rank, sim_mesh(32),
                     CFG.with_(algorithm="doubling"))


def test_euler_tour_stats_psums_simshard():
    """treealg.euler tour stats (former lax.psum x2): device tour
    construction on a virtual mesh matches the host oracle."""
    import jax
    from repro.core import treealg
    parent = instances.gen_tree_parents(301, seed=36, locality=True,
                                        num_trees=3)
    succ, w, _ = treealg.build_tour(parent, sim_mesh(16), cfg=CFG)
    got = np.asarray(jax.device_get(succ))[:2 * 301]
    want = treealg.oracle_tour(301, parent).astype(np.int32)
    np.testing.assert_array_equal(got, want)


def test_graphalg_cc_psums_simshard():
    """graphalg.cc hooking-loop psums (former lax.psum x5) + frontdoor
    pipeline stats: components and stats on a virtual mesh."""
    from _graph_oracles import union_find_labels
    from repro.core import graphalg
    edges = instances.gen_graph_edges(200, 300, seed=37, num_components=4)
    labels, st = graphalg.connected_components(edges, 200, sim_mesh(16),
                                               cfg=CFG)
    np.testing.assert_array_equal(labels, union_find_labels(200, edges))
    assert st["cc_unconverged"] == 0


def test_simshard_rejects_pallas_kernels():
    """The batched trace can't honor the Pallas kernels; the front door
    must fail loudly, not corrupt results."""
    succ, rank = instances.gen_list(64, gamma=0.0, seed=38)
    for bad in (CFG.with_(use_pallas=True), CFG.with_(use_pallas_pack=True)):
        with pytest.raises(ValueError, match="Pallas"):
            rank_list_with_stats(succ, rank, sim_mesh(8), cfg=bad)


def test_mesh_backend_rejects_sim_mesh():
    succ, rank = instances.gen_list(64, gamma=0.0, seed=39)
    with pytest.raises(ValueError, match="real device mesh"):
        rank_list_with_stats(succ, rank, sim_mesh(8),
                             cfg=CFG.with_(backend="mesh"))


def test_forced_simshard_on_real_mesh():
    """backend='simshard' with a real mesh: same axis names/sizes, no
    device placement — the escape hatch for large-p runs on any host."""
    from repro.launch.mesh import make_mesh
    succ, rank = instances.gen_list(128, gamma=1.0, seed=40)
    mesh = make_mesh((1,), ("pe",))
    st = _solve_and_check(succ, rank, mesh, CFG.with_(backend="simshard"))
    assert st["attempts"] >= 1
