"""The in-process large-p regression matrix (simshard backend).

What the subprocess 8-device matrices could never do: execute the
solver at p = 64 and 256 — the regimes where the tuner's decisions
(per-level r*, the Corollary-1 SRS-vs-doubling switch, capacity
derivations that scale with hop size) actually change — in ONE process,
against the sequential oracle, across instance families x wire formats
x algorithms.

Compile economy: all families of a (p, wire, algorithm) cell share one
jitted program — ``term_bound`` is pinned to the per-PE maximum so the
capacity specs (the jit key) are instance-independent.

The heavy cross-product tests carry the ``matrix`` marker (dedicated CI
job; deselect with ``-m "not matrix"`` for the fast lane).
"""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.listrank import (ListRankConfig, instances, introspect,
                                 rank_list_seq, rank_list_with_stats,
                                 sim_mesh, tuner)
from repro.core.listrank import api as api_lib
from repro.core.listrank.exchange import MeshPlan

P_SIZES = (8, 64, 256)
BASE = ListRankConfig(srs_rounds=1, local_contraction=False)


def _families(n: int):
    """All instance families at total size n (terminals self-looped)."""
    fams = {}
    fams["list-g1"] = instances.gen_list(n, gamma=1.0, seed=11)
    fams["random-lists"] = instances.gen_random_lists(
        n, num_lists=9, seed=12, weighted=True)
    for name, loc in (("gnm-tour", False), ("rgg2d-tour", True)):
        s, r, _ = instances.gen_euler_tour(n // 2 + 1, seed=13, locality=loc)
        fams[name] = instances.pad_to_multiple(s, r, n)[:2]
    return fams


@pytest.mark.matrix
@pytest.mark.parametrize("algorithm", ("srs", "doubling", "auto"))
@pytest.mark.parametrize("packed", (True, False), ids=("packed", "unpacked"))
@pytest.mark.parametrize("p", P_SIZES)
def test_large_p_matrix(p, packed, algorithm):
    n = max(512, 4 * p)
    cfg = BASE.with_(wire_packing=packed, algorithm=algorithm)
    mesh = sim_mesh(p)
    for fam, (succ, rank) in _families(n).items():
        s_ref, r_ref = rank_list_seq(succ, rank)
        s, r, stats = rank_list_with_stats(succ, rank, mesh, cfg=cfg,
                                           term_bound=n // p)
        assert np.array_equal(np.asarray(s), s_ref), (fam, p, stats)
        assert np.array_equal(np.asarray(r), r_ref), (fam, p, stats)


@pytest.mark.matrix
def test_cost_model_r_star_differs_at_large_p():
    """ruler_fraction=None must EXECUTE a different per-level r* at
    p=256 than at p=8 (tuner.level_plan through the live solve path,
    not just the unit-level derivation): r* grows with p, and at this n
    the p=256 plan saturates the 1/4 cap while p=8 stays below it."""
    n = 1 << 19
    cfg = BASE.with_(ruler_fraction=None)
    lp8 = tuner.level_plan(cfg, 8, 1, n)
    lp256 = tuner.level_plan(cfg, 256, 1, n)
    assert lp8[0].frac != lp256[0].frac
    assert lp8[0].r_total < lp256[0].r_total

    succ, rank = instances.gen_list(n, gamma=1.0, seed=21)
    s_ref, r_ref = rank_list_seq(succ, rank)
    fracs = {}
    for p, lp in ((8, lp8), (256, lp256)):
        mesh = sim_mesh(p)
        plan = MeshPlan.from_mesh(mesh, ("pe",))
        specs = api_lib.build_specs(cfg, plan, n // p, n, term_bound=1)
        # the spec the solve will run with carries the plan's fraction
        assert specs[0].ruler_frac == pytest.approx(lp[0].frac)
        s, r, stats = rank_list_with_stats(succ, rank, mesh, cfg=cfg,
                                           term_bound=1)
        assert np.array_equal(np.asarray(s), s_ref), (p, stats)
        assert np.array_equal(np.asarray(r), r_ref), (p, stats)
        fracs[p] = specs[0].ruler_frac
    assert fracs[8] != fracs[256]


@pytest.mark.parametrize("p", (8, 256))
@pytest.mark.parametrize("packed", (True, False), ids=("packed", "unpacked"))
def test_solver_collective_counts_mesh_vs_simshard(p, packed):
    """The simulated-collective markers keep the jaxpr pins meaningful:
    tracing the full solver program on an abstract p-device mesh and on
    the simshard backend yields IDENTICAL collective counts (trace
    only — no devices, no compile)."""
    import jax.numpy as jnp
    import functools
    from repro.core.listrank import transport as transport_lib

    n = 4 * p
    m = n // p
    cfg = BASE.with_(wire_packing=packed)

    am = jax.sharding.AbstractMesh((p,), ("pe",))
    plan_mesh = MeshPlan.from_mesh(am, ("pe",), None, wire_packing=packed)
    specs = api_lib.build_specs(cfg, plan_mesh, m, n, term_bound=m)
    spec = P(("pe",))
    fn = functools.partial(api_lib._solve_sharded, plan=plan_mesh, cfg=cfg,
                           specs=specs, m=m)
    mapped = jax.shard_map(fn, mesh=am, in_specs=(spec, spec, P()),
                           out_specs=(spec, spec, P()), check_vma=False)
    args = (jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32), jnp.int32(0))
    counts_mesh = introspect.collective_counts(mapped, *args)

    sm = sim_mesh(p)
    plan_sim = MeshPlan.from_mesh(sm, ("pe",), None, wire_packing=packed)
    fn_s = functools.partial(api_lib._solve_sharded, plan=plan_sim, cfg=cfg,
                             specs=specs, m=m)
    runner = transport_lib.device_run(sm, ("pe",), fn_s,
                                      in_specs=(spec, spec, P()),
                                      out_specs=(spec, spec, P()))
    counts_sim = introspect.collective_counts(runner, *args)
    assert counts_mesh == counts_sim
    assert counts_mesh.get("all_to_all", 0) > 0
