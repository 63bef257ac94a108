"""List-ranking correctness on a single-device mesh (full code path —
routing, spawning, recursion, contraction — with p=1 self-sends) plus
hypothesis property tests. On one PE local contraction leaves nothing
for the recursion (the driver skips it), so configurations with
contraction run on four virtual PEs, where the recursion still runs.
Multi-PE runs on devices live in test_listrank_multi."""
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest
from _hypothesis_compat import HealthCheck, given, settings, st

from repro.launch.mesh import make_mesh
from repro.core.listrank import (IndirectionSpec, ListRankConfig, analysis,
                                 instances, rank_list_seq,
                                 rank_list_with_stats, sim_mesh)
from repro.core.listrank import transport


def mesh1():
    return make_mesh((1,), ("pe",))


def mesh_for(cfg):
    """Where ``cfg`` reaches the recursion: four virtual PEs with local
    contraction, one device without. The Pallas kernels need a device
    mesh, so a Pallas contraction stays on one PE and skips."""
    if cfg.local_contraction and not cfg.use_pallas:
        return sim_mesh(4)
    return mesh1()


def run_and_check(succ, rank, cfg, mesh=None, **kw):
    s_ref, r_ref = rank_list_seq(succ, rank)
    s, r, stats = rank_list_with_stats(succ, rank, mesh or mesh1(), cfg=cfg,
                                       **kw)
    np.testing.assert_array_equal(np.asarray(s), s_ref)
    np.testing.assert_array_equal(np.asarray(r), r_ref)
    return stats


BASE = ListRankConfig(srs_rounds=1, local_contraction=False)
VARIANTS = {
    "srs1": BASE,
    "srs2": BASE.with_(srs_rounds=2),
    "srs1_contract": BASE.with_(local_contraction=True),
    "srs2_contract": BASE.with_(srs_rounds=2, local_contraction=True),
    "reversal": BASE.with_(avoid_reversal=False),
    "doubling": BASE.with_(algorithm="doubling"),
    "doubling_contract": BASE.with_(algorithm="doubling",
                                    local_contraction=True),
    "allgather_base": BASE.with_(base_case="allgather"),
    "nodedup": BASE.with_(dedup_requests=False),
    "pallas_contract": BASE.with_(local_contraction=True, use_pallas=True),
    "unpacked": BASE.with_(wire_packing=False),
    "unpacked_srs2": BASE.with_(srs_rounds=2, local_contraction=True,
                                wire_packing=False),
    "pallas_pack": BASE.with_(use_pallas_pack=True),
    "auto_tuned": BASE.with_(ruler_fraction=None),
    "auto_tuned_srs2": BASE.with_(ruler_fraction=None, srs_rounds=2,
                                  local_contraction=True),
}


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_variants_random_list(variant):
    succ, rank = instances.gen_list(256, gamma=1.0, seed=3)
    cfg = VARIANTS[variant]
    mesh = mesh_for(cfg)
    stats = run_and_check(succ, rank, cfg, mesh)
    one_pe = not transport.is_sim(mesh)
    assert stats["recursion_skipped"] == (cfg.local_contraction and one_pe)


@pytest.mark.parametrize("gamma", [0.0, 0.3, 1.0])
def test_locality_instances(gamma):
    succ, rank = instances.gen_list(512, gamma=gamma, seed=5)
    stats = run_and_check(succ, rank, BASE.with_(local_contraction=True),
                          sim_mesh(4))
    assert stats["recursion_skipped"] == 0


def test_multilist_and_weighted():
    succ, rank = instances.gen_random_lists(512, num_lists=9, seed=7,
                                            weighted=True)
    stats = run_and_check(succ, rank, BASE.with_(srs_rounds=2,
                                                 local_contraction=True),
                          sim_mesh(4))
    assert stats["dropped"] == 0
    assert stats["recursion_skipped"] == 0


def test_euler_tour_instance():
    succ, rank, arcs = instances.gen_euler_tour(200, seed=11, locality=True)
    succ, rank = instances.pad_to_multiple(succ, rank, 1)
    run_and_check(succ, rank, BASE.with_(local_contraction=True))


def test_float_weights():
    rng = np.random.default_rng(0)
    succ, _ = instances.gen_random_lists(128, num_lists=4, seed=13)
    w = rng.uniform(0.0, 2.0, 128).astype(np.float32)
    w[succ == np.arange(128)] = 0.0
    s_ref, r_ref = rank_list_seq(succ, w)
    s, r, _ = rank_list_with_stats(succ, w, mesh1(), cfg=BASE)
    np.testing.assert_array_equal(np.asarray(s), s_ref)
    np.testing.assert_allclose(np.asarray(r), r_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("make", [
    lambda: instances.gen_list(256, gamma=1.0, seed=3),
    lambda: instances.gen_list(256, gamma=0.0, seed=4),
    lambda: instances.gen_random_lists(256, num_lists=7, seed=5,
                                       weighted=True),
])
def test_packed_unpacked_bit_identical(make):
    """The packed wire format must be a pure transport change: identical
    output bits to the unpacked exchange, on every instance."""
    succ, rank = make()
    for cfg in (BASE, BASE.with_(srs_rounds=2, local_contraction=True)):
        mesh = mesh_for(cfg)
        s_p, r_p, st_p = rank_list_with_stats(succ, rank, mesh, cfg=cfg)
        s_u, r_u, _ = rank_list_with_stats(
            succ, rank, mesh, cfg=cfg.with_(wire_packing=False))
        assert st_p["recursion_skipped"] == 0
        np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_u))
        np.testing.assert_array_equal(
            np.asarray(r_p).view(np.int32), np.asarray(r_u).view(np.int32))


def test_singletons_only():
    n = 64
    succ = np.arange(n, dtype=np.int32)
    rank = np.zeros(n, np.int32)
    s, r, _ = rank_list_with_stats(succ, rank, mesh1(), cfg=BASE)
    np.testing.assert_array_equal(np.asarray(s), succ)
    np.testing.assert_array_equal(np.asarray(r), rank)


# --------------------------------------------------------------------- props
@settings(max_examples=20, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(n=st.integers(8, 200), nl=st.integers(1, 8), seed=st.integers(0, 999),
       srs_rounds=st.integers(1, 2), contract=st.booleans(),
       avoid_rev=st.booleans())
def test_property_random_forests(n, nl, seed, srs_rounds, contract,
                                 avoid_rev):
    cfg = BASE.with_(srs_rounds=srs_rounds, local_contraction=contract,
                     avoid_reversal=avoid_rev)
    mesh = mesh_for(cfg)
    n -= n % mesh.size              # whole blocks per PE
    nl = min(nl, n)
    succ, rank = instances.gen_random_lists(n, num_lists=nl, seed=seed)
    run_and_check(succ, rank, cfg, mesh)


@settings(max_examples=10, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(n=st.integers(16, 128), gamma=st.floats(0.0, 1.0),
       seed=st.integers(0, 99))
def test_property_rank_is_permutation_distance(n, gamma, seed):
    """Invariant: on a single full list, the multiset of ranks is
    exactly {0..n-1} and succ is constant (the terminal)."""
    succ, rank = instances.gen_list(n, gamma=gamma, seed=seed)
    s, r, _ = rank_list_with_stats(succ, rank, mesh1(),
                                   cfg=BASE.with_(local_contraction=True))
    r = np.sort(np.asarray(r))
    np.testing.assert_array_equal(r, np.arange(n))
    assert len(np.unique(np.asarray(s))) == 1


def test_cost_model_sanity():
    m = analysis.SUPERMUC
    r = analysis.r_star(1 << 24, 1024, 2, m)
    assert 1024 <= r < (1 << 24)
    t_opt = analysis.t_model(1 << 24, 1024, r, 2, m)
    t_bad = analysis.t_model(1 << 24, 1024, 64 * r, 2, m)
    assert t_opt <= t_bad
    assert analysis.expected_rounds(1 << 20, 1 << 10) == pytest.approx(1025.0)


def test_retry_on_tiny_capacity():
    """Pathologically small capacities must retry, not fail/corrupt."""
    succ, rank = instances.gen_list(128, gamma=1.0, seed=1)
    cfg = BASE.with_(capacity_slack=0.1, min_capacity=1, queue_slack=1.0,
                     sub_capacity_slack=0.5)
    s_ref, r_ref = rank_list_seq(succ, rank)
    s, r, stats = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg,
                                       max_retries=6)
    np.testing.assert_array_equal(np.asarray(s), s_ref)
    np.testing.assert_array_equal(np.asarray(r), r_ref)


def check_lowering(cfg, mesh):
    """lower_stages, from shapes alone, lowers the very programs (and
    argument placements and pytrees: jit drops an unused leaf from the
    text) that a first-attempt solve of a random list on ``mesh`` runs:
    every stage where the recursion runs, prep and post where the
    driver skips it."""
    from repro.core.listrank import api, resume

    n = 1 << 10
    succ, rank = instances.gen_list(n, gamma=1.0, seed=3)
    ran = []
    stage_call = resume._stage_call

    def spy(*a):
        runner, args = stage_call(*a)
        lw = runner.lower(*args)
        ran.append((lw.in_tree, lw.as_text()))
        return runner, args

    resume._stage_call = spy
    try:
        stats = run_and_check(succ, rank, cfg, mesh)
    finally:
        resume._stage_call = stage_call
    lowered = api.lower_stages(n, mesh, cfg=cfg)
    labels = [st.label for st in resume.schedule_for(cfg)]
    assert [lbl for lbl, _ in lowered] == labels
    program = {lbl: (lw.in_tree, lw.as_text()) for lbl, lw in lowered}
    if stats["recursion_skipped"]:
        assert stats["stage_log"] == ("prep", "post")
        assert [program["prep"], program["post"]] == ran
    else:
        assert list(stats["stage_log"]) == labels
        assert [program[lbl] for lbl in labels] == ran
    return stats


@pytest.mark.parametrize("kw,pes", [
    ({}, 1),
    ({"local_contraction": False}, 1),
    ({"algorithm": "doubling"}, 1),
    ({"algorithm": "doubling", "local_contraction": False}, 1),
    ({"srs_rounds": 0, "local_contraction": False}, 1),
    ({}, 4),
], ids=["default", "chase", "doubling", "doubling_chase", "srs0_chase",
        "default_4pe"])
def test_lower_stages_lowers_the_solve_programs(kw, pes):
    """One PE with contraction skips the recursion; without contraction,
    and with contraction on four devices (a subprocess, since the device
    count is fixed before jax starts), every stage is compared."""
    cfg = ListRankConfig(**kw)
    if pes == 1:
        stats = check_lowering(cfg, mesh1())
        assert stats["recursion_skipped"] == cfg.local_contraction
        return
    here = pathlib.Path(__file__).parent
    code = (f"import sys; sys.path[:0] = [{str(here.parent / 'src')!r}, "
            f"{str(here)!r}]\n"
            "from test_listrank import ListRankConfig, check_lowering, "
            "make_mesh\n"
            f"stats = check_lowering(ListRankConfig(**{kw!r}), "
            f"make_mesh(({pes},), ('pe',)))\n"
            "assert stats['recursion_skipped'] == 0\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={pes}")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]


def test_lower_stages_refuses_capacity_estimation():
    from repro.core.listrank import api

    with pytest.raises(ValueError, match="capacity_estimation"):
        api.lower_stages(1 << 10, mesh1(),
                         cfg=ListRankConfig(capacity_estimation=True))
