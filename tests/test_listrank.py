"""List-ranking correctness on a single-device mesh (full code path —
routing, spawning, recursion, contraction — with p=1 self-sends) plus
hypothesis property tests. Multi-PE runs live in test_listrank_multi."""
import jax
import numpy as np
import pytest
from _hypothesis_compat import HealthCheck, given, settings, st

from repro.launch.mesh import make_mesh
from repro.core.listrank import (IndirectionSpec, ListRankConfig, analysis,
                                 instances, rank_list_seq,
                                 rank_list_with_stats)


def mesh1():
    return make_mesh((1,), ("pe",))


def run_and_check(succ, rank, cfg, **kw):
    s_ref, r_ref = rank_list_seq(succ, rank)
    s, r, stats = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg, **kw)
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
    run_and_check(succ, rank, VARIANTS[variant])


@pytest.mark.parametrize("gamma", [0.0, 0.3, 1.0])
def test_locality_instances(gamma):
    succ, rank = instances.gen_list(512, gamma=gamma, seed=5)
    run_and_check(succ, rank, BASE.with_(local_contraction=True))


def test_multilist_and_weighted():
    succ, rank = instances.gen_random_lists(512, num_lists=9, seed=7,
                                            weighted=True)
    stats = run_and_check(succ, rank, BASE.with_(srs_rounds=2,
                                                 local_contraction=True))
    assert stats["dropped"] == 0


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
        s_p, r_p, _ = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg)
        s_u, r_u, _ = rank_list_with_stats(
            succ, rank, mesh1(), cfg=cfg.with_(wire_packing=False))
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
    nl = min(nl, n)
    succ, rank = instances.gen_random_lists(n, num_lists=nl, seed=seed)
    cfg = BASE.with_(srs_rounds=srs_rounds, local_contraction=contract,
                     avoid_reversal=avoid_rev)
    run_and_check(succ, rank, cfg)


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


@pytest.mark.parametrize("cfg", [
    ListRankConfig(),
    ListRankConfig(local_contraction=False),
    ListRankConfig(algorithm="doubling"),
], ids=["default", "chase", "doubling"])
def test_lower_stages_lowers_the_solve_programs(cfg, monkeypatch):
    """lower_stages, from shapes alone, lowers the very programs (and
    argument placements) that a first-attempt solve runs."""
    from repro.core.listrank import api, resume

    n = 1 << 10
    succ, rank = instances.gen_list(n, gamma=1.0, seed=3)
    ran = []
    stage_call = resume._stage_call

    def spy(*a):
        runner, args = stage_call(*a)
        ran.append(runner.lower(*args).as_text())
        return runner, args

    monkeypatch.setattr(resume, "_stage_call", spy)
    run_and_check(succ, rank, cfg)
    monkeypatch.undo()
    lowered = api.lower_stages(n, mesh1(), cfg=cfg)
    assert [lw.as_text() for _, lw in lowered] == ran


def test_lower_stages_refuses_capacity_estimation():
    from repro.core.listrank import api

    with pytest.raises(ValueError, match="capacity_estimation"):
        api.lower_stages(1 << 10, mesh1(),
                         cfg=ListRankConfig(capacity_estimation=True))
