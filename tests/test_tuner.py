"""Tests for the §2.6 parameter-tuning subsystem (tuner.py) and its
wiring through build_specs / the retry driver.

Covers the ISSUE acceptance criteria: ruler_fraction=None demonstrably
routes through analysis.r_star, targeted retries rescale only the
offending capacity family (simulated per fatal stat, plus the forced
sub_overflow end-to-end check), auto-PD below the efficiency threshold,
and the build_specs consistency fixes (log p term in max_rounds,
r_target <= r_static by construction).
"""
import math

import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.core.listrank import (IndirectionSpec, ListRankConfig, analysis,
                                 instances, rank_list_seq,
                                 rank_list_with_stats, tuner)
from repro.core.listrank import api
from repro.core.listrank.exchange import MeshPlan


def mesh1():
    return make_mesh((1,), ("pe",))


def plan_of(p=16, axes=2):
    if axes == 2:
        side = int(math.isqrt(p))
        return MeshPlan(pe_axes=("row", "col"), axis_sizes=(side, p // side),
                        indirection=IndirectionSpec.grid(("row", "col")))
    return MeshPlan(pe_axes=("pe",), axis_sizes=(p,),
                    indirection=IndirectionSpec.direct(("pe",)))


#: a machine whose startup cost dominates — huge efficiency threshold.
ALPHA_HEAVY = analysis.MachineModel(alpha=1.0, beta=1e-12, name="alpha-heavy")
#: effectively free startups — threshold ~ 0, SRS always efficient.
BETA_HEAVY = analysis.MachineModel(alpha=1e-12, beta=1.0, name="beta-heavy")


# --------------------------------------------------------------------------
# targeted capacity retries
# --------------------------------------------------------------------------

@pytest.mark.parametrize("stat,family", [
    ("dropped", "chase"),
    ("sub_overflow", "sub"),
    ("undelivered", "gather"),
])
def test_escalate_rescales_only_the_offending_family(stat, family):
    scales = tuner.escalate(tuner.CapacityScales(), {stat: 3})
    for f in ("chase", "sub", "gather"):
        assert getattr(scales, f) == (2.0 if f == family else 1.0), (stat, f)


def test_escalate_store_miss_and_unknown_rescale_globally():
    for stats in ({"store_miss": 1}, {}):
        scales = tuner.escalate(tuner.CapacityScales(), stats)
        assert (scales.chase, scales.sub, scales.gather) == (2.0, 2.0, 2.0)


def test_escalate_compounds_geometrically():
    s = tuner.CapacityScales()
    s = tuner.escalate(s, {"sub_overflow": 1})
    s = tuner.escalate(s, {"sub_overflow": 1, "dropped": 2})
    assert (s.chase, s.sub, s.gather) == (2.0, 4.0, 1.0)


def test_escalate_widens_when_targeting_proved_insufficient():
    """`undelivered` is not capacity-exclusive (chase coverage failures
    report it too): once the implicated family has already been
    rescaled and the stat persists, the retry must widen globally
    instead of re-doubling the wrong capacity forever."""
    s = tuner.escalate(tuner.CapacityScales(), {"undelivered": 5})
    assert (s.chase, s.sub, s.gather) == (1.0, 1.0, 2.0)
    s = tuner.escalate(s, {"undelivered": 5})  # same failure again
    assert s.chase > 1.0 and s.sub > 1.0 and s.gather > 2.0


def test_escalate_exclusive_stats_stay_targeted_forever():
    """Capacity-exclusive stats (dropped, sub_overflow) re-double only
    their own family no matter how often they fire — the widening
    ladder applies exclusively to the ambiguous stats."""
    s = tuner.CapacityScales()
    for _ in range(3):
        s = tuner.escalate(s, {"sub_overflow": 1})
    assert (s.chase, s.sub, s.gather) == (1.0, 8.0, 1.0)
    for _ in range(2):
        s = tuner.escalate(s, {"dropped": 1})
    assert (s.chase, s.sub, s.gather) == (4.0, 8.0, 1.0)


def test_sub_overflow_rescale_leaves_mail_and_queue_caps_unchanged():
    """The ISSUE acceptance check, at the build_specs level: a
    sub_overflow retry must change only the sub-store capacities."""
    cfg = ListRankConfig(srs_rounds=2)
    plan = plan_of()
    base = api.build_specs(cfg, plan, 1 << 12, 1 << 16, 4)
    esc = api.build_specs(cfg, plan, 1 << 12, 1 << 16, 4,
                          tuner.escalate(tuner.CapacityScales(),
                                         {"sub_overflow": 7}))
    assert esc[0].mail_caps == base[0].mail_caps
    assert esc[0].queue_cap == base[0].queue_cap
    assert esc[0].gather_req_cap == base[0].gather_req_cap
    assert esc[0].cap_sub > base[0].cap_sub


def test_dropped_rescale_leaves_gather_and_sub_caps_unchanged():
    cfg = ListRankConfig(srs_rounds=1)
    plan = plan_of()
    base = api.build_specs(cfg, plan, 1 << 12, 1 << 16, 4)
    esc = api.build_specs(cfg, plan, 1 << 12, 1 << 16, 4,
                          tuner.escalate(tuner.CapacityScales(),
                                         {"dropped": 1}))
    assert esc[0].cap_sub == base[0].cap_sub
    assert esc[0].gather_req_cap == base[0].gather_req_cap
    assert esc[0].mail_caps > base[0].mail_caps or \
        esc[0].queue_cap > base[0].queue_cap


def test_forced_sub_overflow_retry_end_to_end(monkeypatch):
    """Force a sub_overflow on attempt 1 (tiny sub_capacity_slack) and
    assert the retry that fixes it kept the chase/gather capacities of
    the failing attempt whenever only sub_overflow fired."""
    recorded = []
    orig = api.build_specs

    def spy(cfg, plan, m, n, term_bound, scales=tuner.CapacityScales(),
            estimate=None):
        specs = orig(cfg, plan, m, n, term_bound, scales, estimate)
        sc = tuner.normalize_level_scales(scales, cfg.srs_rounds + 1)
        # the staged driver rebuilds specs once per executed stage;
        # record one entry per *distinct* scale vector (= per attempt).
        if not recorded or recorded[-1][0] != sc:
            recorded.append((sc, specs))
        return specs

    monkeypatch.setattr(api, "build_specs", spy)
    succ, rank = instances.gen_list(256, gamma=1.0, seed=2)
    cfg = ListRankConfig(srs_rounds=1, local_contraction=False,
                         sub_capacity_slack=0.05)
    s_ref, r_ref = rank_list_seq(succ, rank)
    s, r, stats = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg,
                                       max_retries=8)
    np.testing.assert_array_equal(np.asarray(s), s_ref)
    np.testing.assert_array_equal(np.asarray(r), r_ref)
    assert stats["attempts"] >= 2, "expected at least one forced retry"
    (first_scales, first_specs), (second_scales, second_specs) = recorded[:2]
    first_scales, second_scales = first_scales[0], second_scales[0]
    assert (first_scales.chase, first_scales.sub) == (1.0, 1.0)
    # the sub family was escalated, the chase family untouched
    assert second_scales.sub > 1.0
    assert second_scales.chase == 1.0
    assert second_specs[0].mail_caps == first_specs[0].mail_caps
    assert second_specs[0].queue_cap == first_specs[0].queue_cap
    assert second_specs[0].cap_sub > first_specs[0].cap_sub


# --------------------------------------------------------------------------
# ruler_fraction=None -> analysis.r_star
# --------------------------------------------------------------------------

def test_none_fraction_invokes_r_star(monkeypatch):
    calls = []
    orig = analysis.r_star

    def spy(n, p, d, m):
        calls.append((n, p, d))
        return orig(n, p, d, m)

    monkeypatch.setattr(analysis, "r_star", spy)
    cfg = ListRankConfig(ruler_fraction=None, srs_rounds=2)
    levels = tuner.level_plan(cfg, p=16, d=2, n=1 << 20)
    assert len(calls) == 2, "one r* derivation per level"
    assert calls[0][0] == 1 << 20
    # level 1 runs on the *expected* shrunken sub-instance
    assert calls[1][0] == levels[1].n_expected < (1 << 20)
    # fixed fraction must NOT consult the cost model
    calls.clear()
    tuner.level_plan(ListRankConfig(srs_rounds=2), p=16, d=2, n=1 << 20)
    assert calls == []


def test_none_fraction_differs_from_legacy_fallback():
    """The old silent 1/32 fallback is gone: with None the derived
    fraction is the cost model's, not 1/32."""
    cfg = ListRankConfig(ruler_fraction=None)
    levels = tuner.level_plan(cfg, p=16, d=2, n=1 << 20)
    assert levels[0].frac != pytest.approx(1.0 / 32.0)
    assert levels[0].r_total == min(
        max(analysis.r_star(1 << 20, 16, 2, cfg.machine),
            cfg.min_rulers_per_pe * 16),
        int(math.ceil(tuner.RULER_FRAC_CAP * (1 << 20))))


def test_build_specs_and_solver_share_one_derivation():
    """r_target can never exceed r_static: both come from the same
    LevelSpec.ruler_frac (spec carries the fraction the caps were sized
    for)."""
    for frac in (None, 1.0 / 32.0, 1.0 / 8.0):
        cfg = ListRankConfig(ruler_fraction=frac, srs_rounds=2)
        plan = plan_of()
        specs = api.build_specs(cfg, plan, 1 << 12, 1 << 16, 4)
        levels = tuner.level_plan(cfg, plan.p, plan.indirection.depth,
                                  1 << 16)
        for spec, lp in zip(specs[:-1], levels):
            assert spec.ruler_frac == lp.frac
            # dynamic target = min(max(floor, frac*n_active), r_static)
            # with n_active <= cap: frac*n_active <= frac*cap <= r_static
            assert int(spec.ruler_frac * spec.cap) <= spec.r_static


def test_none_fraction_end_to_end_bounds():
    """ruler_fraction=None end to end on a tiny mesh: the run succeeds
    and the level-0 ruler count lands in
    [min_rulers_per_pe * p, r_static * p]."""
    succ, rank = instances.gen_list(512, gamma=1.0, seed=9)
    cfg = ListRankConfig(ruler_fraction=None, srs_rounds=1,
                         local_contraction=False)
    s_ref, r_ref = rank_list_seq(succ, rank)
    s, r, stats = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg)
    np.testing.assert_array_equal(np.asarray(s), s_ref)
    np.testing.assert_array_equal(np.asarray(r), r_ref)
    plan = MeshPlan(pe_axes=("pe",), axis_sizes=(1,),
                    indirection=IndirectionSpec.direct(("pe",)))
    specs = api.build_specs(cfg, plan, 512, 512, 1)
    # "rulers" counts launched rulers (initial + restarts), each launch
    # bounded by r_static; at least the floor is always launched.
    assert stats["rulers"] >= cfg.min_rulers_per_pe
    assert stats["rulers"] <= specs[0].r_static * (1 + cfg.max_restarts)


# --------------------------------------------------------------------------
# algorithm / indirection selection
# --------------------------------------------------------------------------

def test_auto_algorithm_picks_pd_below_threshold():
    cfg = ListRankConfig(algorithm="auto", machine=ALPHA_HEAVY)
    assert tuner.choose_algorithm(cfg, p=16, d=2, m=1 << 10) == "doubling"
    cfg = ListRankConfig(algorithm="auto", machine=BETA_HEAVY)
    assert tuner.choose_algorithm(cfg, p=16, d=2, m=1 << 10) == "srs"
    # explicit algorithms pass through untouched
    assert tuner.choose_algorithm(ListRankConfig(algorithm="srs",
                                                 machine=ALPHA_HEAVY),
                                  16, 2, 1) == "srs"


def test_auto_algorithm_end_to_end():
    """Below the Corollary-1 regime the solver must run pointer
    doubling: zero chase rounds, pd rounds > 0 — and stay correct."""
    succ, rank = instances.gen_list(256, gamma=1.0, seed=4)
    s_ref, r_ref = rank_list_seq(succ, rank)
    cfg = ListRankConfig(algorithm="auto", machine=ALPHA_HEAVY,
                         local_contraction=False)
    s, r, stats = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg)
    np.testing.assert_array_equal(np.asarray(s), s_ref)
    np.testing.assert_array_equal(np.asarray(r), r_ref)
    assert stats["rounds"] == 0 and stats["pd_rounds"] > 0


def test_choose_indirection_follows_the_model():
    # startup-dominated machine: indirection amortizes p startups
    cfg = ListRankConfig(machine=ALPHA_HEAVY)
    spec = tuner.choose_indirection(cfg, ("row", "col"), (64, 64), 1 << 22)
    assert spec.depth == 2
    # volume-dominated machine: direct delivery avoids the 2x volume
    cfg = ListRankConfig(machine=BETA_HEAVY)
    spec = tuner.choose_indirection(cfg, ("row", "col"), (64, 64), 1 << 22)
    assert spec.depth == 1
    # a 1-axis mesh only admits direct delivery
    cfg = ListRankConfig(machine=ALPHA_HEAVY)
    spec = tuner.choose_indirection(cfg, ("pe",), (256,), 1 << 22)
    assert spec.hops == (("pe",),)


def test_candidates_exclude_size1_axes_from_hops():
    """A hop over a one-PE group is a real collective that moves
    nothing — size-1 axes must not appear in grid/topology hops nor be
    picked as the intra-node axis."""
    cands = dict((name, (spec, intra)) for name, spec, intra in
                 tuner.candidate_indirections(("a", "b", "c"), (4, 4, 1)))
    assert cands["grid"][0].hops == (("b",), ("a",))
    assert cands["topology"][1] == ("b",)
    # all axes size 1 except one -> direct only
    only = tuner.candidate_indirections(("a", "b"), (1, 8))
    assert [name for name, _, _ in only] == ["direct"]


def test_auto_indirection_end_to_end():
    succ, rank = instances.gen_list(256, gamma=1.0, seed=6)
    s_ref, r_ref = rank_list_seq(succ, rank)
    cfg = ListRankConfig(auto_indirection=True, srs_rounds=1,
                         local_contraction=False)
    s, r, _ = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg)
    np.testing.assert_array_equal(np.asarray(s), s_ref)
    np.testing.assert_array_equal(np.asarray(r), r_ref)


# --------------------------------------------------------------------------
# build_specs consistency (satellite: p used, log p in max_rounds)
# --------------------------------------------------------------------------

def test_build_specs_max_rounds_has_log_p_term():
    cfg = ListRankConfig(srs_rounds=1)
    small = api.build_specs(cfg, plan_of(p=4), 1 << 12, 1 << 14, 4)
    big = api.build_specs(cfg, plan_of(p=1024), 1 << 12, 1 << 22, 4)
    assert big[0].max_rounds > small[0].max_rounds
    expect = int(cfg.max_round_slack * (32.0 + math.log2(1024)) + 256)
    assert big[0].max_rounds == expect


def test_build_specs_consistency():
    cfg = ListRankConfig(srs_rounds=2, ruler_fraction=None)
    plan = plan_of(p=16)
    m, n = 1 << 12, 1 << 16
    specs = api.build_specs(cfg, plan, m, n, term_bound=4)
    assert len(specs) == cfg.srs_rounds + 1
    assert specs[-1].base and not any(s.base for s in specs[:-1])
    cap = m
    for s in specs[:-1]:
        assert s.cap == cap
        assert s.r_static >= cfg.min_rulers_per_pe
        assert 0.0 < s.ruler_frac <= 1.0
        assert s.cap_sub <= s.cap
        assert all(c >= cfg.min_capacity for c in s.mail_caps)
        assert len(s.mail_caps) == plan.indirection.depth
        assert s.queue_cap >= 2 * sum(
            plan.hop_size(h) * c
            for h, c in zip(plan.indirection.hops, s.mail_caps))
        assert s.max_restarts == cfg.max_restarts
        cap = s.cap_sub
    assert specs[-1].cap == cap
    assert specs[-1].max_rounds >= int(math.log2(n))


def test_max_restarts_threads_into_levelspec():
    cfg = ListRankConfig(max_restarts=7)
    specs = api.build_specs(cfg, plan_of(), 1 << 10, 1 << 14, 4)
    assert all(s.max_restarts == 7 for s in specs)
