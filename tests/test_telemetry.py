"""Telemetry-plane suite (`-m telemetry` fast lane).

Pins the two contracts DESIGN.md §13 promises:

1. **telemetry=False changes nothing** — the committed golden byte
   records (output hashes, attempts, scales_log, every integer counter)
   are reproduced by telemetry-ON solves after popping the telemetry
   key, i.e. the flag only *adds* outputs, it never perturbs the solve
   (the traced-collective-count pin lives in test_transport_audit.py);
2. **telemetry=True explains the run** — every scheduled stage of
   every paper family reports finite utilization, headroom rows stay
   within compiled caps on first-attempt-clean solves, escalations are
   cross-referenced in scales terms, and the host-half algebra (merge,
   aggregate, DKW back-test, skew table) is exact on synthetic input.
"""
import json

import numpy as np
import pytest

from _simshard_cases import AXES, SHAPE, case_record, golden_cases, load_golden
from repro.core.listrank import (ListRankConfig, instances,
                                 rank_list_with_stats, sim_mesh)
from repro.core.listrank import resume as resume_lib
from repro import obs
from repro.obs import cost as cost_lib
from repro.obs import telemetry as tele_lib

pytestmark = pytest.mark.telemetry


# --------------------------------------------------------------------------
# host-half algebra on synthetic records
# --------------------------------------------------------------------------

def test_merge_semantics():
    """MAX_KEYS leaves merge by max, everything else adds; None is the
    identity; keys are unioned (partial increments merge into a full
    stage_zero record)."""
    a = {"fill_max": np.float32(0.25), "rounds": np.int32(2),
         "sub": {"queue_hwm": np.int32(3)}}
    b = {"fill_max": np.float32(0.75), "rounds": np.int32(1),
         "hist": np.int32(7)}
    m = tele_lib.merge(a, b)
    assert float(m["fill_max"]) == 0.75          # max
    assert int(m["rounds"]) == 3                 # additive
    assert int(m["hist"]) == 7                   # union from b
    assert int(m["sub"]["queue_hwm"]) == 3       # union from a
    assert tele_lib.merge(None, a) is a
    assert tele_lib.merge(a, None) is a
    # merge(zero, x) == x for the canonical stage record shape
    z = tele_lib.stage_zero(2)
    w = tele_lib.merge(z, tele_lib.stage_zero(2))
    assert int(w["queue_hwm"]) == 0
    assert set(w) == set(z)


def test_stage_zero_shapes():
    tele = tele_lib.stage_zero(3)
    assert set(tele) == set(tele_lib.STAGE_FAMILIES) | {"queue_hwm"}
    for fam in tele_lib.STAGE_FAMILIES:
        rec = tele[fam]
        assert rec["fill_max"].shape == (3,)
        assert rec["hist"].shape == (tele_lib.HIST_BINS,)


def test_utilization_always_finite():
    """A stage that routed nothing reports zeros, never NaN/inf."""
    zero = tele_lib.json_tele(tele_lib.stage_zero(2))
    util = tele_lib.utilization(zero)
    assert util == {"util_max": 0.0, "util_mean": 0.0}
    busy = dict(zero)
    busy["chase"] = dict(zero["chase"], fill_max=[0.5, 1.25],
                         fill_mean_sum=[0.4, 0.8], rounds=2)
    util = tele_lib.utilization(busy)
    assert util["util_max"] == 1.25
    assert util["util_mean"] == pytest.approx((0.4 + 0.8) / 4)


def test_stage_record_roundtrip_and_headroom():
    tele = tele_lib.json_tele(tele_lib.stage_zero(1))
    tele["gather"] = dict(tele["gather"], fill_max=[0.5],
                          dest_frac_max=[0.2], rounds=3)
    tele["queue_hwm"] = 6
    rec = tele_lib.StageRecord(label="descend@0", kind="descend", level=0,
                               caps={"gather": (16,)}, queue_cap=24,
                               tele=tele)
    back = tele_lib.StageRecord.from_json(json.loads(
        json.dumps(rec.to_json())))
    assert (back.label, back.level, back.caps, back.queue_cap) == \
        ("descend@0", 0, {"gather": (16,)}, 24)
    rows = tele_lib.headroom_rows([rec], final_scales="chase=1,gather=2")
    by_fam = {r["family"]: r for r in rows}
    # families with rounds==0 are skipped; queue HWM gets its own row
    assert set(by_fam) == {"gather", "queue"}
    g = by_fam["gather"]
    assert (g["cap"], g["fill_max"], g["scale"]) == (16, 0.5, 2.0)
    assert g["headroom"] == pytest.approx(0.5)
    q = by_fam["queue"]
    assert (q["cap"], q["fill_max"]) == (24, 6 / 24)
    table = tele_lib.format_headroom_table(rows)
    assert "worst fill 0.500 of cap 16" in table
    assert tele_lib.format_headroom_table([]).startswith("(no telemetry")


def test_parse_scales():
    assert tele_lib.parse_scales("chase=1,sub=2,gather=1.5,graph=1") == \
        {"chase": 1.0, "sub": 2.0, "gather": 1.5, "graph": 1.0}
    # scales_log joins attempts with ";" — last occurrence wins
    assert tele_lib.parse_scales("chase=1,sub=1;chase=2,sub=1")["chase"] == 2.0
    assert tele_lib.parse_scales("") == {}


def test_dkw_backtest_synthetic():
    """Observed skew under the sampled bound -> ok; above it -> flagged."""
    tele = tele_lib.json_tele(tele_lib.stage_zero(2))
    tele["chase"] = dict(tele["chase"], dest_frac_max=[0.1, 0.9], rounds=1)
    rec = tele_lib.StageRecord(label="s", kind="descend", level=0,
                               caps={"chase": (8, 8)}, queue_cap=0,
                               tele=tele)
    rows = tele_lib.dkw_backtest([0.15, 0.15], sample_size=1024,
                                 hop_sizes=[8, 8], records=[rec])
    assert [r["hop"] for r in rows] == [0, 1]
    margin = tele_lib.dkw_margin(1024, 8)
    assert rows[0]["bound"] == pytest.approx(0.15 + margin)
    assert rows[0]["ok"] and not rows[1]["ok"]
    assert rows[1]["observed_frac"] == pytest.approx(0.9)


def test_skew_rows_against_uniform_model():
    tele = tele_lib.json_tele(tele_lib.stage_zero(1))
    tele["gather"] = dict(tele["gather"], dest_frac_max=[0.5], rounds=1)
    rec = tele_lib.StageRecord(label="s", kind="descend", level=0,
                               caps={"gather": (16,)}, queue_cap=0,
                               tele=tele)
    # accepts StageRecord objects and their to_json dicts alike
    for recs in ([rec], [rec.to_json()]):
        rows = obs.skew_rows((8,), recs)
        assert len(rows) == 1
        assert rows[0]["modeled_frac"] == pytest.approx(1 / 8)
        assert rows[0]["observed_frac"] == pytest.approx(0.5)
        assert rows[0]["skew"] == pytest.approx(4.0)
    assert "skew" in obs.format_skew_table(rows, title="t")


# --------------------------------------------------------------------------
# contract 1: telemetry ON reproduces the committed goldens byte-for-byte
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", ("list-g1-s1", "escalate-s6"))
def test_telemetry_on_matches_golden_bytes(name):
    """Solving with cfg.telemetry=True and popping the telemetry key
    reproduces the committed golden record exactly — hashes, attempts,
    scales_log, and every integer counter (incl. the 3-attempt
    escalation ladder of escalate-s6)."""
    case = {c[0]: c for c in golden_cases()}[name]
    _, succ, rank, cfg = case
    sf, rf, stats = rank_list_with_stats(
        succ, rank, sim_mesh(SHAPE, AXES), cfg=cfg.with_(telemetry=True),
        seed=0)
    tele = stats.pop("telemetry")
    assert case_record(sf, rf, stats) == load_golden(name)
    # ...and the popped plane is well-formed for the same solve
    assert tele["stages"] and tele["headroom"]
    for srec in tele["stages"]:
        assert np.isfinite(srec["util_max"])
        assert np.isfinite(srec["util_mean"])


# --------------------------------------------------------------------------
# contract 2: telemetry ON explains every family's run
# --------------------------------------------------------------------------

def _family_instances(n):
    yield "list_g0.0", instances.gen_list(n, gamma=0.0, seed=1)
    yield "list_g0.5", instances.gen_list(n, gamma=0.5, seed=1)
    yield "list_g1.0", instances.gen_list(n, gamma=1.0, seed=1)
    for fam, loc in (("euler_local", True), ("euler_random", False)):
        s, r, _ = instances.gen_euler_tour(n // 2 + 1, seed=1, locality=loc)
        yield fam, instances.pad_to_multiple(s, r, 8)[:2]


def test_all_families_report_finite_utilization():
    """Every scheduled stage of all five paper families produces a
    telemetry record with finite utilization; on first-attempt-clean
    solves the observed max fill stays within the compiled cap."""
    cfg = ListRankConfig(srs_rounds=2, local_contraction=True,
                         telemetry=True)
    sched = [st.label for st in resume_lib.schedule_for(cfg)]
    mesh = sim_mesh(8)
    for fam, (succ, rank) in _family_instances(512):
        _, _, stats = rank_list_with_stats(succ, rank, mesh, cfg=cfg,
                                           seed=1)
        tele = stats["telemetry"]
        labels = {s["label"] for s in tele["stages"]}
        assert not [lbl for lbl in sched if lbl not in labels], \
            (fam, sched, labels)
        assert all(np.isfinite(s["util_max"]) and np.isfinite(s["util_mean"])
                   for s in tele["stages"]), fam
        worst = max((r["fill_max"] for r in tele["headroom"]), default=0.0)
        if stats["attempts"] == 1:
            assert worst <= 1.0, (fam, worst)


def test_escalation_explained_in_scales_terms():
    """A capacity escalation shows up in the headroom report: the
    escalated family's final scale is >1 on the rows of the stage that
    overflowed, so scales_log entries are explained by observed fill."""
    succ, rank = instances.gen_list(512, gamma=1.0, seed=6)
    cfg = ListRankConfig(srs_rounds=1, local_contraction=True,
                         sub_capacity_slack=0.05, telemetry=True)
    _, _, stats = rank_list_with_stats(succ, rank, sim_mesh(8), cfg=cfg,
                                       seed=0)
    assert stats["attempts"] > 1
    scales = tele_lib.parse_scales(stats["scales_log"])
    escalated = [fam for fam, s in scales.items() if s > 1.0]
    assert escalated
    rows = stats["telemetry"]["headroom"]
    for fam in escalated:
        fam_rows = [r for r in rows if r["family"] == fam]
        assert fam_rows and all(r["scale"] > 1.0 for r in fam_rows)


def test_tracer_gets_utilization_annotations():
    """With a tracer attached, telemetry annotates the span tree: every
    committed attempt span carries the stage's util_max, util_mean and
    queue high-water mark as args, the values its telemetry record
    holds."""
    succ, rank = instances.gen_list(512, gamma=1.0, seed=1)
    cfg = ListRankConfig(srs_rounds=2, local_contraction=True,
                         telemetry=True)
    tr = obs.Tracer()
    _, _, stats = rank_list_with_stats(succ, rank, sim_mesh(8), cfg=cfg,
                                       seed=1, tracer=tr)
    annotated = [s for s in tr.spans if "util_max" in s.args]
    assert annotated
    assert all(np.isfinite(s.args["util_max"]) for s in annotated)
    committed = [s for s in tr.find(cat="stage-attempt")
                 if s.args["outcome"] == "committed"]
    assert committed == annotated
    records = stats["telemetry"]["stages"]
    assert [s.args["stage"] for s in committed] == [r["label"]
                                                    for r in records]
    for span, rec in zip(committed, records):
        tele = obs.StageRecord.from_json(rec).tele
        assert span.args["util_max"] == obs.utilization(tele)["util_max"]
        assert span.args["util_mean"] == obs.utilization(tele)["util_mean"]
        assert span.args["queue_hwm"] == float(tele.get("queue_hwm", 0))


def test_metrics_ingest_telemetry():
    """Host-stats ingestion turns the telemetry block into typed
    metrics: stage count, utilization histograms, worst-fill gauge."""
    succ, rank = instances.gen_list(512, gamma=1.0, seed=1)
    cfg = ListRankConfig(srs_rounds=1, local_contraction=True,
                         telemetry=True)
    _, _, stats = rank_list_with_stats(succ, rank, sim_mesh(8), cfg=cfg,
                                       seed=1)
    reg = obs.MetricsRegistry()
    obs.ingest_host_stats(reg, stats)
    by_name = {m.name: m for m in reg}
    assert by_name["solve/telemetry/stages"].snapshot()["value"] > 0
    worst = by_name["solve/telemetry/worst_fill"].snapshot()["value"]
    assert np.isfinite(worst) and worst >= 0
    assert by_name["solve/telemetry/stage_util_max"].snapshot()["count"] > 0


def test_graph_family_telemetry_cc_mode():
    """graphalg front door: the hooking/tour capacities report under
    the 'graph' family and the pipeline record lands in host stats."""
    from _graph_oracles import union_find_labels
    from repro.core import graphalg
    cfg = ListRankConfig(srs_rounds=1, local_contraction=True,
                         telemetry=True)
    edges = instances.gen_graph_edges(120, 180, seed=37, num_components=3)
    labels, st = graphalg.connected_components(edges, 120, sim_mesh(8),
                                               cfg=cfg)
    np.testing.assert_array_equal(labels, union_find_labels(120, edges))
    tele = st["telemetry"]
    (rec,) = tele["stages"]
    assert rec["label"].startswith("graphalg:")
    assert int(rec["tele"]["graph"]["rounds"]) > 0
    assert np.isfinite(rec["util_max"])
    assert any(r["family"] == "graph" for r in tele["headroom"])


def test_telemetry_off_has_no_stats_key():
    succ, rank = instances.gen_list(256, gamma=1.0, seed=1)
    cfg = ListRankConfig(srs_rounds=1, local_contraction=True)
    _, _, stats = rank_list_with_stats(succ, rank, sim_mesh(8), cfg=cfg,
                                       seed=1)
    assert "telemetry" not in stats
