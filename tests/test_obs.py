"""Flight-recorder tests (``repro.obs``): span-tree shape, the
no-perturbation pins (tracer on == tracer off, byte for byte and
collective count for collective count), the metrics registry schema,
the Chrome-trace exporter, and the zero-cost disabled path.

Marked ``obs`` (fast lane); the real-device mesh half runs in
``tests/_subprocess_smoke.py`` suite ``obs``.
"""
import functools
import json

import jax
import numpy as np
import pytest

from _simshard_cases import AXES, SHAPE, case_record, golden_cases, load_golden
from repro import obs
from repro.core import graphalg, treealg
from repro.core.listrank import (FaultSpec, ListRankConfig,
                                 SolveExhausted, instances, introspect,
                                 rank_list_seq, rank_list_with_stats,
                                 sim_mesh, tuner)
from repro.core.listrank.exchange import MeshPlan
from repro.core.listrank import api as api_lib
from repro.core.listrank import resume as resume_lib
from repro.core.listrank import transport as transport_lib
from repro.obs import trace as trace_lib
from repro.runtime.fault_tolerance import SolveSupervisor, SolveSupervisorConfig

from jax.sharding import PartitionSpec as P

pytestmark = pytest.mark.obs

CASES = {name: (s, r, cfg) for name, s, r, cfg in golden_cases()}


def mesh8():
    return sim_mesh(SHAPE, AXES)


def small_case():
    s, r = instances.gen_list(256, gamma=1.0, seed=7)
    return s, r, ListRankConfig(srs_rounds=2, local_contraction=False)


# --------------------------------------------------------------------------
# span-tree well-formedness
# --------------------------------------------------------------------------

def test_clean_solve_covers_every_scheduled_stage_exactly_once():
    s, r, cfg = small_case()
    tr = obs.Tracer()
    sf, rf, stats = rank_list_with_stats(s, r, mesh8(), cfg=cfg, seed=1,
                                         tracer=tr)
    s_ref, r_ref = rank_list_seq(s, r)
    assert np.array_equal(np.asarray(sf), s_ref)
    assert np.array_equal(np.asarray(rf), r_ref)

    labels = [st.label for st in resume_lib.schedule_for(
        cfg.with_(algorithm="srs"))]
    assert labels == ["prep", "descend@0", "descend@1", "base@2",
                      "ascend@1", "ascend@0", "post"]
    stage_spans = list(tr.find(cat="stage"))
    assert [sp.name for sp in stage_spans] == labels

    (solve,) = tr.find(cat="solve")
    assert solve.parent == -1 and solve.args["outcome"] == "ok"
    assert solve.args["backend"] == "simshard"
    for sp in stage_spans:
        assert sp.parent == solve.index
        # exactly one committed attempt nested under each stage
        kids = tr.children(sp)
        assert [k.cat for k in kids] == ["stage-attempt"]
        assert kids[0].name == f"{sp.name}#1"
        assert kids[0].args["outcome"] == "committed"
        assert kids[0].args["wall_s"] >= 0
    # every span closed, with sane interval nesting
    for sp in tr.spans:
        assert sp.t1 is not None and sp.t1 >= sp.t0
        if sp.parent >= 0:
            par = tr.spans[sp.parent]
            assert par.t0 <= sp.t0 and sp.t1 <= par.t1 + 1e-9


def test_attempts_annotated_with_prediction_and_footprint():
    s, r, cfg = small_case()
    tr = obs.Tracer()
    rank_list_with_stats(s, r, mesh8(), cfg=cfg, seed=1, tracer=tr)
    for att in tr.find(cat="stage-attempt"):
        assert att.args["predicted_s"] >= 0
        assert att.args["collective_count"] >= 0
        assert att.args["payload_bytes"] >= 0
    # the solve span carries the §2.6 whole-solve prediction
    (solve,) = tr.find(cat="solve")
    assert solve.args["predicted_solve_s"] > 0
    rows = obs.residual_rows(tr)
    assert {row["stage"] for row in rows} == {
        st.label for st in resume_lib.schedule_for(cfg.with_(algorithm="srs"))}
    assert all(np.isfinite(row["measured_s"]) for row in rows)
    # the table renders every row
    table = obs.format_residual_table(rows)
    for row in rows:
        assert row["stage"] in table


def test_overflow_retry_nests_under_its_stage_span():
    """An injected chase overflow at descend@0: the stage span stays
    open across the retry, so both attempts are its children — the
    first marked overflow, the second committed — with fault/retry
    instants in between."""
    s, r, cfg = CASES["list-g1-s1"]
    tr = obs.Tracer()
    sf, rf, stats = rank_list_with_stats(
        s, r, mesh8(), cfg=cfg, tracer=tr,
        inject=FaultSpec("overflow", stage="descend", level=0,
                         family="chase"))
    assert stats["attempts"] == 2
    (d0,) = tr.find(cat="stage", name="descend@0")
    kids = tr.children(d0)
    assert [k.name for k in kids] == ["descend@0#1", "descend@0#2"]
    assert kids[0].args["outcome"] == "overflow"
    assert kids[0].args["fatal"]["dropped"] > 0
    assert kids[1].args["outcome"] == "committed"
    assert kids[1].args["scales"].startswith("chase=2")
    # the other stages still ran exactly once
    for lbl in ("prep", "base@1", "ascend@0", "post"):
        (sp,) = tr.find(cat="stage", name=lbl)
        assert len(tr.children(sp)) == 1
    names = [i.name for i in tr.instants]
    assert "overflow:chase:descend@0" in names
    assert "escalate:descend@0" in names


def test_checkpoint_spans_appear_under_supervised_solve(tmp_path):
    s, r, cfg = CASES["list-g1-s1"]
    tr = obs.Tracer()
    sup = SolveSupervisor(SolveSupervisorConfig(ckpt_dir=str(tmp_path)))
    rank_list_with_stats(s, r, mesh8(), cfg=cfg, supervisor=sup, tracer=tr)
    saves = list(tr.find(cat="checkpoint"))
    assert saves and all(sp.name.startswith("ckpt-save@") for sp in saves)
    assert saves[0].parent >= 0  # nested inside the solve tree


# --------------------------------------------------------------------------
# no-perturbation pins: tracer on == tracer off
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", ("list-g1-s1", "escalate-s6"))
def test_golden_bytes_identical_with_tracing_on(name):
    """The committed mesh goldens (solve output hashes, escalation
    path, full counters) reproduce exactly with the tracer attached —
    including through the capacity-escalation retry ladder."""
    s, r, cfg = CASES[name]
    tr = obs.Tracer()
    sf, rf, stats = rank_list_with_stats(s, r, mesh8(), cfg=cfg, tracer=tr)
    assert case_record(sf, rf, stats) == load_golden(name)
    assert len(tr.spans) > 0  # the tracer really was recording


@pytest.mark.parametrize("p", (8, 256))
def test_stage_collective_counts_identical_tracer_on_off(p):
    """The live staged solve's per-stage traced collective counts
    (host_stats["stage_collectives"], derived from each stage jaxpr)
    are identical with and without the tracer, at small and large p."""
    n = 8 * p
    s, r = instances.gen_list(n, gamma=1.0, seed=9)
    cfg = ListRankConfig(srs_rounds=1, local_contraction=True)
    out = {}
    for tag, tr in (("off", None), ("on", obs.Tracer())):
        sf, rf, stats = rank_list_with_stats(
            s, r, sim_mesh(p), cfg=cfg, seed=1, stage_counters=True,
            tracer=tr, term_bound=1)
        out[tag] = (np.asarray(sf).tobytes(), np.asarray(rf).tobytes(),
                    stats["stage_collectives"],
                    {k: v for k, v in stats.items() if isinstance(v, int)})
    assert out["on"] == out["off"]
    assert any(dict(c).get("all_to_all", 0) > 0
               for _, c in out["on"][2])


@pytest.mark.parametrize("p", (8, 256))
def test_mesh_program_counts_unaffected_by_active_tracer(p):
    """Tracing the mesh-backend solver program (abstract p-device mesh,
    no devices) inside an open tracer span yields the same jaxpr
    collective counts as with no tracer anywhere in scope — the
    recorder adds zero collectives to the traced program."""
    import jax.numpy as jnp

    n = 4 * p
    m = n // p
    cfg = ListRankConfig(srs_rounds=1, local_contraction=True)
    am = jax.sharding.AbstractMesh((p,), ("pe",))
    plan = MeshPlan.from_mesh(am, ("pe",))
    specs = api_lib.build_specs(cfg, plan, m, n, term_bound=m)
    spec = P(("pe",))
    fn = functools.partial(api_lib._solve_sharded, plan=plan, cfg=cfg,
                           specs=specs, m=m)
    mapped = jax.shard_map(fn, mesh=am, in_specs=(spec, spec, P()),
                           out_specs=(spec, spec, P()), check_vma=False)
    args = (jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32), jnp.int32(0))

    baseline = introspect.collective_counts(mapped, *args)
    tr = obs.Tracer()
    with tr.span("solve", cat="solve"):
        with tr.span("descend@0", cat="stage"):
            traced = introspect.collective_counts(mapped, *args)
    assert traced == baseline
    assert baseline.get("all_to_all", 0) > 0


def test_disabled_tracer_allocates_no_spans(monkeypatch):
    """With tracing off every instrumentation site goes through
    NULL_TRACER; no Span object may be constructed anywhere in the
    solve/graphalg/treealg paths (near-zero disabled overhead)."""
    def boom(*a, **kw):
        raise AssertionError("Span allocated with tracing disabled")

    monkeypatch.setattr(trace_lib, "Span", boom)
    s, r, cfg = small_case()
    sf, rf, stats = rank_list_with_stats(s, r, mesh8(), cfg=cfg, seed=1)
    assert np.array_equal(np.asarray(rf), rank_list_seq(s, r)[1])
    edges = instances.gen_graph_edges(24, 30, seed=3)
    graphalg.connected_components(edges, 24, mesh8(), cfg=cfg)


# --------------------------------------------------------------------------
# front doors: graphalg / treealg spans
# --------------------------------------------------------------------------

def test_graphalg_frontdoor_traced():
    edges = instances.gen_graph_edges(48, 80, seed=3)
    cfg = ListRankConfig(srs_rounds=1, local_contraction=False)
    tr = obs.Tracer()
    labels, stats = graphalg.connected_components(edges, 48, mesh8(),
                                                  cfg=cfg, tracer=tr)
    (pipe,) = tr.find(cat="solve", name="graphalg:cc")
    assert pipe.args["outcome"] == "ok" and pipe.args["backend"] == "simshard"
    kids = tr.children(pipe)
    assert kids and kids[-1].args["outcome"] == "committed"
    assert kids[-1].args["predicted_s"] >= 0
    assert tr.metrics.get("graphalg/cc/cc_rounds").value > 0


def test_treealg_build_tour_traced():
    parent = np.array([0, 0, 0, 1, 1, 2, 5, 6], np.int32)
    cfg = ListRankConfig(srs_rounds=1, local_contraction=False)
    tr = obs.Tracer()
    treealg.build_tour(parent, mesh8(), cfg=cfg, tracer=tr)
    (tour,) = tr.find(cat="solve", name="build_tour")
    assert tour.args["outcome"] == "ok"
    kids = tr.children(tour)
    assert kids[-1].args["outcome"] == "committed"


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_metrics_registry_schema():
    reg = obs.MetricsRegistry()
    c = reg.counter("msgs", help="messages")
    c.inc().inc(3)
    assert reg.counter("msgs").value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("msgs")  # kind conflict is an error
    reg.gauge("depth").set(7)
    h = reg.histogram("wall")
    h.observe(1.0)
    h.observe(3.0)
    assert h.count == 2 and h.mean == 2.0 and h.min == 1.0 and h.max == 3.0
    reg.text("log").set("a;b")
    snap = reg.to_dict()
    assert snap["msgs"]["value"] == 4 and snap["wall"]["count"] == 2
    assert {m.kind for m in reg} == {"counter", "gauge", "histogram", "text"}
    json.dumps(snap)  # the snapshot is JSON-clean


def test_ingest_host_stats_types_and_help():
    s, r, cfg = small_case()
    _, _, stats = rank_list_with_stats(s, r, mesh8(), cfg=cfg, seed=1)
    reg = obs.MetricsRegistry()
    obs.ingest_host_stats(reg, stats)
    assert reg.get("solve/rounds").kind == "counter"
    assert reg.get("solve/rounds").help  # help sourced from srs.STAT_HELP
    assert reg.get("solve/max_queue").kind == "gauge"
    assert reg.get("solve/scales_log").kind == "text"
    assert reg.get("solve/stages_run").value == len(
        resume_lib.schedule_for(cfg.with_(algorithm="srs")))
    json.dumps(reg.to_dict())


def test_json_safe_stats_handles_solver_stats():
    s, r, cfg = CASES["list-g1-s1"]
    _, _, stats = rank_list_with_stats(s, r, mesh8(), cfg=cfg)
    out = obs.json_safe_stats(stats)
    json.dumps(out)  # tuples (stage_log), nested dicts (recovery) survive
    assert out["stage_log"] == list(stats["stage_log"])


# --------------------------------------------------------------------------
# exporter
# --------------------------------------------------------------------------

def test_chrome_trace_roundtrip(tmp_path):
    s, r, cfg = CASES["list-g1-s1"]
    tr = obs.Tracer(meta={"name": "roundtrip"})
    rank_list_with_stats(
        s, r, mesh8(), cfg=cfg, tracer=tr,
        inject=FaultSpec("overflow", stage="descend", level=0,
                         family="chase"))
    path = tmp_path / "trace.json"
    obs.write_chrome_trace(tr, path)
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == len(tr.spans)
    for e in xs:
        assert e["dur"] >= 0 and e["ts"] >= 0
    # spans export in begin order: timestamps are monotone nondecreasing
    ts = [e["ts"] for e in xs]
    assert ts == sorted(ts)
    # the injected fault shows up as a thread-scoped instant
    instants = [e for e in evs if e["ph"] == "i"]
    assert any(e["name"] == "overflow:chase:descend@0" for e in instants)
    assert all(e["s"] == "t" for e in instants)


def test_chrome_trace_null_tracer_and_empty_tree(tmp_path):
    """Exporter edge cases: the NullTracer, a tracer with no spans at
    all, and spans without counter samples all export valid
    Perfetto-loadable JSON (round-trips through json)."""
    for tracer in (trace_lib.NULL_TRACER, obs.Tracer()):
        doc = obs.chrome_trace(tracer)
        blob = json.dumps(doc)
        back = json.loads(blob)
        assert isinstance(back["traceEvents"], list)
        assert back["traceEvents"][0]["ph"] == "M"
        assert back["displayTimeUnit"] == "ms"
        assert not [e for e in back["traceEvents"] if e["ph"] == "C"]
    # spans but no counters: X events export, no C events
    tr = obs.Tracer(meta={"name": "edge"})
    with tr.span("solo", cat="stage"):
        pass
    path = tmp_path / "edge.json"
    obs.write_chrome_trace(tr, path)
    doc = json.loads(path.read_text())
    phs = [e["ph"] for e in doc["traceEvents"]]
    assert "X" in phs and "C" not in phs


def test_counter_tracks_interleave_with_fault_instants():
    """Counter samples and fault instants share the timeline: both
    export, counter events are time-sorted, and their timestamps land
    inside the span that emitted them."""
    tr = obs.Tracer()
    with tr.span("solve", cat="solve"):
        tr.instant("fault:injected", cat="fault")
        tr.counter("telemetry/util_max", 0.25)
        tr.instant("fault:recovered", cat="fault")
        tr.counter("telemetry/util_max", 0.75)
        tr.counter("telemetry/queue_hwm", 12.0)
    doc = obs.chrome_trace(tr)
    evs = doc["traceEvents"]
    cs = [e for e in evs if e["ph"] == "C"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert len(cs) == 3 and len(instants) == 2
    assert [e["ts"] for e in cs] == sorted(e["ts"] for e in cs)
    assert {e["name"] for e in cs} == {"telemetry/util_max",
                                       "telemetry/queue_hwm"}
    assert all(e["args"]["value"] >= 0 for e in cs)
    (solve,) = [e for e in evs if e["ph"] == "X"]
    for e in cs + instants:
        assert solve["ts"] <= e["ts"] <= solve["ts"] + solve["dur"]
    json.dumps(doc)


def test_null_tracer_counter_is_noop():
    trace_lib.NULL_TRACER.counter("telemetry/util_max", 1.0)
    assert trace_lib.NULL_TRACER.counters == ()


def test_residual_summary_totals():
    s, r, cfg = small_case()
    tr = obs.Tracer()
    rank_list_with_stats(s, r, mesh8(), cfg=cfg, seed=1, tracer=tr)
    rows = obs.residual_rows(tr)
    summ = obs.residual_summary(rows)
    assert summ["stages"] == len(rows)
    assert summ["measured_s"] == pytest.approx(
        sum(row["measured_s"] for row in rows))
    assert summ["predicted_s"] == pytest.approx(
        sum(row["predicted_s"] for row in rows))


# --------------------------------------------------------------------------
# structured exhaustion rendering (satellite a)
# --------------------------------------------------------------------------

def test_exhaustion_error_renders_escalation_path():
    s, r, cfg = CASES["escalate-s6"]
    with pytest.raises(SolveExhausted) as ei:
        rank_list_with_stats(s, r, mesh8(), cfg=cfg, max_retries=1)
    msg = str(ei.value)
    assert "did not complete after 2 attempts" in msg
    assert "escalation path:" in msg
    # each attempt line is a tuner.format_scales rendering
    assert f"attempt 1: {ei.value.scales_log[0]}" in msg
    assert ei.value.scales_log[0] == tuner.format_scales(
        tuner.CapacityScales())
    assert "fatal stats of the failing attempt:" in msg
    for key, count in ei.value.fatal.items():
        if count:
            assert f"{key}={count}" in msg
    for fam in ei.value.families:
        assert fam in msg
