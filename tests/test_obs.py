"""Flight-recorder tests (``repro.obs``): span-tree shape, the
no-perturbation pins (tracer on == tracer off, byte for byte and
collective count for collective count), the metrics registry schema,
the spans' host annotations in the ``jax.profiler`` trace, the
host-sync counter, and the zero-cost disabled path.

Marked ``obs`` (fast lane); the real-device mesh half runs in
``tests/_subprocess_smoke.py`` suite ``obs``.
"""
import functools
import json
import tempfile

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
    NULL_TRACER; no Span object and no profiler annotation may be
    constructed anywhere in the solve/graphalg/treealg paths (near-zero
    disabled overhead)."""
    def boom(*a, **kw):
        raise AssertionError("Span allocated with tracing disabled")

    def no_annotation(*a, **kw):
        raise AssertionError("TraceAnnotation made with tracing disabled")

    monkeypatch.setattr(trace_lib, "Span", boom)
    monkeypatch.setattr(trace_lib, "TraceAnnotation", no_annotation)
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
# the spans in the jax.profiler trace
# --------------------------------------------------------------------------

def profiled(fn):
    """Run ``fn()`` under a ``jax.profiler`` trace; returns the spans a
    tracer wrote there: [(cat/name, start s, end s)] by start."""
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            fn()
        return obs.profile_spans(logdir)


def span_label(sp):
    return f"{sp.cat}/{sp.name}"


def assert_nested_as_tree(tr, events):
    """One profiler event per recorded span, in begin order, each inside
    the event of its parent span."""
    assert [e[0] for e in events] == [span_label(sp) for sp in tr.spans]
    for sp, (_, t0, t1) in zip(tr.spans, events):
        assert t0 <= t1
        if sp.parent >= 0:
            _, p0, p1 = events[sp.parent]
            assert p0 <= t0 and t1 <= p1


def test_chrome_trace_roundtrip():
    """The solve's timeline is the profiler's own trace: a traced solve
    through an injected overflow and its retry writes one prefixed host
    event per span, with starts in begin order, nested as the span
    tree; the fault instants stay in the tracer's record, inside the
    attempt that raised them."""
    s, r, cfg = CASES["list-g1-s1"]
    tr = obs.Tracer()
    events = profiled(lambda: rank_list_with_stats(
        s, r, mesh8(), cfg=cfg, tracer=tr,
        inject=FaultSpec("overflow", stage="descend", level=0,
                         family="chase")))
    assert len(events) == len(tr.spans)
    starts = [t0 for _, t0, _ in events]
    assert starts == sorted(starts)
    assert_nested_as_tree(tr, events)
    (over,) = [i for i in tr.instants if i.name == "overflow:chase:descend@0"]
    (first,) = tr.find(cat="stage-attempt", name="descend@0#1")
    assert first.t0 <= over.t0 <= first.t1


def test_chrome_trace_null_tracer_and_empty_tree():
    """Edge cases of the profiler annotations: a solve through the
    NullTracer writes no prefixed event, a tracer with no span writes
    none, and a lone span writes exactly one."""
    s, r, cfg = small_case()
    assert profiled(lambda: rank_list_with_stats(
        s, r, mesh8(), cfg=cfg, seed=1)) == []
    assert profiled(obs.Tracer) == []
    tr = obs.Tracer()

    def one_span():
        with tr.span("solo", cat="stage"):
            pass

    (event,) = profiled(one_span)
    assert event[0] == "stage/solo" and event[1] <= event[2]


def test_counter_tracks_interleave_with_fault_instants():
    """Utilization values ride as args of the attempt spans, and fault
    instants share the tracer's clock with them: each instant lies
    inside the span open when it was raised, between the attempts it
    separates, and the attempts' profiler events nest in the solve's."""
    tr = obs.Tracer()

    def record():
        with tr.span("solve", cat="solve"):
            tr.instant("fault:injected", cat="fault")
            with tr.span("descend@0#1", cat="stage-attempt") as a:
                a.annotate(util_max=0.25, util_mean=0.125)
            tr.instant("fault:recovered", cat="fault")
            with tr.span("descend@0#2", cat="stage-attempt") as b:
                b.annotate(util_max=0.75, util_mean=0.5, queue_hwm=12.0)

    events = profiled(record)
    assert_nested_as_tree(tr, events)
    solve, first, second = tr.spans
    injected, recovered = tr.instants
    assert solve.t0 <= injected.t0 <= first.t0
    assert first.t1 <= recovered.t0 <= second.t0 <= second.t1 <= solve.t1
    assert [sp.args["util_max"] for sp in (first, second)] == [0.25, 0.75]
    assert second.args["queue_hwm"] == 12.0
    assert {i.parent for i in tr.instants} == {solve.index}


def test_null_tracer_counter_is_noop():
    """The NullTracer's host-sync counter counts nothing, and neither
    tracer keeps counter tracks any more."""
    for _ in range(3):
        trace_lib.NULL_TRACER.host_sync()
    assert trace_lib.NULL_TRACER.host_syncs == 0
    tr = obs.Tracer()
    tr.host_sync()
    tr.host_sync()
    assert tr.host_syncs == 2
    assert not hasattr(trace_lib.NULL_TRACER, "counter")
    assert not hasattr(tr, "counter")


def test_profiler_trace_holds_one_event_per_span_nested_as_the_tree():
    s, r, cfg = small_case()
    tr = obs.Tracer()
    events = profiled(lambda: rank_list_with_stats(
        s, r, mesh8(), cfg=cfg, seed=1, tracer=tr))
    assert len(tr.spans) > 7 * 4
    assert all(e[0].split("/")[0] in ("solve", "frontdoor", "stage",
                                      "stage-attempt", "driver")
               for e in events)
    assert_nested_as_tree(tr, events)


def test_end_on_parent_exits_forgotten_children_annotations():
    """``end`` on a parent closes its still-open children, innermost
    first, and exits their annotations; so does ``close_all``. A second
    ``end`` of a closed span changes nothing."""
    tr = obs.Tracer()

    def forget_children():
        outer = tr.begin("outer", cat="solve")
        tr.begin("middle", cat="stage")
        tr.begin("inner", cat="driver")
        tr.end(outer)
        tr.begin("a", cat="solve")
        tr.begin("b", cat="stage")
        tr.close_all()
        closed = [sp.t1 for sp in tr.spans]
        tr.end(outer, outcome="late")
        assert [sp.t1 for sp in tr.spans] == closed

    events = profiled(forget_children)
    assert [e[0] for e in events] == ["solve/outer", "stage/middle",
                                      "driver/inner", "solve/a", "stage/b"]
    assert_nested_as_tree(tr, events)
    outer, middle, inner, a, b = tr.spans
    assert outer.t1 >= middle.t1 >= inner.t1 >= inner.t0
    assert a.t1 >= b.t1
    assert outer.args["outcome"] == "late"


def _solve_syncs(cfg, **kw):
    s, r, _ = small_case()
    tr = obs.Tracer()
    _, _, stats = rank_list_with_stats(s, r, mesh8(), cfg=cfg, seed=1,
                                       tracer=tr, **kw)
    (solve,) = tr.find(cat="solve")
    return solve.args["host_syncs"], stats["attempts"]


#: blocking syncs of a clean srs_rounds=2 solve: the term_bound
#: readback, the fingerprint's two copies, one wait per stage (7),
#: four fatal counters per stage, prep's live-link count, the 15 stats
#: of the last readback
CLEAN_SYNCS = 1 + 2 + 7 + 7 * len(resume_lib.FATAL_KEYS) + 1 + 15


@pytest.mark.parametrize("case", ("clean", "escalated", "injected",
                                  "skipped"))
def test_host_syncs_counts_every_blocking_readback(case):
    _, _, cfg = small_case()
    assert CLEAN_SYNCS == 54
    if case == "clean":
        assert _solve_syncs(cfg) == (54, 1)
    elif case == "escalated":
        # a real sub overflow at descend@0, twice: each retried attempt
        # waits once and reads its four fatal counters again
        syncs, attempts = _solve_syncs(cfg.with_(sub_capacity_slack=0.05))
        assert attempts == 3
        assert syncs == 54 + (attempts - 1) * (1 + 4)
    elif case == "injected":
        # an injector also validates each stage's output (three copies
        # per store; two stores after descend@0), so compare with one
        # that never fires
        idle, _ = _solve_syncs(cfg, inject=FaultSpec(
            "overflow", stage="descend", level=5, family="chase"))
        syncs, attempts = _solve_syncs(cfg, inject=FaultSpec(
            "overflow", stage="descend", level=0, family="chase"))
        assert attempts == 2 and idle > 54
        assert syncs == idle + 1 + 4 + 2 * 3
    else:
        # one PE with local contraction: prep and post alone
        s, r, _ = small_case()
        tr = obs.Tracer()
        _, _, stats = rank_list_with_stats(
            s, r, sim_mesh(1), cfg=cfg.with_(local_contraction=True),
            tracer=tr)
        (solve,) = tr.find(cat="solve")
        assert stats["stage_log"] == ("prep", "post")
        assert solve.args["recursion_skipped"] == 1
        assert solve.args["host_syncs"] == 1 + 2 + 2 + 2 * 4 + 1 + 15


def test_each_committed_attempt_has_dispatch_wait_readback():
    s, r, cfg = small_case()
    tr = obs.Tracer()
    rank_list_with_stats(s, r, mesh8(), cfg=cfg, seed=1, tracer=tr)
    committed = [a for a in tr.find(cat="stage-attempt")
                 if a.args["outcome"] == "committed"]
    assert len(committed) == 7
    for att in committed:
        kids = tr.children(att)
        assert [span_label(k) for k in kids] == [
            "driver/dispatch", "driver/wait", "driver/readback"]
        dispatch, wait, _ = kids
        # wall_s is read on the spans' clock: enqueue (inside dispatch)
        # plus the device wait
        assert wait.duration <= att.args["wall_s"]
        assert att.args["wall_s"] <= dispatch.duration + wait.duration
    (solve,) = tr.find(cat="solve")
    last = tr.children(solve)[-1]
    assert span_label(last) == "driver/readback"
    assert last.t0 >= max(a.t1 for a in committed)


def test_solve_span_encloses_front_door_spans():
    s, r, cfg = small_case()
    tr = obs.Tracer()
    rank_list_with_stats(s, r, mesh8(), cfg=cfg, seed=1, tracer=tr)
    (solve,) = tr.find(cat="solve")
    door = [span_label(k) for k in tr.children(solve)][:3]
    assert door == ["frontdoor/term_bound", "frontdoor/place",
                    "frontdoor/fingerprint"]
    first_stage = next(tr.find(cat="stage"))
    for sp in tr.find(cat="frontdoor"):
        assert sp.parent == solve.index
        assert solve.t0 <= sp.t0 <= sp.t1 <= first_stage.t0


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
