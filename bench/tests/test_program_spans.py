"""The program's own spans in a profiler trace recorded on the chip.

``one_chip_n4096_spans.xplane.pb.xz``: solves of 4096 elements on one
v5e through the benchmark's harness (``run_cell`` with ``trace``), so
the program's span recorder was on and each of its spans is a host
annotation ``repro:<cat>/<name>`` beside the harness's ``bench_call#k``.
These tests check, on that trace, that the spans are on the device's
clock and name the device's idle gaps inside a call, and that the
benchmark's reduction keeps reading only its own annotations.
"""
import lzma
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from harness import CALL_ANNOTATION
from repro.obs import ANNOTATION_PREFIX, profile_spans
from trace_reduce import TraceData, idle_gaps

FIXTURE = Path(__file__).parent / "data" / "one_chip_n4096_spans.xplane.pb.xz"
DEVICE = "/device:TPU:0"
STAGES = ["prep", "descend@0", "descend@1", "base@2", "ascend@1",
          "ascend@0", "post"]


@pytest.fixture(scope="module")
def raw():
    return lzma.decompress(FIXTURE.read_bytes())


@pytest.fixture(scope="module")
def bench_trace(raw):
    return TraceData.from_profile(ProfileData.from_serialized_xspace(raw),
                                  CALL_ANNOTATION)


@pytest.fixture(scope="module")
def spans(raw, tmp_path_factory):
    """The program's spans, read by the program's own reader from a
    profile directory laid out as ``jax.profiler`` writes it."""
    logdir = tmp_path_factory.mktemp("profile")
    run = logdir / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(raw)
    return profile_spans(logdir)


def inside(outer, inner) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_benchmark_reduction_reads_only_its_annotations(bench_trace, spans):
    calls = bench_trace.annotations
    assert calls and all(name.startswith(CALL_ANNOTATION)
                         for name, _, _ in calls)
    assert not any(name.startswith(ANNOTATION_PREFIX)
                   for name, _, _ in calls)
    assert bench_trace.window() == (calls[0][1], calls[-1][2])
    assert list(bench_trace.devices) == [DEVICE]


def test_each_call_holds_one_solve_with_the_span_tree(bench_trace, spans):
    for call in bench_trace.annotations:
        mine = [s for s in spans if inside(call, s)]
        (solve,) = [s for s in mine if s[0] == "solve/solve"]
        assert all(inside(solve, s) for s in mine)
        names = [s[0] for s in mine]
        assert names[:4] == ["solve/solve", "frontdoor/term_bound",
                             "frontdoor/place", "frontdoor/fingerprint"]
        assert [n.split("/", 1)[1] for n in names if n.startswith(
            "stage/")] == STAGES
        attempts = [s for s in mine if s[0].startswith("stage-attempt/")]
        assert [a[0] for a in attempts] == [f"stage-attempt/{lbl}#1"
                                            for lbl in STAGES]
        for att in attempts:
            assert [s[0] for s in mine if inside(att, s) and s is not att] \
                == ["driver/dispatch", "driver/wait", "driver/readback"]
        assert names[-1] == "driver/readback"
        assert mine[-1][1] >= attempts[-1][2]


def test_spans_share_the_device_clock(bench_trace, spans):
    """Each stage program starts on the device inside the stage attempt
    that dispatched it: ``jit__<kind>_body`` of the XLA Modules line
    within ``stage-attempt/<kind>...``."""
    attempts = [s for s in spans if s[0].startswith("stage-attempt/")]
    assert len(attempts) == len(STAGES) * len(bench_trace.annotations)
    lo, hi = bench_trace.window()
    programs = [p for p in bench_trace.modules[DEVICE]
                if lo <= p[1] <= hi and p[0].endswith("_body")]
    assert programs
    for prog, start, _ in programs:
        (att,) = [a for a in attempts if a[1] <= start <= a[2]]
        kind = att[0].split("/", 1)[1].split("@")[0].split("#")[0]
        assert prog == f"jit__{kind}_body"


def test_idle_gaps_in_calls_are_named_by_program_spans(bench_trace, spans):
    """The device's ten longest idle gaps that lie in a call each have
    a front-door, driver or stage-attempt span over their middle."""
    lo, hi = bench_trace.window()
    gaps = sorted(idle_gaps(bench_trace.devices[DEVICE], lo, hi),
                  key=lambda g: g[0] - g[1])
    mids = [(s + e) / 2 for s, e in gaps]
    in_calls = [t for t in mids if any(c[1] <= t <= c[2]
                                       for c in bench_trace.annotations)]
    assert in_calls
    for t in in_calls[:10]:
        cover = [s for s in spans if s[1] <= t <= s[2]]
        innermost = max(cover, key=lambda s: s[1])
        assert innermost[0].split("/", 1)[0] in (
            "frontdoor", "driver", "stage-attempt")
