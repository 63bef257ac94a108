"""The reduction from a profiler trace to busy time, idle gaps and op
sums, on made-up intervals."""
import pytest

from trace_reduce import (TraceData, busy_seconds, idle_gaps, merged,
                          op_family, op_seconds)

OPS = [("fusion.1", 1.0, 2.0), ("all-to-all.3", 1.5, 3.0),
       ("copy.2", 5.0, 6.0), ("all-to-all.4", 8.0, 9.5),
       ("fusion.1", 5.5, 5.75)]


def test_merged_unions_and_clips():
    assert merged([(s, e) for _, s, e in OPS], 0.0, 9.0) == [
        (1.0, 3.0), (5.0, 6.0), (8.0, 9.0)]
    assert merged([(1.0, 2.0), (2.0, 3.0)], 0.0, 10.0) == [(1.0, 3.0)]
    assert merged([(1.0, 2.0)], 3.0, 4.0) == []


def test_busy_and_gaps_cover_the_window():
    lo, hi = 0.5, 9.0
    busy = busy_seconds(OPS, lo, hi)
    gaps = idle_gaps(OPS, lo, hi)
    assert busy == pytest.approx(2.0 + 1.0 + 1.0)
    assert gaps == [(0.5, 1.0), (3.0, 5.0), (6.0, 8.0)]
    assert busy + sum(e - s for s, e in gaps) == pytest.approx(hi - lo)


def test_op_seconds_clips_each_event():
    sec = op_seconds(OPS, 0.0, 9.0)
    assert sec["fusion.1"] == pytest.approx(1.25)
    assert sec["all-to-all.4"] == pytest.approx(1.0)
    assert sum(sec.values()) == pytest.approx(1.0 + 1.5 + 1.0 + 1.0 + 0.25)


@pytest.mark.parametrize("name,family", [
    ("fusion.12", "fusion"), ("all-to-all.3", "all-to-all"),
    ("while", "while"), ("copy-start.1.2", "copy-start.1"),
    ("custom.call", "custom.call")])
def test_op_family(name, family):
    assert op_family(name) == family


def test_window_spans_the_annotations():
    td = TraceData(devices={}, annotations=[("bench_call#0", 1.0, 2.0),
                                            ("bench_call#1", 2.5, 4.0)])
    assert td.window() == (1.0, 4.0)
    with pytest.raises(ValueError):
        TraceData(devices={}, annotations=[]).window()


def load_recorded(name):
    import lzma
    from pathlib import Path

    from jax.profiler import ProfileData
    raw = lzma.decompress((Path(__file__).parent / "data" / name)
                          .read_bytes())
    return TraceData.from_profile(ProfileData.from_serialized_xspace(raw),
                                  "bench_call#")


def test_recorded_one_chip_trace():
    """Two solves of 4096 elements on one v5e, recorded with the
    benchmark's profiler options and call annotations."""
    from trace_reduce import in_programs, self_seconds
    td = load_recorded("one_chip_n4096.xplane.pb.xz")
    assert [a[0] for a in td.annotations] == ["bench_call#0", "bench_call#1"]
    assert list(td.devices) == ["/device:TPU:0"]
    lo, hi = td.window()
    ops = td.devices["/device:TPU:0"]
    programs = td.modules["/device:TPU:0"]
    busy = busy_seconds(ops, lo, hi)
    gaps = idle_gaps(ops, lo, hi)
    assert busy == pytest.approx(0.016056111, abs=1e-9)
    assert hi - lo == pytest.approx(0.109403254, abs=1e-9)
    assert busy + sum(e - s for s, e in gaps) == pytest.approx(hi - lo)
    # every op runs inside one of the seven stage programs (and the
    # conversions of the front door), and self time adds up to busy time
    named = in_programs(ops, programs)
    assert not any(n.startswith("?/") for n, _, _ in named)
    assert {n.split("/")[0] for n, _, _ in named} >= {
        "jit__prep_body", "jit__descend_body", "jit__base_body",
        "jit__ascend_body", "jit__post_body"}
    sec = self_seconds(named, lo, hi)
    assert min(sec.values()) > -1e-12
    assert sum(sec.values()) == pytest.approx(busy, rel=1e-9)
    assert busy_seconds(programs, lo, hi) == pytest.approx(busy, rel=0.02)


def test_recorded_2x2_trace_all_to_all():
    """Two solves of 4 x 1024 elements on the 2x2 host: every device
    runs the same 180 all_to_all ops, which ``all_to_all_s`` sums per
    device and averages per solve; the device idle share agrees with
    the busy time."""
    import types

    import harness
    td = load_recorded("2x2_n4x1024.xplane.pb.xz")
    assert sorted(td.devices) == [f"/device:TPU:{i}" for i in range(4)]
    lo, hi = td.window()
    a2a = [[(n, s, e) for n, s, e in ops if op_family(n) == "all_to_all"]
           for ops in td.devices.values()]
    assert [len(x) for x in a2a] == [180] * 4
    run = types.SimpleNamespace(trace=td, calls=[None, None])
    value = harness.load_module("metrics", "all_to_all_s").read(run)
    per_dev = [sum(e - s for _, s, e in x) for x in a2a]
    assert value == pytest.approx(sum(per_dev) / 4 / 2, rel=1e-12)
    assert value == pytest.approx(0.000325243, abs=1e-9)
    idle = harness.load_module("metrics", "device_idle_share").read(run)
    busy = [busy_seconds(ops, lo, hi) for ops in td.devices.values()]
    assert idle == pytest.approx(100 * (1 - sum(busy) / 4 / (hi - lo)))
    assert 0 < idle < 100


def test_readers_find_nothing_on_one_chip():
    """One chip has no exchange: the reader returns nothing, not 0."""
    import types

    import harness
    td = load_recorded("one_chip_n4096.xplane.pb.xz")
    run = types.SimpleNamespace(trace=td, calls=[None, None])
    assert harness.load_module("metrics", "all_to_all_s").read(run) is None
