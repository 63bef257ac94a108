"""The span readers on a made-up run: the front door, the driver's host
time and the stage walls add up to each call's latency."""
import types

import pytest

import harness

SPANS = [("prep", 1.010, 1.030, 0.015), ("descend@0", 1.032, 1.050, 0.010),
         ("base@2", 1.051, 1.070, 0.012), ("post", 1.072, 1.090, 0.016)]


def fake_run():
    call = harness.Call(index=0, instance=0, seed=1, t_call=1.000,
                        t_ret=1.095, spans=SPANS)
    failed = harness.Call(index=1, instance=1, seed=2, t_call=1.1,
                          t_ret=1.2, error="SolveExhausted: ...")
    return types.SimpleNamespace(calls=[call, failed], done=[call], n=4096,
                                 t_first=1.0, t_last=1.2, trace=None)


def read(name):
    return harness.metric_reader(name).read(fake_run())


def test_span_readers():
    assert read("frontdoor_host_s") == pytest.approx(0.010 + 0.005)
    assert read("driver_gap_s") == pytest.approx(0.080 - 0.053)
    assert read("contract_s") == pytest.approx(0.031)
    assert read("descend_s") == pytest.approx(0.010)
    assert read("base_s") == pytest.approx(0.012)
    assert read("ascend_s") is None          # no ascend stage ran


def test_span_readers_add_up_to_the_latency():
    parts = ["frontdoor_host_s", "driver_gap_s", "contract_s", "descend_s",
             "base_s"]
    assert sum(read(p) for p in parts) == pytest.approx(0.095)


def test_split_metric_shares_its_reader():
    assert harness.metric_reader("descend_s.2x2") is \
        harness.metric_reader("descend_s")


def test_rate_counts_completed_solves_over_the_window():
    assert read("rank_rate") == pytest.approx(4096 / 0.2 / 1e6)
    assert read("device_idle_share") is None
