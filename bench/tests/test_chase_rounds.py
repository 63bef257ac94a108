"""The locality cell ``list-2x2-g0.n20x4-loop`` and the metric
``chase_rounds.2x2``: the reader on a made-up run, the cell as the
harness loads it, and a CPU rehearsal of the cell on four devices."""
import ast
import subprocess
import sys
import types
from pathlib import Path

import pytest

import harness

CELL = "list-2x2-g0.n20x4-loop"
REHEARSE = Path(harness.__file__).with_name("rehearse.py")


def call(index, counters, error=None):
    return harness.Call(index=index, instance=0, seed=index, t_call=0.0,
                        t_ret=1.0, counters=counters, error=error)


@pytest.mark.parametrize("calls,expect", [
    ([call(0, {"attempts": 1, "rounds": 8}),
      call(1, {"attempts": 1, "rounds": 10})], 9.0),
    # a failed solve returns no counters and counts for nothing
    ([call(0, {"attempts": 1, "rounds": 2024}),
      call(1, None, error="SolveExhausted: ...")], 2024.0),
    ([call(0, None, error="SolveExhausted: ...")], None),
])
def test_reader_is_the_mean_per_completed_solve(calls, expect):
    run = types.SimpleNamespace(
        calls=calls, done=[c for c in calls if c.error is None])
    assert harness.metric_reader("chase_rounds.2x2").read(run) == expect


def test_cell_loads_with_four_chips_and_no_permutation():
    cell = harness.load_cell(CELL)
    assert cell.chips == 4 and cell.config["mesh"] == [2, 2]
    assert cell.config["gamma"] == 0.0
    assert cell.elements_per_pe == 1 << 20
    gamma1 = harness.load_cell("list-2x2.n20x4-loop")
    assert {m["name"] for m in cell.per_layer} == \
        {m["name"] for m in gamma1.per_layer}
    assert "chase_rounds.2x2" in {m["name"] for m in cell.per_layer}
    assert [m["name"] for m in cell.end_to_end] == \
        [m["name"] for m in gamma1.end_to_end]


def test_rehearsal_is_correct_and_reads_the_stage_metrics():
    proc = subprocess.run(
        [sys.executable, str(REHEARSE), "--workload", CELL,
         "--seconds", "0.5", "--elements-per-pe", "1024"],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = proc.stdout.strip().splitlines()[-1]
    assert "correct=True" in line, line
    found = ast.literal_eval(line.split("readers with a value: ", 1)[1])
    for name in ("chase_rounds.2x2", "contract_s.2x2", "descend_s.2x2",
                 "base_s.2x2", "ascend_s.2x2", "frontdoor_host_s.2x2",
                 "driver_gap_s.2x2"):
        assert name in found, found
