"""A whole run of each cell, on the CPU and without the harness's look
for a chip, with the timed path broken underneath: ``correct`` must
come out false for every fault the cell can have, and true unbroken.
One chip has no exchange between chips, so ``no_exchange`` is a fault
of the 2x2 cell only."""
import subprocess
import sys
from pathlib import Path

import pytest

RUNNER = Path(__file__).with_name("_fault_run.py")
ONE_CHIP = ["list-1chip.n20-loop", "list-1chip.n14-stream"]
FAULTS = ["unchanged_state", "half_batch", "altered_answer"]
CASES = ([(w, f) for w in ONE_CHIP for f in ["none"] + FAULTS]
         + [("list-2x2.n20x4-loop", f)
            for f in ["none"] + FAULTS + ["no_exchange"]])


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_makes_the_run_incorrect(workload, fault):
    proc = subprocess.run([sys.executable, str(RUNNER), workload, fault],
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("correct=")]
    assert proc.returncode == 0 and lines, proc.stderr[-3000:]
    assert lines[-1].startswith(f"correct={fault == 'none'} "), lines[-1]
