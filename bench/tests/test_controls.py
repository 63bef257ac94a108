"""The controls, on the CPU at sizes a test run holds: the reference's
own pointer jumping in the program's place comes out correct, and each
control (int16 ranks, one doubling step short) comes out not correct,
through the same harness and comparison as a benchmark run."""
import pytest

import controls
from rehearse import rehearse

CASES = [
    # (control options, elements per PE, correct?)
    (dict(dtype="int32"), 1 << 16, True),
    (dict(dtype="int16"), 1 << 16, False),   # ranks pass 2^15 - 1
    (dict(dtype="int16"), 1 << 14, True),    # ranks stay below 2^15
    (dict(dtype="int32", short=1), 1 << 10, False),
    (dict(dtype="int32", short=1), 1 << 16, False),
]


@pytest.mark.parametrize("kw,m,expect", CASES)
def test_control_on_one_chip(kw, m, expect):
    import jax
    entry = controls.Jumping(jax.devices()[:1], **kw)
    correct, compared, _ = rehearse("list-1chip.n20-loop", seed=3,
                                    seconds=0.2, elements_per_pe=m, pool=2,
                                    trace=False, entry=entry)
    assert correct is expect, compared
    if not expect:
        assert compared["wrong_ranks"]["value"] > 0


@pytest.mark.parametrize("n,steps", [(1, 0), (2, 0), (3, 1), (4, 2),
                                     (5, 2), (1 << 20, 20),
                                     ((1 << 20) + 1, 20)])
def test_steps_needed(n, steps):
    assert controls.steps_needed(n) == steps
