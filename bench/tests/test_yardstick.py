"""The benchmark's copies of the instance generator and the reference
equal the program's, so the yardstick measures what the program's own
tests trust, while no later change to the program can move it."""
import numpy as np
import pytest

import harness
from repro.core.listrank import instances, rank_list_seq

family = harness.load_module("instances", "list")
reference = harness.load_module("references", "rank_list")


@pytest.mark.parametrize("n,gamma,seed,num_lists", [
    (1, 1.0, 0, 1), (2, 1.0, 5, 1), (1000, 1.0, 1, 1), (4096, 0.0, 2, 1),
    (4096, 0.5, 3, 7), (1 << 14, 1.0, 2**31 + 11, 1)])
def test_gen_list_equals_program(n, gamma, seed, num_lists):
    ours = family.gen_list(n, gamma, seed=seed, num_lists=num_lists)
    theirs = instances.gen_list(n, gamma, seed=seed, num_lists=num_lists)
    for a, b in zip(ours, theirs):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_make_reads_the_configuration():
    config = harness.load_json("configs", "list-1chip")
    succ, rank = family.make(256, config, seed=9)
    assert np.array_equal(succ, instances.gen_list(256, 1.0, seed=9)[0])
    assert rank.dtype == np.int32 and rank.sum() == 255


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_reference_equals_program(seed):
    cases = [instances.gen_list(3000, 1.0, seed=seed),
             instances.gen_list(3000, 0.3, seed=seed, num_lists=5),
             instances.gen_random_lists(3000, 17, seed=seed, weighted=True)]
    for succ, rank in cases:
        ours = reference.rank_list_seq(succ, rank)
        theirs = rank_list_seq(succ, rank)
        for a, b in zip(ours, theirs):
            assert a.dtype == b.dtype and np.array_equal(a, b)


def test_reference_refuses_a_cycle():
    with pytest.raises(ValueError, match="cycle"):
        reference.rank_list_seq(np.array([1, 2, 0], np.int32),
                                np.ones(3, np.int32))


def test_compare_counts_each_wrong_element():
    succ, rank = instances.gen_list(500, 1.0, seed=4)
    expected = reference.reference(succ, rank)
    assert reference.compare(expected, expected) == {"wrong_ends": 0,
                                                     "wrong_ranks": 0}
    bad_rank = expected[1].copy()
    bad_rank[[3, 7]] += 1
    bad_succ = expected[0].copy()
    bad_succ[5] = (bad_succ[5] + 1) % 500
    assert reference.compare(expected, (bad_succ, bad_rank)) == {
        "wrong_ends": 1, "wrong_ranks": 2}
