"""Plain reference for ``rank_list``: sequential pointer jumping in
numpy, copied from the program's oracle so that the benchmark's
yardstick imports nothing of the program.

The answer for element i is (the terminal of i's list, the sum of the
weights from i to that terminal). Integer weights make the comparison
exact: ``compare`` counts every element whose list end or rank differs.
"""
from __future__ import annotations

import numpy as np


def rank_list_seq(succ: np.ndarray, rank: np.ndarray | None = None):
    """(succ_out, rank_out) of every list in ``succ`` by pointer jumping:
    after k steps s[i] is 2^k links ahead (clamped at the terminal) and
    w[i] the weight over the links passed. Raises on an element with two
    predecessors, a terminal with a weight, or a cycle."""
    succ = np.asarray(succ)
    n = succ.shape[0]
    idx = np.arange(n, dtype=succ.dtype)
    if rank is None:
        rank = (succ != idx).astype(np.int64)
    rank = np.asarray(rank)
    is_term = succ == idx
    if not np.all(rank[is_term] == 0):
        raise ValueError("terminal elements must carry weight 0")
    targets = succ[~is_term]
    if np.unique(targets).size != targets.size:
        raise ValueError(
            "an element has two predecessors (not a set of lists)")
    s = succ.astype(np.int64)
    w = rank.copy()
    for _ in range(max(int(n).bit_length(), 1) + 1):
        if np.all(is_term[s]):
            break
        w = w + w[s]
        s = s[s]
    # even cycles collapse to spurious fixed points under jumping, so
    # the check consults the original terminal set
    if not np.all(is_term[s]):
        raise ValueError("input contains a cycle (not a set of lists)")
    return s.astype(succ.dtype), w.astype(rank.dtype)


reference = rank_list_seq
#: the numbers ``compare`` returns, each a count of wrong elements
NUMBERS = ("wrong_ends", "wrong_ranks")


def compare(expected, answer) -> dict:
    """Counts of wrong answers in one solve: elements whose list end
    (``wrong_ends``) or rank (``wrong_ranks``) differs from the
    reference's ``expected`` pair. ``answer`` is the pair the timed
    call returned."""
    ref_succ, ref_rank = expected
    succ, rank = (np.asarray(a) for a in answer)
    if succ.shape != ref_succ.shape or rank.shape != ref_rank.shape:
        return {"wrong_ends": ref_succ.size, "wrong_ranks": ref_rank.size}
    return {"wrong_ends": int(np.count_nonzero(succ != ref_succ)),
            "wrong_ranks": int(np.count_nonzero(rank != ref_rank))}
