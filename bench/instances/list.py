"""The paper's List(n, gamma) instance family (arXiv:2606.09318, §3
Input Instances), copied from the program's generator so that no PR to
the program can change what the benchmark ranks.

``make`` builds one instance of ``n`` elements from a seed: the identity
chain ``i -> i+1`` with a ``gamma`` fraction of its labels permuted at
random, unit int32 weights, and one terminal that points to itself with
weight 0.
"""
from __future__ import annotations

import numpy as np


def gen_list(n: int, gamma: float, seed: int = 0, num_lists: int = 1):
    """List(n, gamma): chain succ[i]=i+1 with a random relabeling applied
    to a gamma-fraction of positions; ``num_lists`` cuts the chain into
    that many lists at evenly spaced points."""
    if not 0.0 <= gamma <= 1.0:
        raise ValueError("gamma must be in [0,1]")
    if n == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.int32)
    rng = np.random.default_rng(seed)
    labels = np.arange(n, dtype=np.int64)
    k = int(round(gamma * n))
    if k > 1:
        pos = rng.choice(n, size=k, replace=False)
        labels[pos] = labels[rng.permutation(pos)]
    # chain over labels: labels[j] -> labels[j+1], self-loop at cuts
    succ = np.empty(n, dtype=np.int64)
    succ[labels[:-1]] = labels[1:]
    succ[labels[-1]] = labels[-1]
    cuts = np.linspace(0, n, num_lists + 1).astype(np.int64)[1:]
    ends = cuts - 1
    ends = ends[(ends >= 0) & (ends < n)]
    succ[labels[ends]] = labels[ends]
    rank = (succ != np.arange(n)).astype(np.int64)
    return succ.astype(np.int32), rank.astype(np.int32)


def make(n: int, config: dict, seed: int):
    """(succ, rank) of one instance of the configuration's family."""
    return gen_list(n, config["gamma"], seed=seed,
                    num_lists=config.get("num_lists", 1))
