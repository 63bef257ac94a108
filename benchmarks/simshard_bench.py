"""simshard benchmark: how far can virtual p push on one host?

For each virtual PE count the harness runs the full solver on the
simshard backend (one process, one device, no XLA_FLAGS subprocesses),
and records:

  - compile + first-solve wall time and steady-state solve wall time
    (the emulation's practical limit is compile time and memory, both
    growing with p),
  - the traced collective counts via the simulated-collective markers
    (must stay the mesh program's counts — the coalescing invariant at
    every p),
  - solver round/message counters, which feed the same §2.6 modeled
    time as every other bench.

Usage: python benchmarks/simshard_bench.py   (BENCH_QUICK=1 for smoke).
Full mode writes benchmarks/results/simshard.json (committed); quick
mode writes simshard_quick.json (NOT committed).

Measured practical limit on this CPU container: p=512 cold-compiles in
~4 minutes; p=1024 blows past 25 minutes of XLA compile (the batched
mailbox transposes scale with p^2 x cap), so the committed sweep tops
out at 512 — that IS the answer to "how far can virtual p push on one
host" today, and the number to beat when attacking compile time.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).parent
sys.path.insert(0, str(HERE.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.listrank import (ListRankConfig, instances,  # noqa: E402
                                 introspect, rank_list_seq,
                                 rank_list_with_stats, sim_mesh)
from repro.core.listrank import api as api_lib  # noqa: E402
from repro.core.listrank import transport as transport_lib  # noqa: E402
from repro.core.listrank.exchange import MeshPlan  # noqa: E402

QUICK = os.environ.get("BENCH_QUICK", "0") == "1"
P_SIZES = (8, 64) if QUICK else (8, 64, 256, 512)
N_PER_PE = 256 if QUICK else 1024
RESULTS = HERE / "results"


def _trace_counts(p: int, n: int, cfg: ListRankConfig) -> dict:
    mesh = sim_mesh(p)
    plan = MeshPlan.from_mesh(mesh, ("pe",))
    m = n // p
    specs = api_lib.build_specs(cfg, plan, m, n, term_bound=1)
    import functools
    fn = functools.partial(api_lib._solve_sharded, plan=plan, cfg=cfg,
                           specs=specs, m=m)
    spec = P(("pe",))
    runner = transport_lib.device_run(mesh, ("pe",), fn,
                                      in_specs=(spec, spec, P()),
                                      out_specs=(spec, spec, P()))
    args = (jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32), jnp.int32(0))
    return introspect.collective_counts(runner, *args)


def bench_p(p: int) -> dict:
    n = p * N_PER_PE
    cfg = ListRankConfig(srs_rounds=1, local_contraction=True)
    succ, rank = instances.gen_list(n, gamma=1.0, seed=1)
    mesh = sim_mesh(p)

    t0 = time.perf_counter()
    s, r, stats = rank_list_with_stats(succ, rank, mesh, cfg=cfg,
                                       term_bound=1)
    cold_s = time.perf_counter() - t0
    s_ref, r_ref = rank_list_seq(succ, rank)
    ok = (np.array_equal(np.asarray(s), s_ref)
          and np.array_equal(np.asarray(r), r_ref))

    t0 = time.perf_counter()
    rank_list_with_stats(succ, rank, mesh, cfg=cfg, term_bound=1)
    warm_s = time.perf_counter() - t0

    counts = _trace_counts(p, n, cfg)
    row = {
        "p": p, "n": n, "n_per_pe": N_PER_PE, "correct": bool(ok),
        "cold_wall_s": cold_s, "warm_wall_s": warm_s,
        "collectives": counts,
        "rounds": stats["rounds"] // p,
        "chase_msgs": stats["chase_msgs"],
        "attempts": stats["attempts"],
    }
    print(f"simshard/p{p},{warm_s * 1e6:.1f},"
          f"cold_s={cold_s:.2f};a2a={counts.get('all_to_all', 0)};"
          f"rounds={row['rounds']};ok={ok}")
    return row


def main():
    enable_compile_cache()
    print("name,us_per_call,derived")
    rows = [bench_p(p) for p in P_SIZES]
    RESULTS.mkdir(exist_ok=True)
    out = RESULTS / ("simshard_quick.json" if QUICK else "simshard.json")
    out.write_text(json.dumps(rows, indent=1))
    print(f"# wrote {out}")
    if any(not r["correct"] for r in rows):
        sys.exit(1)


if __name__ == "__main__":
    main()
