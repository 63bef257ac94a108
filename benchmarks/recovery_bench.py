"""Recovery economics of the level-resumable solver.

Two measurements, in-process on the simshard backend (any p, no
subprocess workers):

1. **resume vs full restart**: prepare a level-boundary checkpoint by
   injecting a preemption after the last descend stage, then time (a) a
   full solve from scratch and (b) a resume from that checkpoint —
   restore cost included. The resume skips prep + every chase level, so
   its wall time is the tail of the schedule; the ratio is what a
   mid-solve fault costs with and without the checkpointable boundary
   state (DESIGN.md §11).
2. **sampled-splitter estimation**: with ``capacity_estimation=True``
   the pre-pass (tuner.estimate_capacities) sizes the mailboxes from an
   instance sample before the first attempt; every one of the paper's 5
   instance families must finish in ``attempts == 1`` at bench scale
   (the acceptance gate), and the measured per-hop slack is recorded.

Results land in benchmarks/results/recovery.json (committed from a
``BENCH_QUICK=1`` run; the flag is recorded in the artifact).
"""
import json
import os
import pathlib
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).parent
RESULTS = HERE / "results"
sys.path.insert(0, str(HERE.parent / "src"))

import numpy as np  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

QUICK = os.environ.get("BENCH_QUICK", "0") == "1"
P = 8 if QUICK else 16
NPE = 1 << 11 if QUICK else 1 << 14
ITERS = 2 if QUICK else 3
SRS_ROUNDS = 2

FAMILIES = [
    ("list_g0.0", lambda n: _list(n, 0.0)),
    ("list_g0.5", lambda n: _list(n, 0.5)),
    ("list_g1.0", lambda n: _list(n, 1.0)),
    ("euler_local", lambda n: _euler(n, True)),
    ("euler_random", lambda n: _euler(n, False)),
]


def _list(n, gamma):
    from repro.core.listrank import instances
    return instances.gen_list(n, gamma=gamma, seed=1)


def _euler(n, locality):
    from repro.core.listrank import instances
    s, r, _ = instances.gen_euler_tour(n // 2 + 1, seed=2,
                                       locality=locality)
    return instances.pad_to_multiple(s, r, P)


def main():
    enable_compile_cache()
    from repro.core.listrank import (FaultSpec, ListRankConfig,
                                     rank_list_with_stats, sim_mesh, tuner)
    from repro.core.listrank.exchange import MeshPlan
    from repro.runtime.fault_tolerance import (Preempted, SolveSupervisor,
                                               SolveSupervisorConfig)

    mesh = sim_mesh((P,), ("pe",))
    cfg = ListRankConfig(srs_rounds=SRS_ROUNDS, local_contraction=True)
    n = P * NPE
    succ, rank = _list(n, 1.0)

    # ---- 1. resume-from-level-k vs full restart -----------------------
    # warm the stage compile cache, then time steady-state runs.
    rank_list_with_stats(succ, rank, mesh, cfg=cfg)
    t_full = min(_timed(lambda: rank_list_with_stats(succ, rank, mesh,
                                                     cfg=cfg))
                 for _ in range(ITERS))

    with tempfile.TemporaryDirectory() as d:
        prep = SolveSupervisor(SolveSupervisorConfig(ckpt_dir=d))
        try:
            rank_list_with_stats(
                succ, rank, mesh, cfg=cfg, supervisor=prep,
                inject=FaultSpec("preempt", stage="descend",
                                 level=SRS_ROUNDS - 1))
        except Preempted:
            pass
        boundary_idx = prep.latest_meta()["idx"]

        def resume_once():
            # huge cadence: the timed resume restores the prepared
            # checkpoint but writes none of its own, so every iteration
            # resumes from the same boundary.
            sv = SolveSupervisor(SolveSupervisorConfig(ckpt_dir=d,
                                                       ckpt_every=10 ** 9))
            _, _, st = rank_list_with_stats(succ, rank, mesh, cfg=cfg,
                                            supervisor=sv)
            assert st["recovery"]["resumed_from"] == boundary_idx
            return st

        resume_once()  # warm restore path
        t_resume = min(_timed(resume_once) for _ in range(ITERS))

    speedup = t_full / max(t_resume, 1e-9)
    print(f"recovery/resume,p={P},n={n},boundary_idx={boundary_idx},"
          f"full={t_full * 1e3:.1f}ms,resume={t_resume * 1e3:.1f}ms,"
          f"speedup={speedup:.2f}x")

    # ---- 2. estimation pre-pass: attempts == 1 on all families --------
    plan = MeshPlan.from_mesh(mesh, ("pe",))
    m = n // P
    est_cfg = cfg.with_(capacity_estimation=True)
    est_rows = []
    for fam, gen in FAMILIES:
        s_f, r_f = gen(n)
        est = tuner.estimate_capacities(np.asarray(s_f), plan,
                                        s_f.shape[0] // P, est_cfg)
        _, _, st = rank_list_with_stats(s_f, r_f, mesh, cfg=est_cfg)
        est_rows.append({"family": fam, "n": int(s_f.shape[0]),
                         "attempts": st["attempts"],
                         "hop_slack": list(est.hop_slack),
                         "max_frac": list(est.max_frac),
                         "sample_size": est.sample_size})
        print(f"recovery/estimation/{fam},attempts={st['attempts']},"
              f"hop_slack={est.hop_slack[0]:.2f}")

    # gates before touching the committed artifact
    assert speedup > 1.0, \
        f"resume ({t_resume:.3f}s) no faster than full restart ({t_full:.3f}s)"
    bad = [r["family"] for r in est_rows if r["attempts"] != 1]
    assert not bad, f"estimation pre-pass failed to avoid retries on {bad}"

    RESULTS.mkdir(exist_ok=True)
    out = {"quick": QUICK, "p": P, "n_per_pe": NPE,
           "srs_rounds": SRS_ROUNDS,
           "resume": {"boundary_idx": boundary_idx,
                      "t_full_s": t_full, "t_resume_s": t_resume,
                      "speedup": speedup},
           "estimation": est_rows}
    dst = RESULTS / "recovery.json"
    dst.write_text(json.dumps(out, indent=1))
    print(f"# wrote {dst}")


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


if __name__ == "__main__":
    main()
