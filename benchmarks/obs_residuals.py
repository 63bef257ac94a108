"""Model-vs-measured residual gate: one traced solve per instance
family, per-stage §2.6 predicted-vs-observed table.

Runs a full traced SRS solve (simshard backend, in-process) for every
paper instance family — List(γ∈{0, 0.5, 1}) and both Euler-tour tree
models — and emits the flight recorder's per-stage residual table.
The gate (CI BENCH_QUICK step) is structural: every scheduled stage of
every family must produce a row with a finite measured time and a
prediction, or the bench exits nonzero. Absolute residuals are
reported, not gated — this container measures python-dispatch wall
time on one CPU, so measured/predicted ratios are large by
construction; the artifact records them for trend tracking.

Results land in benchmarks/results/obs_residuals.json
(obs_residuals_quick.json under BENCH_QUICK=1).
"""
import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).parent
RESULTS = HERE / "results"
sys.path.insert(0, str(HERE.parent / "src"))

import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.listrank import (ListRankConfig, instances,  # noqa: E402
                                 rank_list_with_stats, sim_mesh)
from repro.core.listrank import resume as resume_lib  # noqa: E402
from repro.obs import (Tracer, format_residual_table,  # noqa: E402
                       residual_rows, residual_summary)
from repro.obs import telemetry as tele_lib  # noqa: E402

QUICK = os.environ.get("BENCH_QUICK", "0") == "1"
P = 8
NPE = 1 << 9 if QUICK else 1 << 13

#: all five families — the gate requires every one, in both modes.
FAMILIES = [
    ("list_g0.0", {"instance": "list", "gamma": 0.0}),
    ("list_g0.5", {"instance": "list", "gamma": 0.5}),
    ("list_g1.0", {"instance": "list", "gamma": 1.0}),
    ("euler_local", {"instance": "euler_local"}),
    ("euler_random", {"instance": "euler_random"}),
]


def make_instance(fam_kw, n):
    if fam_kw["instance"] == "list":
        return instances.gen_list(n, gamma=fam_kw["gamma"], seed=1)
    succ, rank, _ = instances.gen_euler_tour(
        n // 2 + 1, seed=1, locality=fam_kw["instance"] == "euler_local")
    return instances.pad_to_multiple(succ, rank, P)[:2]


def main():
    enable_compile_cache()
    RESULTS.mkdir(exist_ok=True)
    n = NPE * P
    cfg = ListRankConfig(algorithm="srs", srs_rounds=2,
                         local_contraction=True)
    mesh = sim_mesh(P)
    sched_labels = [st.label for st in resume_lib.schedule_for(
        cfg.with_(algorithm="srs"))]
    records = []
    failures = []
    headroom_report = []
    for fam, fam_kw in FAMILIES:
        succ, rank = make_instance(fam_kw, n)
        tr = Tracer(meta={"name": f"obs_residuals/{fam}", "family": fam})
        _, _, stats = rank_list_with_stats(succ, rank, mesh, cfg=cfg,
                                           seed=1, tracer=tr)
        rows = residual_rows(tr)
        print(format_residual_table(rows, title=f"== {fam} (n={n}, p={P})"))
        summ = residual_summary(rows)
        covered = {r["stage"] for r in rows}
        missing = [lbl for lbl in sched_labels if lbl not in covered]
        ok = (not missing
              and all(np.isfinite(r["measured_s"]) and r["measured_s"] >= 0
                      and np.isfinite(r["predicted_s"]) for r in rows))
        if not ok:
            failures.append((fam, missing))

        # telemetry plane: the same solve with device counters on —
        # every scheduled stage must report finite utilization, and on
        # a first-attempt-clean solve no observed fill may exceed its
        # compiled cap (the headroom report's acceptance gate).
        _, _, tstats = rank_list_with_stats(
            succ, rank, mesh, cfg=cfg.with_(telemetry=True), seed=1)
        tele = tstats.get("telemetry", {})
        stages = tele.get("stages", [])
        tele_missing = [lbl for lbl in sched_labels
                        if lbl not in {s["label"] for s in stages}]
        tele_finite = all(np.isfinite(s["util_max"])
                          and np.isfinite(s["util_mean"]) for s in stages)
        hrows = tele.get("headroom", [])
        worst_fill = max((r["fill_max"] for r in hrows), default=0.0)
        tele_ok = (not tele_missing and tele_finite
                   and (tstats["attempts"] > 1 or worst_fill <= 1.0))
        if not tele_ok:
            failures.append((fam, {"telemetry_missing": tele_missing,
                                   "finite": tele_finite,
                                   "worst_fill": worst_fill}))
        headroom_report.append(
            f"== {fam} (n={n}, p={P}, attempts={tstats['attempts']})\n"
            + tele_lib.format_headroom_table(hrows))
        records.append({"family": fam, "n": n, "p": P, "quick": QUICK,
                        "rows": rows, "summary": summ,
                        "attempts": stats["attempts"], "ok": ok,
                        "telemetry": {"stages": len(stages),
                                      "worst_fill": worst_fill,
                                      "headroom": hrows,
                                      "ok": tele_ok}})
        print(f"obs/{fam},{summ['measured_s'] * 1e6:.1f},"
              f"predicted_s={summ['predicted_s']:.6f};"
              f"stages={summ['stages']};ok={int(ok)};"
              f"tele_worst_fill={worst_fill:.3f};tele_ok={int(tele_ok)}")

    hr_path = RESULTS / ("headroom_quick.txt" if QUICK else "headroom.txt")
    hr_path.write_text("\n\n".join(headroom_report) + "\n")
    print(f"# wrote {hr_path}")

    out = RESULTS / ("obs_residuals_quick.json" if QUICK
                     else "obs_residuals.json")
    out.write_text(json.dumps(records, indent=1))
    print(f"# wrote {out}")
    if failures:
        print(f"RESIDUAL GATE FAILED: {failures}", file=sys.stderr)
        sys.exit(1)
    print(f"# residual gate OK: all {len(FAMILIES)} families produced "
          f"complete per-stage tables and in-cap telemetry headroom")


if __name__ == "__main__":
    main()
