"""Flight recorder demo: one traced list-ranking solve, end to end.

  PYTHONPATH=src python examples/trace_solve.py [profile_dir]

Runs sparse-ruling-set on the simshard backend with the span tracer
attached, then prints the three artifacts the observability layer
produces for every solve:

  1. the span tree — prep/descend@k/base/ascend@k/post stage spans with
     their per-attempt children and wall timings;
  2. the model-vs-measured residual table — each stage's observed wall
     time next to its §2.6 predicted time (alpha/beta under the active
     MachineModel, collective footprint counted statically from the
     stage jaxpr);
  3. the capacity headroom report and measured-vs-modeled skew table —
     the device telemetry plane (cfg.telemetry=True): observed max
     mailbox fill vs compiled cap per stage/family/hop, and the
     per-hop destination skew vs the uniform model;
  4. the metrics registry — the solver's host stats ingested into one
     typed counter/gauge schema.

The solve runs under a ``jax.profiler`` trace written to
``profile_dir`` (default ``trace_solve_profile``): the tracer puts
every span on the trace's host plane as a ``repro:<cat>/<name>``
annotation, beside the device's ops (open it in TensorBoard's profile
plugin or https://ui.perfetto.dev).

Tracing is host-side only: the traced program is byte-identical with
the tracer on or off (asserted continuously by tests/test_obs.py).
"""
import os
import sys

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.listrank import (ListRankConfig, instances,  # noqa: E402
                                 rank_list_seq, rank_list_with_stats,
                                 sim_mesh)
from repro import obs  # noqa: E402


def main():
    enable_compile_cache()
    out_dir = sys.argv[1] if len(sys.argv) > 1 else "trace_solve_profile"
    p, n = 8, 1 << 14
    succ, rank = instances.gen_list(n, gamma=1.0, seed=0)
    cfg = ListRankConfig(algorithm="srs", srs_rounds=2,
                         local_contraction=True, telemetry=True)
    mesh = sim_mesh(p)

    tracer = obs.Tracer()
    with jax.profiler.trace(out_dir):
        succ_out, rank_out, stats = rank_list_with_stats(
            succ, rank, mesh, cfg=cfg, seed=1, tracer=tracer)

    s_ref, r_ref = rank_list_seq(succ, rank)
    assert np.array_equal(np.asarray(succ_out), s_ref)
    assert np.array_equal(np.asarray(rank_out), r_ref)
    print(f"ranked n={n} on p={p} virtual PEs "
          f"({stats['attempts']} attempt(s)); matches the oracle\n")

    print("span tree:")
    for line in obs.span_tree_lines(tracer):
        print("  " + line)

    rows = obs.residual_rows(tracer)
    print()
    print(obs.format_residual_table(
        rows, title="model-vs-measured (§2.6, "
                    f"{cfg.machine.name} constants)"))
    summ = obs.residual_summary(rows)
    print(f"  total measured {summ['measured_s'] * 1e3:.2f}ms vs "
          f"predicted {summ['predicted_s'] * 1e6:.1f}us — large ratios "
          f"are expected here: the model prices network time on the "
          f"paper's machine, the measurement is single-CPU dispatch")

    tele = stats.get("telemetry", {})
    print()
    print(obs.format_headroom_table(tele.get("headroom", [])))

    from repro.core.listrank.exchange import MeshPlan  # noqa: E402
    from repro.obs import cost as cost_lib  # noqa: E402
    plan = MeshPlan.from_mesh(mesh, tuple(mesh.axis_names))
    print()
    print(obs.format_skew_table(
        obs.skew_rows(cost_lib.hop_sizes_of(plan), tele.get("stages", [])),
        title="measured-vs-modeled destination skew (uniform model)"))

    print("\nmetrics registry:")
    for metric in sorted(tracer.metrics, key=lambda m: m.name):
        snap = metric.snapshot()
        snap.pop("help", None)
        print(f"  {metric.name:<40} {metric.kind:<9} {snap}")

    events = obs.profile_spans(out_dir)
    starts = [t0 for _, t0, _ in events]
    assert [name for name, _, _ in events] == [
        f"{sp.cat}/{sp.name}" for sp in tracer.spans]
    assert starts == sorted(starts)
    print(f"\nwrote a profiler trace under {out_dir}: its host plane holds "
          f"the {len(events)} spans as {obs.ANNOTATION_PREFIX}<cat>/<name> "
          f"annotations")


if __name__ == "__main__":
    main()
