"""One CPU rehearsal of a cell with the program broken underneath the
timed path in one way; prints ``correct=<bool>`` and what was compared.

  python bench/tests/_fault_run.py <workload> <fault>

Faults: ``none``; ``unchanged_state`` (the last stage returns its
input state: the instance unranked); ``half_batch`` (the second half of
the elements left unranked); ``altered_answer`` (one rank altered where
the last stage produces it); ``no_exchange`` (every all_to_all between
PEs returns its input). Runs as a subprocess because the number of CPU
devices is fixed before JAX starts.
"""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402


def break_last_stage(transform):
    """Wrap the staged driver's ``post`` stage so that its outputs pass
    through ``transform(outputs, succ_in, rank_in)``."""
    from repro.core.listrank import resume
    orig = resume._stage_call

    def stage_call(mesh, plan, cfg, stage, specs, m, state, succ_d, rank_d,
                   seed):
        runner, args = orig(mesh, plan, cfg, stage, specs, m, state, succ_d,
                            rank_d, seed)
        if stage.kind != "post":
            return runner, args

        def broken(*a):
            return transform(runner(*a), succ_d, rank_d)

        broken.lower = runner.lower
        return broken, args

    resume._stage_call = stage_call


def apply(fault: str) -> None:
    import jax.numpy as jnp
    if fault == "none":
        return
    if fault == "unchanged_state":
        break_last_stage(lambda out, s, r: (s, r) + tuple(out[2:]))
    elif fault == "half_batch":
        def half(out, s, r):
            rest = jnp.arange(s.shape[0]) >= s.shape[0] // 2
            return (jnp.where(rest, s, out[0]), jnp.where(rest, r, out[1])) \
                + tuple(out[2:])
        break_last_stage(half)
    elif fault == "altered_answer":
        break_last_stage(lambda out, s, r: (out[0], out[1].at[0].add(1))
                         + tuple(out[2:]))
    elif fault == "no_exchange":
        from repro.core.listrank import transport
        transport.MeshTransport.all_to_all = (
            lambda self, x, axes, split_axis, concat_axis, tiled=True: x)
    else:
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> None:
    workload, fault = sys.argv[1:3]
    chips = harness.load_cell(workload).chips
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                               f"{chips}")
    apply(fault)
    from rehearse import rehearse
    correct, compared, _ = rehearse(workload, seed=5, seconds=0.5,
                                    elements_per_pe=512, pool=2,
                                    trace=False)
    print(f"correct={correct} compared={compared}", flush=True)


if __name__ == "__main__":
    main()
