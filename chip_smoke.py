"""Smoke test of the list-ranking solve on TPU chips.

  python chip_smoke.py                 # one chip
  python chip_smoke.py --chips 4       # the 2x2 host: the mesh phases only
  python chip_smoke.py --log2-n 16     # a smaller list, for a quick run

Runs in one process and starts none. It prints the device first and
exits non-zero, with no result line, where JAX finds no TPU: there is no
CPU fallback. It then ranks the paper's List(n, gamma=1) instance
(integer weights) through ``rank_list_with_stats`` on the PE mesh of
``make_pe_mesh`` and checks every output exactly against the sequential
oracle ``rank_list_seq``.

One chip: n = 2^LOG2_N (or 2^K with ``--log2-n K``), whose fullest
stage program must fit 60% of the chip's HBM by the compiler's memory
analysis. It solves with the default ``ListRankConfig()`` and with
``local_contraction=False``: at p=1 contraction alone absorbs the whole
list, so the second run is the one in which the ruler chase, the
recursion and the base case do the work. Then one small solve per
Pallas kernel, with the kernel selected and present in the compiled
stage programs.

``--chips 4``: n = 4 x the one-chip size on the 2x2 ``("row", "col")``
mesh, with direct and with grid indirection.

Every stage program is compiled before its solve, so each solve runs
warm. It runs with the host-side span tracer on, which prints each
stage's device-synced wall time. Times printed are set-up and smoke
readings, not benchmark numbers. The last line is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

#: list elements per chip, log2, and why (PERF.md: at 2^26 the fullest
#: stage program needs 9.11 GiB of a v5e's 15.75 GiB)
LOG2_N = 24
SIZE_REASON = ("2^26 fits a v5e's HBM, but its default-config solve "
               "alone takes 277 s and the whole run must end within "
               "1200 s")
HBM_SHARE = 0.6               # of the device's memory, per program
PACK_N = 1 << 18              # mailbox_pack phase size
SEED = 0
#: stage programs compiled at once. Each TPU compile runs on one host
#: thread; a process compiling one peaked at 2.6 GB of host memory at
#: 2^20 elements per chip and 3.7 GB at 2^22 (a v5e compile on a CPU
#: host), so each gets 10 GiB.
COMPILE_WORKERS = max(1, min(
    (os.cpu_count() or 2) // 2,
    os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE") // (10 << 30)))


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--log2-n", type=int, choices=range(12, 27),
                    default=LOG2_N, metavar="K",
                    help=f"list elements per chip, 2^K (default {LOG2_N})")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform!r} devices")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, "
             f"JAX found {len(devices)}")

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_pe_mesh

    print(f"compile cache: {enable_compile_cache()}, at most "
          f"{jax.config.jax_compilation_cache_max_size} bytes "
          f"(-1: unbounded)", flush=True)
    mesh = make_pe_mesh(devices[:args.chips])
    hbm = dev.memory_stats()["bytes_limit"]
    n = args.chips << args.log2_n
    why = (SIZE_REASON if args.log2_n == LOG2_N
           else f"--log2-n {args.log2_n}")
    print(f"chosen n = {args.chips} x 2^{args.log2_n} = {n}: {why}",
          flush=True)
    if args.chips == 1:
        ok = one_chip(n, mesh, hbm, dev.device_kind)
    else:
        ok = four_chips(n, mesh, hbm, dev.device_kind)
    if not ok:
        fail("a phase failed")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


def compile_stages(n, mesh, cfg, ind=None):
    """[(stage label, compiled)] for every stage program of the solve,
    compiled COMPILE_WORKERS at a time."""
    from repro.core.listrank import api

    lowered = api.lower_stages(n, mesh, cfg=cfg, indirection=ind)
    with ThreadPoolExecutor(COMPILE_WORKERS) as pool:
        return list(pool.map(lambda lw: (lw[0], lw[1].compile()), lowered))


def compile_and_fit(n: int, mesh, cfgs, hbm: int) -> None:
    """Compile every stage program of each (config, indirection) in
    ``cfgs`` and fail unless each fits HBM_SHARE of ``hbm`` by
    ``memory_analysis``. The solves then run these executables."""
    t0 = time.time()
    programs = 0
    peak, where = 0, ""
    for cfg, ind in cfgs:
        for label, compiled in compile_stages(n, mesh, cfg, ind):
            ma = compiled.memory_analysis()
            programs += 1
            need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
            if need > peak:
                peak, where = need, label
    fits = peak <= HBM_SHARE * hbm
    print(f"n={n} (2^{n.bit_length() - 1}): fullest stage program {where} "
          f"needs {peak / 2**30:.2f} GiB per device, "
          f"{'within' if fits else 'over'} {HBM_SHARE:.0%} of "
          f"{hbm / 2**30:.2f} GiB", flush=True)
    print(f"compile (set-up): {time.time() - t0:.1f} s for {programs} "
          f"stage programs", flush=True)
    if not fits:
        fail(f"the stage programs at n={n} do not fit")


def solve_and_check(name, succ, rank, ref, mesh, cfg, ind, kind) -> bool:
    """One front-door solve, timed to ``block_until_ready`` and checked
    exactly against the oracle."""
    from repro.core.listrank import rank_list_with_stats
    from repro.obs import Tracer

    tracer = Tracer()
    t0 = time.time()
    s_out, r_out, stats = rank_list_with_stats(succ, rank, mesh, cfg=cfg,
                                               indirection=ind, seed=SEED,
                                               tracer=tracer)
    jax.block_until_ready((s_out, r_out))
    wall = time.time() - t0
    exact = (np.array_equal(np.asarray(s_out), ref[0])
             and np.array_equal(np.asarray(r_out), ref[1]))
    spans = {len(s_out.sharding.device_set), len(r_out.sharding.device_set)}
    stages = [(sp.args["stage"], sp.args["wall_s"]) for sp in tracer.spans
              if sp.cat == "stage-attempt" and "wall_s" in sp.args]
    print(f"{name}: exact={exact} outputs on {sorted(spans)} devices; "
          f"attempts={stats['attempts']} rounds={stats['rounds']} "
          f"sub_size={stats['sub_size']}; solve {wall:.2f} s on {kind}, "
          f"of it stages (device-synced) "
          + ", ".join(f"{label} {t:.2f} s" for label, t in stages),
          flush=True)
    return exact and spans == {mesh.size}


def oracle(n: int):
    from repro.core.listrank import instances, rank_list_seq

    t0 = time.time()
    succ, rank = instances.gen_list(n, gamma=1.0, seed=SEED)
    ref = rank_list_seq(succ, rank)
    print(f"instance List(n={n}, gamma=1) and oracle: "
          f"{time.time() - t0:.1f} s on the host", flush=True)
    return succ, rank, ref


def one_chip(n: int, mesh, hbm: int, kind: str) -> bool:
    from repro.core.listrank import ListRankConfig

    default = ListRankConfig()
    chase = default.with_(local_contraction=False)
    compile_and_fit(n, mesh, [(default, None), (chase, None)], hbm)
    succ, rank, ref = oracle(n)
    ok = solve_and_check("default config", succ, rank, ref, mesh, default,
                         None, kind)
    ok &= solve_and_check("local_contraction=False", succ, rank, ref, mesh,
                          chase, None, kind)
    del succ, rank, ref
    ok &= kernel_phases(mesh, kind)
    return ok


def kernel_phases(mesh, kind: str) -> bool:
    """A solve per Pallas kernel, at a size whose stage programs the
    kernel's size predicate admits; the compiled programs must hold it."""
    from repro.core.listrank import ListRankConfig, instances
    from repro.core.listrank import rank_list_seq
    from repro.kernels.local_chase import ops as lc_ops

    # pointer doubling keeps each phase to three stage programs
    doubling = ListRankConfig(algorithm="doubling")
    phases = [
        # local contraction's doubling over the whole PE-local array
        ("use_pallas (local_chase)", lc_ops.MAX_M,
         doubling.with_(use_pallas=True)),
        # with no contraction every doubling gather packs its requests
        ("use_pallas_pack (mailbox_pack)", PACK_N,
         doubling.with_(use_pallas_pack=True, local_contraction=False)),
    ]
    ok = True
    for name, n, cfg in phases:
        calls = sum(
            sum("tpu_custom_call" in line
                for line in compiled.as_text().splitlines())
            for _, compiled in compile_stages(n, mesh, cfg))
        succ, rank = instances.gen_list(n, gamma=1.0, seed=SEED)
        ref = rank_list_seq(succ, rank)
        print(f"{name}: n={n}, {calls} Pallas kernel ops in the compiled "
              f"stage programs", flush=True)
        ok &= calls > 0
        ok &= solve_and_check(name, succ, rank, ref, mesh, cfg, None, kind)
    return ok


def four_chips(n: int, mesh, hbm: int, kind: str) -> bool:
    from repro.core.listrank import IndirectionSpec, ListRankConfig

    cfg = ListRankConfig()
    grid = IndirectionSpec.grid(("row", "col"))
    compile_and_fit(n, mesh, [(cfg, None), (cfg, grid)], hbm)
    succ, rank, ref = oracle(n)
    ok = solve_and_check("2x2 direct", succ, rank, ref, mesh, cfg, None,
                         kind)
    ok &= solve_and_check("2x2 grid indirection", succ, rank, ref, mesh,
                          cfg, grid, kind)
    return ok


if __name__ == "__main__":
    main()
