"""Exchange-layer microbenchmark: collectives and per-round time.

Routes a realistic chase-round message queue (destination distribution
drawn from the Fig-3 weak-scaling instance: gamma=1 random list,
n_per_pe elements per PE) through ``exchange.route`` on direct and
2D-grid indirection, with the packed wire format on and off, and
records

  * the number of ``all_to_all`` collectives per routing round,
    counted by jaxpr inspection (the §2.6 alpha term), and
  * measured wall time per round on the host-device mesh (CPU "virtual
    PEs" here — trends, not TPU predictions).

Output: ``name,us_per_call,derived`` CSV lines (harness contract) and
benchmarks/results/exchange.json. Standalone:

  BENCH_QUICK=1 python benchmarks/exchange_bench.py
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).parent
QUICK = os.environ.get("BENCH_QUICK", "0") == "1"
# quick mode uses the p=4 point of the Fig-3 weak-scaling sweep — fewer
# virtual devices per core => far less scheduler noise in the timings.
P_BENCH = 4 if QUICK else 16
MESH = (2, 2) if QUICK else (4, 4)
NPE = 1 << 13 if QUICK else 1 << 15
ROUNDS = 7 if QUICK else 12
CHAIN = 8  # route rounds chained inside one jitted call

os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={P_BENCH}")
sys.path.insert(0, str(HERE.parent / "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.core.listrank import analysis, instances, introspect  # noqa: E402
from repro.core.listrank.api import CHASE_WIRE_WORDS  # noqa: E402
from repro.core.listrank.config import IndirectionSpec  # noqa: E402
from repro.core.listrank.exchange import MeshPlan, route  # noqa: E402

AXES = ("row", "col")


def chase_queue(n: int, p: int, seed: int = 1):
    """A chase-round message batch over the Fig-3 instance: targets are
    successor ids of random elements, i.e. the real wave-destination
    distribution of the weak-scaling run."""
    succ, rank = instances.gen_list(n, gamma=1.0, seed=seed)
    rng = np.random.default_rng(seed + 1)
    m = n // p
    q = max(64, m // 32)  # ~queue load of a chase round per PE
    src = rng.integers(0, n, p * q)
    payload = {
        "target": jnp.asarray(succ[src], jnp.int32),
        "ruler": jnp.asarray(src, jnp.int32),
        "weight": jnp.asarray(rank[src], jnp.int32),
    }
    dest = jnp.asarray(succ[src] // m, jnp.int32)
    valid = jnp.ones(p * q, bool)
    return payload, dest, valid, q


def build_fn(mesh, plan, caps, keys, chain=1):
    q = None

    def fn(*leaves):
        pl = dict(zip(keys, leaves[:-2]))
        dest, valid = leaves[-2], leaves[-1]
        n = dest.shape[0]
        acc = jnp.int32(0)
        for _ in range(chain):
            d, dv, lo, st = route(plan, caps, pl, dest, valid)
            # data dependency between rounds so XLA cannot collapse them
            pl = dict(pl, ruler=pl["ruler"] ^ d["ruler"][:n])
            acc = acc + jnp.sum(jnp.where(dv, d["ruler"], 0))
        return acc

    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(P(AXES) for _ in range(len(keys) + 2)),
        out_specs=P(), check_vma=False)


def main():
    enable_compile_cache()
    mesh = make_mesh(MESH, AXES)
    n = NPE * P_BENCH
    payload, dest, valid, q = chase_queue(n, P_BENCH)
    keys = sorted(payload.keys())
    args = [payload[k] for k in keys] + [dest, valid]
    results = []
    print("name,us_per_call,derived")
    for ind_name, ind, hops in (
            ("direct", None, 1),
            ("grid", IndirectionSpec.grid(AXES), 2)):
        caps = [q] if hops == 1 else [q, 4 * q]
        per = {}
        for packed in (True, False):
            plan = MeshPlan.from_mesh(mesh, AXES, ind, wire_packing=packed)
            coll = introspect.collective_counts(
                build_fn(mesh, plan, caps, keys), *args).get("all_to_all", 0)
            jfn = jax.jit(build_fn(mesh, plan, caps, keys, chain=CHAIN))
            jax.block_until_ready(jfn(*args))
            times = []
            for _ in range(ROUNDS):
                t0 = time.perf_counter()
                jax.block_until_ready(jfn(*args))
                times.append(time.perf_counter() - t0)
            # min over repetitions: robust against the oversubscribed
            # virtual-device scheduling noise of the CPU harness
            us = float(np.min(times)) / CHAIN * 1e6
            label = "packed" if packed else "unpacked"
            per[label] = dict(us_per_round=us, all_to_all=coll)
            print(f"exchange/{ind_name}/{label},{us:.1f},"
                  f"all_to_all={coll};hops={hops}")
        # alpha-beta modeled per-round comm time (§2.6, SuperMUC-like
        # constants — the CPU wall numbers are virtual-PE scheduling
        # noise at small sizes; the model is what carries the trend,
        # same methodology as run.py).
        m = analysis.SUPERMUC
        words = CHASE_WIRE_WORDS * q
        startup = P_BENCH ** (1.0 / hops)
        for label in per:
            per[label]["modeled_us"] = 1e6 * (
                m.alpha * per[label]["all_to_all"] * startup
                + m.beta * words)
        ratio = per["unpacked"]["all_to_all"] / max(
            per["packed"]["all_to_all"], 1)
        speedup = per["unpacked"]["us_per_round"] / max(
            per["packed"]["us_per_round"], 1e-9)
        speedup_model = per["unpacked"]["modeled_us"] / max(
            per["packed"]["modeled_us"], 1e-9)
        print(f"exchange/{ind_name}/summary,"
              f"{per['packed']['us_per_round']:.1f},"
              f"collective_ratio={ratio:.1f};speedup={speedup:.2f};"
              f"modeled_speedup={speedup_model:.2f}")
        results.append(dict(indirection=ind_name, hops=hops, q_per_pe=q,
                            n=n, p=P_BENCH, ratio=ratio, speedup=speedup,
                            modeled_speedup=speedup_model,
                            **{f"{k}_{kk}": vv for k, v in per.items()
                               for kk, vv in v.items()}))

    out_dir = HERE / "results"
    out_dir.mkdir(exist_ok=True)
    (out_dir / "exchange.json").write_text(json.dumps(results, indent=1))
    print(f"# wrote {out_dir / 'exchange.json'}")
    # acceptance guard: packed must save >=1.5x collectives per round,
    # and the alpha-beta model must show lower per-round time.
    assert all(r["ratio"] >= 1.5 for r in results), results
    assert all(r["modeled_speedup"] > 1.0 for r in results), results


if __name__ == "__main__":
    main()
