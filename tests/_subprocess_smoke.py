"""The consolidated 8-real-device subprocess smoke driver.

    python tests/_subprocess_smoke.py <suite>     # exchange | listrank
                                                  # | treealg | graphalg

One thin smoke layer per subsystem on a REAL (2, 4) virtual-device
mesh — the simshard in-process matrix (tests/test_simshard_matrix.py
et al.) now carries the behavioral cross-product, and the golden pins
(tests/golden/) prove simshard == mesh bit-for-bit, so these
subprocesses only need to keep the device path honest: real
``all_to_all`` lowering, multi-hop indirection on actual devices, the
Pallas kernels (which simshard rejects), and the jaxpr collective
counts on a live mesh. Replaces the former ``_exchange_multi.py`` /
``_multi_device_matrix.py`` / ``_treealg_multi.py`` /
``_graphalg_multi.py`` (see TESTING.md for the tier split).

Runs as a subprocess because the device count must be fixed before jax
initializes; exits nonzero on any failure.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.launch.mesh import make_mesh  # noqa: E402
from repro.core.listrank import (IndirectionSpec, ListRankConfig,  # noqa
                                 instances, introspect, rank_list_seq,
                                 rank_list_with_stats)
from repro.core.listrank.exchange import (MeshPlan, compact_queue,  # noqa
                                          remote_gather, route)

AXES = ("row", "col")
P_ALL = P(AXES)
FAILURES = 0


def check(name, ok):
    global FAILURES
    print(("OK  " if ok else "FAIL") + " " + name)
    if not ok:
        FAILURES += 1


def _mesh():
    return make_mesh((2, 4), AXES)


# --------------------------------------------------------------------------
# exchange: routing/gather primitives on real devices
# --------------------------------------------------------------------------

def suite_exchange():
    mesh = _mesh()
    p, q = 8, 32
    rng = np.random.default_rng(1)
    payload = {"ia": rng.integers(-50, 50, p * q).astype(np.int32),
               "fb": rng.normal(size=p * q).astype(np.float32)}
    dest = rng.integers(0, p, p * q).astype(np.int32)
    valid = rng.integers(0, 2, p * q).astype(bool)
    keys = sorted(payload.keys())
    specs = {"direct": (None, 1),
             "grid": (IndirectionSpec.grid(AXES), 2),
             "topo": (IndirectionSpec.topology(("col",), ("row",)), 2)}

    want = {}
    for i in np.flatnonzero(valid):
        want.setdefault(int(dest[i]), []).append(
            (int(payload["ia"][i]), int(payload["fb"][i].view(np.int32))))
    want = {k: sorted(v) for k, v in want.items()}

    def run_route(plan, caps):
        def fn(*leaves):
            pl = dict(zip(keys, leaves[:-2]))
            d, dv, lo, _ = route(plan, caps, pl, leaves[-2], leaves[-1])
            left = sum(jnp.sum(lv).astype(jnp.int32) for _, _, lv in lo)
            return d, dv, plan.psum(left)

        args = [jnp.asarray(payload[k]) for k in keys] + [
            jnp.asarray(dest), jnp.asarray(valid)]
        m = jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=tuple(P_ALL for _ in args),
            out_specs=({k: P_ALL for k in keys}, P_ALL, P()), check_vma=False))
        d, dv, left = m(*args)
        return {k: np.asarray(v) for k, v in d.items()}, \
            np.asarray(dv), int(left)

    for name, (ind, hops) in specs.items():
        caps = [q] if hops == 1 else [q, 8 * q]
        outs = {}
        ok = True
        for packed in (True, False):
            plan = MeshPlan.from_mesh(mesh, AXES, ind, wire_packing=packed)
            d, dv, left = run_route(plan, caps)
            ok &= left == 0  # both wire paths must fully deliver
            outs[packed] = (d, dv)
        d, dv = outs[True]
        r = dv.shape[0] // p
        for pe in range(p):
            got = sorted(
                (int(d["ia"][i]), int(d["fb"][i].view(np.int32)))
                for i in range(pe * r, (pe + 1) * r) if dv[i])
            ok &= got == want.get(pe, [])
        check(f"route oracle {name}", ok)
        (d1, v1), (d2, v2) = outs[True], outs[False]
        check(f"route packed==unpacked {name}",
              np.array_equal(v1, v2) and all(
                  np.array_equal(d1[k].view(np.int32),
                                 d2[k].view(np.int32)) for k in d1))

    # tiny capacities: leftover re-queue drains without loss (direct)
    plan = MeshPlan.from_mesh(mesh, AXES, None, wire_packing=True)

    def drain(*leaves):
        pl = dict(zip(keys, leaves[:-2]))
        cur_pl, cur_d, cur_v = pl, leaves[-2], leaves[-1]
        acc_ia, acc_dv = [], []
        for _ in range(24):
            dlv, dv, lo, _ = route(plan, [3], cur_pl, cur_d, cur_v)
            acc_ia.append(jnp.where(dv, dlv["ia"], -10 ** 6))
            acc_dv.append(dv)
            cur_pl, cur_d, cur_v, _ = compact_queue(lo, q)
        rest = plan.psum(jnp.sum(cur_v).astype(jnp.int32))
        return jnp.stack(acc_ia), jnp.stack(acc_dv), rest

    args = [jnp.asarray(payload[k]) for k in keys] + [
        jnp.asarray(dest), jnp.asarray(valid)]
    m = jax.jit(jax.shard_map(
        drain, mesh=mesh, in_specs=tuple(P_ALL for _ in args),
        out_specs=(P(None, AXES), P(None, AXES), P()), check_vma=False))
    ia_r, dv_r, rest = m(*args)
    ia_r, dv_r = np.asarray(ia_r), np.asarray(dv_r)
    check("overflow drain",
          int(rest) == 0 and int(dv_r.sum()) == int(valid.sum())
          and sorted(ia_r[dv_r]) == sorted(payload["ia"][valid]))

    # remote_gather over 2-hop topo (src reconstruction), dedup on
    n = p * q
    targets = rng.integers(0, n, n).astype(np.int32)
    gvalid = rng.integers(0, 2, n).astype(bool)
    plan = MeshPlan.from_mesh(mesh, AXES,
                              IndirectionSpec.topology(("col",), ("row",)))

    def gather(t, v):
        def lookup(g, gv):
            return {"val": g * 3 + 7}
        out, answered, _ = remote_gather(
            plan, t, v, lambda g: g // q, lookup,
            req_cap=[n] * 2, resp_cap=[n] * 2, dedup=True)
        return out, answered

    m = jax.jit(jax.shard_map(
        gather, mesh=mesh, in_specs=(P_ALL, P_ALL),
        out_specs=({"val": P_ALL}, P_ALL), check_vma=False))
    out, answered = m(jnp.asarray(targets), jnp.asarray(gvalid))
    check("gather topo dedup",
          np.array_equal(np.asarray(answered), gvalid)
          and np.array_equal(np.asarray(out["val"])[gvalid],
                             targets[gvalid] * 3 + 7))

    # collective counts on the live mesh (the coalescing acceptance pin)
    for name, (ind, hops) in specs.items():
        for packed, per_hop in ((True, 1), (False, 4)):
            plan = MeshPlan.from_mesh(mesh, AXES, ind, wire_packing=packed)

            def fn(*leaves, plan=plan, hops=hops):
                pl = dict(zip(keys, leaves[:-2]))
                d, dv, _, _ = route(plan, [q] * hops, pl, leaves[-2],
                                    leaves[-1])
                return d, dv

            m = jax.shard_map(
                fn, mesh=mesh, in_specs=tuple(P_ALL for _ in args),
                out_specs=({k: P_ALL for k in keys}, P_ALL), check_vma=False)
            counts = introspect.collective_counts(m, *args)
            check(f"collectives {name} packed={packed}",
                  counts.get("all_to_all", 0) == per_hop * hops)


# --------------------------------------------------------------------------
# listrank: solver end to end on real devices (incl. the Pallas paths
# simshard rejects)
# --------------------------------------------------------------------------

def suite_listrank():
    mesh = _mesh()
    base = ListRankConfig(srs_rounds=1, local_contraction=False)
    grid = IndirectionSpec.grid(AXES)
    n = 1024
    sg1, rg1 = instances.gen_list(n, gamma=1.0, seed=1)
    sml, rml = instances.gen_random_lists(n, num_lists=11, seed=4,
                                          weighted=True)
    se, re_, _ = instances.gen_euler_tour(n // 2 + 1, seed=6, locality=True)
    se, re_ = instances.pad_to_multiple(se, re_, 8)

    topo = IndirectionSpec.topology(("col",), ("row",))
    cases = [
        ("srs2 contract", sg1, rg1,
         base.with_(srs_rounds=2, local_contraction=True), None),
        ("srs1 grid", sg1, rg1, base, grid),
        ("srs1 topo", sg1, rg1, base, topo),
        ("reversal", sg1, rg1, base.with_(avoid_reversal=False), None),
        ("doubling grid", sg1, rg1, base.with_(algorithm="doubling"), grid),
        ("weighted multilist", sml, rml,
         base.with_(local_contraction=True), None),
        ("euler rgg2d contract", se, re_,
         base.with_(local_contraction=True), None),
        ("pallas contract", sg1, rg1,
         base.with_(local_contraction=True, use_pallas=True), None),
        ("pallas mailbox pack", sg1, rg1, base.with_(use_pallas_pack=True),
         None),
    ]
    for name, succ, rank, cfg, ind in cases:
        s_ref, r_ref = rank_list_seq(succ, rank)
        s, r, stats = rank_list_with_stats(succ, rank, mesh, cfg=cfg,
                                           indirection=ind)
        check(f"listrank {name}",
              np.array_equal(np.asarray(s), s_ref)
              and np.array_equal(np.asarray(r), r_ref))

    # paper-theory smoke (§2.2): rounds ~ n/r + 1; |sub| ~ r ln(n/r)
    import math
    cfg = base.with_(ruler_fraction=1 / 32)
    _, _, stats = rank_list_with_stats(sg1, rg1, mesh, cfg=cfg)
    rounds = stats["rounds"] // 8
    r_tot = 8 * max(4, int(n / 8 / 32))
    check("round bound", rounds <= 4 * (n / r_tot + 1))
    check("sub size",
          stats["sub_size"] <= 3 * r_tot * math.log(n / r_tot) + 64)


# --------------------------------------------------------------------------
# treealg: device tour + stats + batched front door
# --------------------------------------------------------------------------

def suite_treealg():
    from _tree_oracles import dfs_stats
    from repro.core import treealg
    mesh = _mesh()
    cfg = ListRankConfig(srs_rounds=1, local_contraction=True)

    n = 501
    parent = instances.gen_tree_parents(n, seed=9, locality=False,
                                        num_trees=7)
    succ, w, _ = treealg.build_tour(parent, mesh, cfg=cfg)
    got = np.asarray(jax.device_get(succ))[:2 * n]
    check("tour forest",
          np.array_equal(got, treealg.oracle_tour(n, parent).astype(
              np.int32)))

    parent = instances.gen_tree_parents(409, seed=8, locality=True)
    st = treealg.tree_stats(parent, mesh, cfg=cfg)
    d, s, pre, post = dfs_stats(parent)
    check("stats rgg2d", np.array_equal(st.depth, d)
          and np.array_equal(st.subtree_size, s)
          and np.array_equal(st.preorder, pre)
          and np.array_equal(st.postorder, post))

    parent = instances.gen_tree_parents(300, 17)
    newp = treealg.root_tree(parent, 271, mesh, cfg=cfg)
    e_old = {frozenset((c, int(parent[c]))) for c in range(300)
             if parent[c] != c}
    e_new = {frozenset((c, int(newp[c]))) for c in range(300)
             if newp[c] != c}
    d2, _, _, _ = dfs_stats(newp)
    check("root_tree", e_old == e_new and newp[271] == 271
          and d2[271] == 0)

    batch = [instances.gen_list(128, gamma=1.0, seed=s) for s in range(2)]
    batch.append(instances.gen_random_lists(160, num_lists=6, seed=5,
                                            weighted=True))
    results, stats = treealg.rank_lists_with_stats(batch, mesh, cfg=cfg)
    ok = stats["attempts"] == 1
    for (s_in, r_in), (s_out, r_out) in zip(batch, results):
        s_ref, r_ref = rank_list_seq(s_in, r_in)
        ok = ok and np.array_equal(s_out, s_ref) \
            and np.array_equal(r_out, r_ref)
    check("rank_lists batch", ok)

    parents = [instances.gen_tree_parents(nn, seed=nn,
                                          locality=bool(nn % 2))
               for nn in (9, 120)]
    out = treealg.solve_forest(parents, mesh, cfg=cfg)
    ok = True
    for q, st in zip(parents, out):
        d, s, pre, post = dfs_stats(q)
        ok = ok and np.array_equal(st.depth, d) \
            and np.array_equal(st.subtree_size, s) \
            and np.array_equal(st.preorder, pre) \
            and np.array_equal(st.postorder, post)
    check("solve_forest", ok)


# --------------------------------------------------------------------------
# graphalg: cc / forest / stats on real devices
# --------------------------------------------------------------------------

def suite_graphalg():
    from _graph_oracles import check_spanning_forest, union_find_labels
    from _tree_oracles import dfs_stats
    from repro.core import graphalg
    mesh = _mesh()
    cfg = ListRankConfig(srs_rounds=1, local_contraction=True)

    for name, n, e, kw in [
            ("gnm", 240, 400, dict(locality=False)),
            ("rgg2d multi", 200, 260, dict(locality=True,
                                           num_components=4)),
            ("empty", 16, None, np.zeros((0, 2), np.int64))]:
        edges = (instances.gen_graph_edges(n, e, seed=len(name), **kw)
                 if e is not None else kw)
        ref = union_find_labels(n, edges)
        labels, st = graphalg.connected_components(edges, n, mesh, cfg=cfg)
        check(f"cc {name}", np.array_equal(labels, ref)
              and st["cc_unconverged"] == 0)
        parent, lab2, st2 = graphalg.spanning_forest(edges, n, mesh,
                                                     cfg=cfg)
        check(f"forest {name}",
              check_spanning_forest(n, edges, parent, lab2) == [] and
              st2["forest_edges"] == n - np.unique(ref).size)

    edges = instances.gen_graph_edges(220, 360, seed=8, locality=False)
    gs = graphalg.graph_stats(edges, 220, mesh, cfg=cfg)
    depth, size, pre, post = dfs_stats(gs.parent)
    check("graph_stats gnm",
          check_spanning_forest(220, edges, gs.parent, gs.components) == []
          and np.array_equal(gs.depth, depth)
          and np.array_equal(gs.subtree_size, size)
          and np.array_equal(gs.preorder, pre)
          and np.array_equal(gs.postorder, post))


# --------------------------------------------------------------------------
# faultinject: recovery + elastic checkpoint restore on real devices.
# The cross-backend halves (mesh checkpoint -> simshard resume and the
# reverse) can only run where a real mesh exists, so they live here; the
# rest of the recovery matrix is in-process (tests/test_faultinject.py).
# --------------------------------------------------------------------------

def suite_faultinject():
    import tempfile
    from _simshard_cases import (AXES as G_AXES, SHAPE as G_SHAPE,
                                 case_record, golden_cases, load_golden)
    from repro.core.listrank import FaultSpec, sim_mesh
    from repro.runtime.fault_tolerance import (Preempted, SolveSupervisor,
                                               SolveSupervisorConfig)

    name = "list-g1-s1"
    s, r, cfg = next((s, r, c) for nm, s, r, c in golden_cases()
                     if nm == name)
    gold = load_golden(name)
    dev_mesh = make_mesh(G_SHAPE, G_AXES)
    backends = {"mesh": lambda: dev_mesh,
                "sim": lambda: sim_mesh(G_SHAPE, G_AXES)}

    def sup(d):
        return SolveSupervisor(SolveSupervisorConfig(ckpt_dir=d))

    # elastic restore: preempt on one backend, resume on the other; the
    # finished record must equal the committed golden exactly.
    for src, dst in (("mesh", "sim"), ("sim", "mesh")):
        with tempfile.TemporaryDirectory() as d:
            preempted = False
            try:
                rank_list_with_stats(
                    s, r, backends[src](), cfg=cfg, supervisor=sup(d),
                    inject=FaultSpec("preempt", stage="descend", level=0))
            except Preempted:
                preempted = True
            check(f"preempt on {src}", preempted)
            sf, rf, stats = rank_list_with_stats(
                s, r, backends[dst](), cfg=cfg, supervisor=sup(d))
            check(f"elastic restore {src}->{dst}",
                  case_record(sf, rf, stats) == gold
                  and stats["recovery"]["resumed_from"] == 2
                  and stats["stage_log"] == ("base@1", "ascend@0", "post"))

    # crash recovery on the real mesh: restore from the level boundary,
    # never re-executing the completed levels.
    with tempfile.TemporaryDirectory() as d:
        sf, rf, stats = rank_list_with_stats(
            s, r, dev_mesh, cfg=cfg, supervisor=sup(d),
            inject=FaultSpec("pe_loss", stage="base"))
        check("mesh pe_loss recovery",
              case_record(sf, rf, stats) == gold
              and stats["recovery"]["restarts"] == 1
              and stats["recovery"]["resumed_from"] == 2
              and stats["stage_log"].count("descend@0") == 1)

    # injected overflow: escalate-and-resume reproduces the golden bytes
    sf, rf, stats = rank_list_with_stats(
        s, r, dev_mesh, cfg=cfg,
        inject=FaultSpec("overflow", stage="descend", level=0,
                         family="chase"))
    rec = case_record(sf, rf, stats)
    check("mesh injected overflow",
          rec["succ_sha256"] == gold["succ_sha256"]
          and rec["rank_sha256"] == gold["rank_sha256"]
          and stats["attempts"] == 2)


def suite_obs():
    """Flight recorder on the REAL mesh backend: a traced full solve
    must cover every scheduled stage with measured + predicted times,
    reproduce the committed golden bytes exactly (no-perturbation), and
    put one host annotation per span into the ``jax.profiler`` trace
    around it. Writes the profile under $OBS_TRACE_OUT when set (the CI
    simshard-matrix job uploads it), else in a temporary directory."""
    import tempfile
    from _simshard_cases import (AXES as G_AXES, SHAPE as G_SHAPE,
                                 case_record, golden_cases, load_golden)
    from repro import obs
    from repro.core.listrank import resume as resume_lib

    name = "list-g1-s1"
    s, r, cfg = next((s, r, c) for nm, s, r, c in golden_cases()
                     if nm == name)
    mesh = make_mesh(G_SHAPE, G_AXES)
    tr = obs.Tracer()
    tmp = tempfile.TemporaryDirectory()
    logdir = os.environ.get("OBS_TRACE_OUT", "") or tmp.name
    with jax.profiler.trace(logdir):
        sf, rf, stats = rank_list_with_stats(s, r, mesh, cfg=cfg, tracer=tr)
    check("mesh golden bytes identical with tracing on",
          case_record(sf, rf, stats) == load_golden(name))

    labels = [st.label for st in resume_lib.schedule_for(
        cfg.with_(algorithm="srs"))]
    stage_spans = list(tr.find(cat="stage"))
    check("mesh trace covers every scheduled stage once",
          [sp.name for sp in stage_spans] == labels)
    (solve,) = tr.find(cat="solve")
    check("mesh solve span", solve.args["backend"] == "mesh"
          and solve.args["outcome"] == "ok")
    rows = obs.residual_rows(tr)
    print(obs.format_residual_table(rows, title=f"== {name} (mesh)"))
    check("every stage has measured + predicted time",
          {row["stage"] for row in rows} == set(labels)
          and all(row["measured_s"] >= 0 for row in rows))

    events = obs.profile_spans(logdir)
    starts = [t0 for _, t0, _ in events]
    check("profiler host plane holds one event per span, starts in order",
          [e[0] for e in events] == [f"{sp.cat}/{sp.name}"
                                     for sp in tr.spans]
          and starts == sorted(starts))
    if logdir != tmp.name:
        print(f"wrote a profile under {logdir}")
    tmp.cleanup()


SUITES = {"exchange": suite_exchange, "listrank": suite_listrank,
          "treealg": suite_treealg, "graphalg": suite_graphalg,
          "faultinject": suite_faultinject, "obs": suite_obs}


def main():
    if len(sys.argv) != 2 or sys.argv[1] not in SUITES:
        print(f"usage: {sys.argv[0]} {{{'|'.join(SUITES)}}}")
        sys.exit(2)
    SUITES[sys.argv[1]]()
    print("failures:", FAILURES)
    sys.exit(1 if FAILURES else 0)


if __name__ == "__main__":
    main()
