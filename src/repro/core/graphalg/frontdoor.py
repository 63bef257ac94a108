"""graphalg front doors: edges in, components / forests / tree
statistics out — each as ONE jitted mesh program per attempt.

``graph_stats`` chains every stage inside a single ``shard_map``-ed
program (the "edges → rooted forest → Euler tour → stats" pipeline):

  1. hooking + pointer-jumping rounds (:mod:`graphalg.cc`) — component
     labels (= min node id) and spanning-forest edge marks;
  2. unrooted-tour construction (:mod:`graphalg.forest`) — the forest's
     Euler tour cut at each component's min-id root;
  3. a full list-ranking solve (``api._solve_sharded`` — the identical
     in-mesh solver the public ``rank_list`` drives) with unit weights:
     tour positions, hence the *orientation* (parent array) of every
     forest edge and each node's subtree size;
  4. a second solve over the same successor array with the now-known
     ±1 depth weights;
  5. finalization: each tree's start arc broadcasts the tour length L
     to the root's owner, every down-arc scatters its child's
     ``(parent, rank1_down, rank1_up, rank±_down)`` to the child's
     owner, and every node fetches its tree's L through one more
     aggregated gather — closed-form arc arithmetic turns these into
     depth / subtree size / pre- & postorder (DESIGN.md §8 formulas,
     re-derived for the unrooted construction in §9).

``connected_components`` and ``spanning_forest`` run prefixes of the
same body (stages 1 and 1–3). All capacities are host-derived
(:func:`graphalg.cc.derive_caps` + ``api.build_specs`` for the solves);
any overflow surfaces as a fatal stat and the driver retries with the
tuner's targeted escalation — the ``graph`` family for hooking/tour
capacities, the usual chase/sub/gather families for the solver's.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.listrank import api as api_lib
from repro.core.listrank import tuner
from repro.core.listrank.config import ListRankConfig
from repro.core.listrank.exchange import MeshPlan
from repro.core.listrank import exchange as exchange_lib
from repro.core.listrank import transport as transport_lib
from repro.core.listrank.srs import _merge, gather_until_done, zero_stats
from repro.core.graphalg import cc as cc_lib
from repro.core.graphalg import forest as forest_lib
from repro.obs import telemetry as tele_lib
from repro.obs import trace as trace_lib
# the single int32 wire-format id headroom constant (arc ids reach
# 2*E_pad and must stay addressable)
from repro.core.treealg.batch import PACKED_ID_LIMIT as _ID_LIMIT

FATAL_KEYS = api_lib.FATAL_KEYS + cc_lib.GRAPH_FATAL_KEYS


@dataclasses.dataclass(frozen=True)
class GraphStats:
    """Per-node outputs of :func:`graph_stats` (host numpy).

    ``depth``/``subtree_size``/``preorder``/``postorder`` are the tree
    statistics of the spanning forest rooted at each component's
    minimum node id; pre/postorder are 0-based per tree. The
    ``is_ancestor``/interval helpers are the closed-form query layer
    over those numbers (no further solves or collectives).
    """
    components: np.ndarray    #: component label (= min node id)
    parent: np.ndarray        #: oriented spanning forest, root-parented
    depth: np.ndarray
    subtree_size: np.ndarray
    preorder: np.ndarray
    postorder: np.ndarray
    stats: dict

    @property
    def n_nodes(self) -> int:
        return self.components.shape[0]

    @property
    def roots(self) -> np.ndarray:
        return np.flatnonzero(self.components == np.arange(self.n_nodes))

    @property
    def n_components(self) -> int:
        return int(self.roots.shape[0])

    def component_size(self, v) -> np.ndarray:
        """Size of the component containing node(s) ``v``."""
        return self.subtree_size[self.components[v]]

    def same_component(self, u, v) -> np.ndarray:
        return self.components[u] == self.components[v]

    def is_ancestor(self, u, v) -> np.ndarray:
        """True iff ``u`` is an ancestor of ``v`` (inclusive) in the
        spanning forest — closed-form from the pre/postorder numbers
        (``treealg.ops.is_ancestor``)."""
        from repro.core.treealg import ops
        return ops.is_ancestor(self.preorder, self.postorder,
                               self.components, u, v)

    def subtree_interval(self, u):
        """Preorder interval [lo, hi] covered by ``u``'s subtree."""
        from repro.core.treealg import ops
        return ops.subtree_interval(self.preorder, self.subtree_size, u)


# --------------------------------------------------------------------------
# the per-PE pipeline (runs under shard_map)
# --------------------------------------------------------------------------

def _pipeline_sharded(edges, seed, *, plan: MeshPlan, cfg: ListRankConfig,
                      caps: cc_lib.GraphCaps, specs, m: int, m_e: int,
                      mode: str):
    pe = plan.my_id().astype(jnp.int32)
    base = pe * m
    gid = base + jnp.arange(m, dtype=jnp.int32)
    ebase = pe * m_e
    arc_gid = 2 * ebase + jnp.arange(2 * m_e, dtype=jnp.int32)
    ea = edges[:, 0].astype(jnp.int32)
    eb = edges[:, 1].astype(jnp.int32)

    def owner_node(g):
        return g // m

    # graph-pipeline counters plus the solver's (the two in-program
    # solves _merge into the same dict)
    stats = {**zero_stats(), **cc_lib.zero_graph_stats()}
    if plan.telemetry:
        stats["telemetry"] = tele_lib.stage_zero(plan.indirection.depth)

    def finish(out, stats):
        # telemetry stays per-PE (a 3rd sharded output); the remaining
        # stats leaves are all psum'd and ride the replicated out-spec.
        tele = stats.pop("telemetry", None)
        if tele is not None:
            return out, stats, jax.tree.map(lambda v: v[None], tele)
        return out, stats

    # ---- 1. components + spanning-forest edge marks
    f, fmask, stats = cc_lib.cc_rounds(plan, caps, ea, eb, m, m_e, stats)
    if mode == "cc":
        return finish({"components": f}, stats)

    # ---- 2. unrooted Euler tour of the forest
    succ_t, w1, first_mask, tst = forest_lib.build_forest_tour(
        plan, caps, ea, eb, fmask, f, m, m_e)
    stats["tour_msgs"] = stats["tour_msgs"] + plan.psum(tst["sent"])
    stats["tour_undelivered"] = stats["tour_undelivered"] + plan.psum(
        tst["leftover"])
    if plan.telemetry:
        stats = _merge(stats, {"telemetry": {"graph": tst["telemetry"]}})

    # ---- 3. unit-weight ranking -> positions -> orientation
    sout1 = api_lib._solve_sharded(
        succ_t, w1, seed, plan=plan, cfg=cfg, specs=specs, m=2 * m_e)
    rank1, sst1 = sout1[1], sout1[2]
    stats = _merge(stats, sst1)
    if plan.telemetry:
        stats = _merge(stats, {"telemetry": jax.tree.map(
            lambda v: v[0], sout1[3])})
    child, parent_of, r1_down, r1_up, down0 = forest_lib.orient_forest(
        rank1, ea, eb, m_e)

    scaps = [caps.tour] * plan.indirection.depth
    if mode == "forest":
        # deliver each child its parent; roots keep themselves
        dlv, dval, _, pst = exchange_lib.route(
            plan, scaps, {"c": child, "q": parent_of},
            owner_node(child).astype(jnp.int32), fmask)
        cslot = jnp.where(dval, dlv["c"] - base, m)
        parent = gid.at[cslot].set(dlv["q"], mode="drop")
        have = jnp.zeros(m, jnp.bool_).at[cslot].set(True, mode="drop")
        miss = jnp.sum(~have & (f != gid)).astype(jnp.int32)
        stats["stats_undelivered"] = stats["stats_undelivered"] + plan.psum(
            pst["leftover"] + miss)
        if plan.telemetry:
            stats = _merge(stats,
                           {"telemetry": {"graph": pst["telemetry"]}})
        return finish({"components": f, "parent": parent}, stats)

    # ---- 4. ±1 depth weights over the same tour
    w2 = forest_lib.pm_weights(succ_t, arc_gid, fmask, down0)
    sout2 = api_lib._solve_sharded(
        succ_t, w2, seed + 1, plan=plan, cfg=cfg, specs=specs, m=2 * m_e)
    rankpm, sst2 = sout2[1], sout2[2]
    stats = _merge(stats, sst2)
    if plan.telemetry:
        stats = _merge(stats, {"telemetry": jax.tree.map(
            lambda v: v[0], sout2[3])})
    rpm = rankpm.reshape(m_e, 2)
    rpm_down = jnp.where(down0, rpm[:, 0], rpm[:, 1])

    # ---- 5a. tree length L to each root's owner (tour start arcs:
    # L = rank1(start) + 1)
    fm = first_mask.reshape(m_e, 2)
    has_first = fm[:, 0] | fm[:, 1]
    r1m = rank1.reshape(m_e, 2)
    L_val = jnp.where(fm[:, 0], r1m[:, 0], r1m[:, 1]) + 1
    # the start arc is a down-arc out of the root: its parent side
    root_node = parent_of
    ldlv, lval, _, lst = exchange_lib.route(
        plan, [caps.scalar] * plan.indirection.depth,
        {"r": root_node, "L": L_val},
        owner_node(root_node).astype(jnp.int32), has_first)
    rslot = jnp.where(lval, ldlv["r"] - base, m)
    L_arr = jnp.zeros(m, jnp.int32).at[rslot].set(ldlv["L"], mode="drop")

    # ---- 5b. per-child stats to the child's owner
    sdlv, sval, _, sst = exchange_lib.route(
        plan, scaps,
        {"c": child, "q": parent_of, "rd": r1_down, "ru": r1_up,
         "rpm": rpm_down},
        owner_node(child).astype(jnp.int32), fmask)
    cslot = jnp.where(sval, sdlv["c"] - base, m)
    parent = gid.at[cslot].set(sdlv["q"], mode="drop")
    rd = jnp.zeros(m, jnp.int32).at[cslot].set(sdlv["rd"], mode="drop")
    ru = jnp.zeros(m, jnp.int32).at[cslot].set(sdlv["ru"], mode="drop")
    rpmd = jnp.zeros(m, jnp.int32).at[cslot].set(sdlv["rpm"], mode="drop")
    have = jnp.zeros(m, jnp.bool_).at[cslot].set(True, mode="drop")
    miss = jnp.sum(~have & (f != gid)).astype(jnp.int32)

    # ---- 5c. every node fetches its tree's L (aggregated gather)
    def lookup_L(gids, valid):
        slots = jnp.clip(gids - base, 0, m - 1).astype(jnp.int32)
        return {"L": L_arr[slots]}

    lresp, lans, lgst = gather_until_done(
        plan, f, jnp.ones(m, jnp.bool_), owner_node, lookup_L,
        caps.scalar, caps.scalar, dedup=True)
    L_of = jnp.where(lans, lresp["L"], 0)
    stats["stats_undelivered"] = stats["stats_undelivered"] + \
        lgst["undelivered"] + plan.psum(
            lst["leftover"] + sst["leftover"] + miss)
    if plan.telemetry:
        finale = tele_lib.merge(tele_lib.merge(lst["telemetry"],
                                               sst["telemetry"]),
                                lgst["telemetry"])
        stats = _merge(stats, {"telemetry": {"graph": finale}})

    # ---- closed-form per-node statistics (DESIGN.md §9)
    is_nonroot = have
    depth = jnp.where(is_nonroot, 2 - rpmd, 0)
    size = jnp.where(is_nonroot, (rd - ru + 1) // 2, L_of // 2 + 1)
    pos_down = L_of - 1 - rd
    pos_up = L_of - 1 - ru
    pre = jnp.where(is_nonroot, (pos_down + 1 + depth) // 2, 0)
    post = jnp.where(is_nonroot, (pos_up + 2 - depth) // 2 - 1,
                     jnp.maximum(L_of // 2, 0))
    out = {"components": f, "parent": parent, "depth": depth,
           "subtree_size": size, "preorder": pre, "postorder": post}
    return finish(out, stats)


@functools.lru_cache(maxsize=128)
def _jitted_pipeline(mesh, plan, cfg, caps, specs, m, m_e, mode):
    fn = functools.partial(_pipeline_sharded, plan=plan, cfg=cfg, caps=caps,
                           specs=specs, m=m, m_e=m_e, mode=mode)
    spec = P(plan.pe_axes)
    out_specs = (dict.fromkeys(_OUT_KEYS[mode], spec), P())
    if plan.telemetry:
        out_specs = out_specs + (spec,)
    return transport_lib.device_run(
        mesh, plan.pe_axes, fn, in_specs=(spec, P()),
        out_specs=out_specs)


_OUT_KEYS = {
    "cc": ("components",),
    "forest": ("components", "parent"),
    "stats": ("components", "parent", "depth", "subtree_size",
              "preorder", "postorder"),
}


# --------------------------------------------------------------------------
# host drivers
# --------------------------------------------------------------------------

def _check_edges(edges, n_nodes: int) -> np.ndarray:
    edges = np.asarray(jax.device_get(edges))
    if edges.ndim != 2 or edges.shape[1] != 2:
        raise ValueError("edges must be an (E, 2) array of node ids")
    if n_nodes <= 0:
        raise ValueError("n_nodes must be positive")
    edges = edges.astype(np.int64)
    if edges.size and not ((edges >= 0) & (edges < n_nodes)).all():
        raise ValueError("edge endpoints out of range")
    return edges


def _prepare(edges, n_nodes, mesh, pe_axes, cfg):
    """Shared host-side prep: padding, plan, capacity derivation."""
    cfg = cfg or ListRankConfig()
    pe_axes = tuple(pe_axes) if pe_axes is not None \
        else tuple(mesh.axis_names)
    backend, mesh = transport_lib.resolve_backend(cfg.backend, mesh, pe_axes)
    if backend == "simshard":
        transport_lib.check_sim_config(cfg)
    edges = _check_edges(edges, n_nodes)
    plan = MeshPlan.from_mesh(mesh, pe_axes, None,
                              wire_packing=cfg.wire_packing,
                              pallas_pack=cfg.use_pallas_pack,
                              telemetry=cfg.telemetry)
    p = plan.p
    n_pad = n_nodes + (-n_nodes) % p
    m = n_pad // p
    # padding edges are self-loops at node 0: they never propose a hook
    # and never join the forest, so no validity plumbing is needed
    e_pad = max(edges.shape[0], p)
    e_pad = e_pad + (-e_pad) % p
    m_e = e_pad // p
    if n_pad >= _ID_LIMIT or 2 * e_pad >= _ID_LIMIT:
        raise ValueError(
            f"instance too large for int32 ids: n_pad={n_pad}, "
            f"2*E_pad={2 * e_pad} must stay below {_ID_LIMIT}")
    edges_pad = np.zeros((e_pad, 2), np.int64)
    edges_pad[:edges.shape[0]] = edges

    base_caps = cc_lib.derive_caps(edges_pad, n_pad, p, cfg)
    if cfg.algorithm == "auto":
        cfg = cfg.with_(algorithm=tuner.choose_algorithm(
            cfg, p, plan.indirection.depth, 2 * m_e))
    return cfg, mesh, plan, edges_pad, base_caps, n_pad, m, e_pad, m_e


def _attempt_specs(cfg, plan, m_e: int, e_pad: int,
                   scales: tuner.CapacityScales = tuner.CapacityScales()):
    """Solve-stage spec ladder for one attempt — the single derivation
    behind both the driver and the traced footprint. The in-program
    solves rank a tour over *edge-sharded* arcs: a node's incident
    arcs all live on edge PEs, so wave traffic concentrates harder
    than the uniform-list expectation behind the §2 capacity
    derivation — the chase/queue slack starts doubled (measured:
    first-attempt clean at benchmark scale, where the default slack
    needed two escalations). The two solves share one ladder over the
    2*E_pad-arc instance; every arc may be a terminal (self-loop
    padding), hence the full term bound."""
    cfg_solve = cfg.with_(capacity_slack=2 * cfg.capacity_slack,
                          queue_slack=2 * cfg.queue_slack)
    return api_lib.build_specs(cfg_solve, plan, 2 * m_e, 2 * e_pad,
                               term_bound=2 * m_e, scales=scales)


def pipeline_collective_footprint(edges, n_nodes: int, mesh,
                                  pe_axes: Sequence[str] | None = None,
                                  cfg: ListRankConfig | None = None,
                                  mode: str = "stats"):
    """Trace the pipeline's mesh program and return its collective
    ``{prim: (count, payload_bytes)}`` footprint (first-attempt
    capacities). The hooking/shortcut loops are ``while_loop``s, so the
    count is *static* — independent of the edge count and instance —
    which is exactly the coalescing invariant the tests pin. Traces
    the very program the driver runs on attempt 1 (same jit cache)."""
    from repro.core.listrank import introspect
    cfg, mesh, plan, edges_pad, caps, n_pad, m, e_pad, m_e = _prepare(
        edges, n_nodes, mesh, pe_axes, cfg)
    specs = _attempt_specs(cfg, plan, m_e, e_pad)
    runner = _jitted_pipeline(mesh, plan, cfg, caps, specs, m, m_e, mode)
    return introspect.collective_footprint(
        runner, jnp.asarray(edges_pad, jnp.int32), jnp.int32(0))


def _run_pipeline(edges, n_nodes, mesh, pe_axes, cfg, mode, seed,
                  max_retries, tracer=None):
    cfg, mesh, plan, edges_pad, base_caps, n_pad, m, e_pad, m_e = _prepare(
        edges, n_nodes, mesh, pe_axes, cfg)
    edges_d = transport_lib.put_sharded(mesh, plan.pe_axes,
                                        jnp.asarray(edges_pad, jnp.int32))
    tr = trace_lib.ensure(tracer)

    scales = tuner.CapacityScales()
    last_stats = None
    with tr.span(f"graphalg:{mode}", cat="solve", n_nodes=n_nodes,
                 p=plan.p, mode=mode,
                 backend=transport_lib.backend_name(mesh)) as pipe_span:
        for attempt in range(max_retries + 1):
            caps = base_caps.scaled(scales.graph)
            specs = _attempt_specs(cfg, plan, m_e, e_pad, scales)
            runner = _jitted_pipeline(mesh, plan, cfg, caps, specs, m, m_e,
                                      mode)
            att = tr.begin(f"graphalg:{mode}#{attempt + 1}",
                           cat="stage-attempt", stage=f"graphalg:{mode}",
                           level=-1, attempt=attempt + 1,
                           scales=tuner.format_scales(scales))
            if tr.enabled:
                att.annotate(**_pipeline_prediction(
                    runner, edges_pad, plan, cfg, mesh))
            t0 = time.time()
            outs = runner(edges_d, jnp.int32(seed))
            jax.block_until_ready(jax.tree.leaves(outs))
            dt = time.time() - t0
            out, stats = outs[0], outs[1]
            host_stats = {k: int(jax.device_get(v)) for k, v in stats.items()}
            host_stats["attempts"] = attempt + 1
            fatal = sum(host_stats.get(k, 0) for k in FATAL_KEYS)
            if fatal == 0:
                util = {}
                if plan.telemetry:
                    agg = tele_lib.aggregate(jax.device_get(outs[2]))
                    util = tele_lib.utilization(agg)
                    spec0 = specs[0]
                    rec = tele_lib.StageRecord(
                        label=f"graphalg:{mode}", kind="pipeline", level=-1,
                        caps={"chase": tuple(spec0.mail_caps),
                              "sub": (spec0.cap_sub,),
                              "gather": tuple(
                                  max(a, b) for a, b in zip(
                                      spec0.gather_req_cap,
                                      spec0.gather_resp_cap)),
                              "graph": (caps.tour,)},
                        queue_cap=spec0.queue_cap, tele=agg)
                    host_stats["telemetry"] = {
                        "stages": [rec.to_json()],
                        "headroom": tele_lib.headroom_rows(
                            [rec], tuner.format_scales(scales))}
                tr.end(att, wall_s=dt, outcome="committed", **util)
                host = {k: np.asarray(jax.device_get(v))[:n_nodes]
                        for k, v in out.items()}
                pipe_span.annotate(attempts=attempt + 1, outcome="ok")
                if tr.enabled:
                    from repro.obs import metrics as metrics_lib
                    metrics_lib.ingest_host_stats(tr.metrics, host_stats,
                                                  prefix=f"graphalg/{mode}/")
                return host, host_stats
            tr.end(att, wall_s=dt, outcome="overflow",
                   fatal={k: host_stats[k] for k in FATAL_KEYS
                          if host_stats.get(k, 0) > 0})
            last_stats = host_stats
            scales = tuner.escalate(scales, host_stats)
            tr.instant(f"escalate:graphalg:{mode}", cat="retry",
                       scales=tuner.format_scales(scales))
        pipe_span.annotate(outcome="exhausted")
    raise RuntimeError(
        f"graphalg {mode} did not complete after {max_retries + 1} "
        f"attempts; stats={last_stats}")


def _pipeline_prediction(runner, edges_pad, plan, cfg, mesh):
    """Static §2.6 prediction annotations for one pipeline attempt
    (trace-only; cached per jitted runner — see resume.run_staged)."""
    from repro.core.listrank import introspect
    from repro.obs import cost as cost_lib
    key = id(runner)
    if key not in _FOOTPRINT_CACHE:
        _FOOTPRINT_CACHE[key] = introspect.collective_footprint(
            runner, jnp.asarray(edges_pad, jnp.int32), jnp.int32(0))
    fprint = _FOOTPRINT_CACHE[key]
    sim = transport_lib.is_sim(mesh)
    pred = cost_lib.predict_stage(fprint, plan, cfg.machine, sim)
    count, nbytes = cost_lib.total_collectives(fprint)
    if sim:
        nbytes //= max(plan.p, 1)
    return {"predicted_s": pred["total_s"], "collective_count": count,
            "payload_bytes": nbytes,
            "footprint": cost_lib.footprint_summary(fprint)}


#: per-runner footprint cache (runners are pinned by _jitted_pipeline's
#: lru_cache, so ids are stable while cached).
_FOOTPRINT_CACHE: dict = {}


def connected_components(edges, n_nodes: int, mesh,
                         pe_axes: Sequence[str] | None = None,
                         cfg: ListRankConfig | None = None, seed: int = 0,
                         max_retries: int = 3, tracer=None):
    """Connected components of an undirected edge list on the mesh.

    Returns (labels, stats): ``labels[v]`` is the minimum node id of
    v's component (a canonical labeling).
    """
    out, stats = _run_pipeline(edges, n_nodes, mesh, pe_axes, cfg, "cc",
                               seed, max_retries, tracer=tracer)
    return out["components"], stats


def spanning_forest(edges, n_nodes: int, mesh,
                    pe_axes: Sequence[str] | None = None,
                    cfg: ListRankConfig | None = None, seed: int = 0,
                    max_retries: int = 3, tracer=None):
    """Oriented spanning forest of an undirected edge list.

    Returns (parent, labels, stats): ``parent`` is a rooted forest of
    *graph edges* — each component spanned and rooted at its minimum
    node id (``parent[root] == root``) — which feeds directly into
    ``treealg`` (``tree_stats`` / ``solve_forest`` / ``root_tree``).
    """
    out, stats = _run_pipeline(edges, n_nodes, mesh, pe_axes, cfg,
                               "forest", seed, max_retries, tracer=tracer)
    return out["parent"], out["components"], stats


def graph_stats(edges, n_nodes: int, mesh,
                pe_axes: Sequence[str] | None = None,
                cfg: ListRankConfig | None = None, seed: int = 0,
                max_retries: int = 3, tracer=None) -> GraphStats:
    """Components, oriented spanning forest, and per-node tree
    statistics from a raw edge list — one jitted mesh program.

    Returns a :class:`GraphStats` with, per node: component label,
    spanning-forest parent, depth, subtree size and pre/postorder
    numbers (plus the closed-form ``is_ancestor``/interval query layer
    over them).
    """
    out, stats = _run_pipeline(edges, n_nodes, mesh, pe_axes, cfg, "stats",
                               seed, max_retries, tracer=tracer)
    return GraphStats(components=out["components"], parent=out["parent"],
                      depth=out["depth"], subtree_size=out["subtree_size"],
                      preorder=out["preorder"], postorder=out["postorder"],
                      stats=stats)
