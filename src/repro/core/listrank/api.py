"""Public API: distributed list ranking over a JAX mesh.

``rank_list(succ, rank, mesh, ...)`` runs the paper's engineered
pipeline:

  1. local contraction of PE-local sublists (§2.3, optional),
  2. sparse-ruling-set with spawning, ``srs_rounds`` recursion levels,
     pointer doubling base case (§2.1-2.2); or plain pointer doubling,
  3. direction handling: §2.5 terminal→initial postprocess (default) or
     the faithful Algorithm-1 reversal preprocessing,
  4. restoration of locally contracted elements.

Every capacity (mailboxes, queues, subproblem stores) is host-derived
from the instance parameters with configurable slack; runs that hit any
capacity report it in ``stats`` and the driver retries, doubling only
the capacity family whose fatal stat fired (tuner.escalate). Capacity
therefore affects only performance, never correctness. Parameter
defaults (ruler fractions, indirection, SRS-vs-PD) can be derived from
the §2.6 cost model — see repro.core.listrank.tuner.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.listrank import local as local_lib
from repro.core.listrank import store as store_lib
from repro.core.listrank import transport as transport_lib
from repro.core.listrank import tuner
from repro.core.listrank.config import IndirectionSpec, ListRankConfig
from repro.core.listrank.doubling import doubling_solve
from repro.core.listrank import exchange as exchange_lib
from repro.core.listrank.exchange import MeshPlan
from repro.core.listrank.srs import (LevelSpec, gather_until_done,
                                     route_until_done, solve_store,
                                     zero_stats, _merge)
from repro.core.listrank import resume as resume_lib
from repro.core.listrank.resume import FATAL_KEYS, SolveExhausted  # noqa: F401
from repro.obs import telemetry as tele_lib
from repro.obs import trace as trace_lib
# (re-exported: graphalg.frontdoor composes FATAL_KEYS; callers catch
# SolveExhausted from either module.)


def chase_leaves(weight_dtype=jnp.float32) -> dict:
    """Structure of a chase wave message for a given weight dtype.

    The weight leaf rides as whatever dtype the caller's rank array
    carries (int32 for the ±1 Euler-tour weights of
    ``repro.core.treealg``, float32 for float instances); the wire
    format bit-reinterprets it, so e.g. int32 ±1 weights round-trip
    exactly — no float detour anywhere in the solver.
    """
    return {"target": jnp.int32, "ruler": jnp.int32,
            "weight": jnp.dtype(weight_dtype)}


def chase_wire_words(weight_dtype=jnp.float32) -> int:
    """int32 words per chase message on the wire (payload leaves +
    routing destination + validity) — the WireFormat descriptor derived
    host-side; the benchmark harness uses it for modeled comm volume.
    Every supported weight dtype packs to one 32-bit word, so the width
    is dtype-independent."""
    return exchange_lib.WireFormat.for_leaves(
        {**chase_leaves(weight_dtype), "_dest": jnp.int32}).width


#: the default-dtype descriptors (kept as module constants for the
#: benchmark harnesses' modeled-volume computations).
CHASE_LEAVES = chase_leaves()
CHASE_WIRE_WORDS = chase_wire_words()


def canonical_weight_dtype(dtype) -> jnp.dtype:
    """The on-device dtype for a rank/weight input: 32-bit words,
    integer kinds to int32, float kinds to float32 (bool weights make
    no sense and are rejected)."""
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.floating):
        return jnp.dtype(jnp.float32)
    if jnp.issubdtype(dt, jnp.integer):
        return jnp.dtype(jnp.int32)
    raise TypeError(f"unsupported weight dtype {dt}")


def build_specs(cfg: ListRankConfig, plan: MeshPlan, m: int, n: int,
                term_bound: int,
                scales=tuner.CapacityScales(),
                estimate: tuner.CapacityEstimate | None = None,
                ) -> tuple[LevelSpec, ...]:
    """Host-side derivation of every static capacity (see module doc).

    Per-level ruler fractions come from :func:`tuner.level_plan` — the
    cost model when ``cfg.ruler_fraction is None``, the fixed fraction
    otherwise. ``scales`` carries the targeted retry multipliers
    (chase mail/queue, sub store, gather) from the driver's retry loop —
    either one :class:`tuner.CapacityScales` for every level or a
    per-level sequence (``srs_rounds`` chase levels + the base level;
    level-resume escalates only levels >= the faulting one, so completed
    levels' static shapes never change). ``estimate`` (the sampled-
    splitter pre-pass, :func:`tuner.estimate_capacities`) replaces the
    static ``cfg.capacity_slack`` guess with the measured per-hop
    destination skew for the mailbox families.
    """
    levels = tuner.level_plan(cfg, plan.p, plan.indirection.depth, n)
    level_scales = tuner.normalize_level_scales(scales, cfg.srs_rounds + 1)

    def hop_slack(hi: int) -> float:
        return (estimate.slack_for_hop(hi) if estimate is not None
                else cfg.capacity_slack)

    specs: list[LevelSpec] = []
    cap = m
    tb = term_bound
    p = plan.p
    logp = math.log2(max(p, 2))
    for li, lp in enumerate(levels):
        sc = level_scales[li]
        frac = lp.frac
        r_static = max(cfg.min_rulers_per_pe, int(math.ceil(frac * cap)))
        mail_caps = tuple(
            max(cfg.min_capacity,
                int(math.ceil(hop_slack(hi) * sc.chase * r_static
                              / plan.hop_size(hop))))
            for hi, hop in enumerate(plan.indirection.hops))
        inbox = sum(plan.hop_size(h) * c
                    for h, c in zip(plan.indirection.hops, mail_caps))
        queue_cap = int(max(cfg.queue_slack * r_static * sc.chase,
                            2 * inbox + cfg.spawn_window + 64))
        # rounds ~ n/r + log p (DESIGN.md §2); 1/frac is the per-PE n/r.
        max_rounds = int(cfg.max_round_slack * (1.0 / frac + logp) + 256)
        exp_sub = r_static * (1.0 + math.log(max(1.0 / frac, 2.0))) + tb + 64
        cap_sub = min(cap, int(math.ceil(cfg.sub_capacity_slack * sc.sub
                                         * exp_sub)))
        gcap = tuple(
            max(cfg.min_capacity,
                int(math.ceil(hop_slack(hi) * sc.gather * cap
                              / plan.hop_size(hop))))
            for hi, hop in enumerate(plan.indirection.hops))
        specs.append(LevelSpec(
            cap=cap, r_static=r_static, mail_caps=mail_caps,
            queue_cap=queue_cap, spawn_window=cfg.spawn_window,
            max_rounds=max_rounds, cap_sub=cap_sub,
            gather_req_cap=gcap, gather_resp_cap=gcap, base=False,
            ruler_frac=frac, max_restarts=cfg.max_restarts))
        cap = cap_sub
        tb = cap_sub  # every sub element may be a sub-terminal
    # base level (pointer doubling or all-gather)
    sc = level_scales[-1]
    gcap = tuple(
        max(cfg.min_capacity,
            int(math.ceil(hop_slack(hi) * sc.gather * cap
                          / plan.hop_size(hop))))
        for hi, hop in enumerate(plan.indirection.hops))
    specs.append(LevelSpec(
        cap=cap, r_static=0, mail_caps=(0,) * plan.indirection.depth,
        queue_cap=0, spawn_window=0,
        max_rounds=int(math.ceil(math.log2(max(n, 2)))) + 8, cap_sub=0,
        gather_req_cap=gcap, gather_resp_cap=gcap, base=True,
        ruler_frac=0.0, max_restarts=cfg.max_restarts))
    return tuple(specs)


# --------------------------------------------------------------------------
# the per-PE program (runs under shard_map)
# --------------------------------------------------------------------------

def _reverse_instance(plan, spec, owner_of, st, stats):
    """Faithful Algorithm-1 preprocessing: build the reversed instance
    with one n-message exchange (the cost §2.5 avoids)."""
    cap = st.ids.shape[0]
    gid = st.ids
    nonterm = st.valid & (st.succ != gid)
    payload = {"target": st.succ, "src": gid, "w": st.rank}
    dest = owner_of(st.succ).astype(jnp.int32)

    got = jnp.zeros(cap, jnp.bool_)
    succ_rev = jnp.where(st.valid, gid, st.succ)
    rank_rev = jnp.zeros_like(st.rank)

    def deliver(carry, delivered, dval):
        got, succ_rev, rank_rev = carry
        slots, found = store_lib.slot_of(st, delivered["target"])
        ok = dval & found
        idx = jnp.where(ok, slots, cap)
        got = got.at[idx].set(True, mode="drop")
        succ_rev = succ_rev.at[idx].set(delivered["src"], mode="drop")
        rank_rev = rank_rev.at[idx].set(delivered["w"], mode="drop")
        return got, succ_rev, rank_rev

    (got, succ_rev, rank_rev), pending, msgs, rtele = route_until_done(
        plan, spec.mail_caps, payload, dest, nonterm, deliver,
        (got, succ_rev, rank_rev))
    upd = {"reversal_msgs": msgs, "undelivered": pending}
    if plan.telemetry:
        # the reversal exchange rides the chase-family mail caps
        upd["telemetry"] = {"chase": rtele}
    stats = _merge(stats, upd)
    rev = st.replace(succ=succ_rev, rank=rank_rev)
    return rev, stats


def _restore_local(plan, spec, owner_of, st, aux, rep, succ_orig, rank_orig,
                   base, stats):
    """Restore locally contracted elements (§2.3 restoration).

    R1: every rep's solved succ points to a contracted-instance terminal
        l_t whose local chain continues to the true terminal — fetch the
        tail (terminal id, tail distance) from l_t's owner (aggregated).
    R2: interior elements splice their local-chain prefix onto the fixed
        final values of the rep their chain exits into.
    """
    m = succ_orig.shape[0]
    lidx = jnp.arange(m, dtype=jnp.int32)
    gid = base + lidx

    # ---- R1: tail fixup for reps
    tail_fn = local_lib.tail_lookup(aux, succ_orig, rank_orig, base)
    resp, answered, g1 = gather_until_done(
        plan, st.succ, rep, owner_of, tail_fn,
        spec.gather_req_cap, spec.gather_resp_cap, dedup=True)
    upd = answered & resp["found"] & rep
    final_succ = jnp.where(upd, resp["succ"], st.succ)
    final_rank = jnp.where(upd, st.rank + resp["rank"], st.rank)
    miss1 = plan.psum(jnp.sum(rep & ~upd).astype(jnp.int32))

    # ---- R2: interior elements
    S, D, stop_is_term = aux["S"], aux["D"], aux["stop_is_term"]
    interior = ~rep
    # chains ending at a true local terminal need no communication
    direct = interior & stop_is_term
    final_succ = jnp.where(direct, base + S, final_succ)
    final_rank = jnp.where(direct, D, final_rank)
    # chains exiting the PE: ask the rep the chain enters (aggregated)
    need = interior & ~stop_is_term
    exit_target = succ_orig[S]  # the remote rep

    def final_fn(gids, valid):
        slots = jnp.clip(gids - base_ref[0], 0, m - 1).astype(jnp.int32)
        ok = valid & (gids >= base_ref[0]) & (gids < base_ref[0] + m)
        return {"succ": jnp.where(ok, final_succ_ref[0][slots], gids),
                "rank": jnp.where(ok, final_rank_ref[0][slots],
                                  jnp.zeros_like(final_rank_ref[0][slots])),
                "found": ok}

    # lookup closes over the *fixed* rep finals on the owner side
    base_ref = [base]
    final_succ_ref = [final_succ]
    final_rank_ref = [final_rank]
    resp2, answered2, g2 = gather_until_done(
        plan, exit_target, need, owner_of, final_fn,
        spec.gather_req_cap, spec.gather_resp_cap, dedup=True)
    upd2 = answered2 & resp2["found"] & need
    final_succ = jnp.where(upd2, resp2["succ"], final_succ)
    final_rank = jnp.where(upd2, D + rank_orig[S] + resp2["rank"], final_rank)
    miss2 = plan.psum(jnp.sum(need & ~upd2).astype(jnp.int32))

    upd = {
        "fixup_msgs": g1["msgs"] + g2["msgs"],
        "undelivered": g1["undelivered"] + g2["undelivered"] + miss1 + miss2}
    if plan.telemetry:
        upd["telemetry"] = {"gather": tele_lib.merge(g1["telemetry"],
                                                     g2["telemetry"])}
    stats = _merge(stats, upd)
    return final_succ, final_rank, stats


def _solve_sharded(succ, rank, seed, *, plan: MeshPlan, cfg: ListRankConfig,
                   specs: list[LevelSpec], m: int):
    pe = plan.my_id().astype(jnp.int32)
    base = pe * m
    lidx = jnp.arange(m, dtype=jnp.int32)
    gid = base + lidx
    key = jax.random.PRNGKey(seed)
    stats = zero_stats()
    if plan.telemetry:
        stats["telemetry"] = tele_lib.stage_zero(plan.indirection.depth)

    def owner_of(g):
        return g // m

    succ_orig, rank_orig = succ, rank
    if cfg.local_contraction:
        succ_w, rank_w, rep, aux = local_lib.contract(
            succ, rank, base, m, cfg.use_pallas)
        active = rep
    else:
        succ_w, rank_w, rep, aux = succ, rank, None, None
        active = jnp.ones(m, jnp.bool_)

    is_term0 = active & (succ_w == gid)
    spec0 = specs[0]

    if cfg.algorithm == "doubling":
        st = store_lib.make_dense_store(succ_w, rank_w, active, base)
        st, pst = doubling_solve(plan, st, owner_of, spec0.gather_req_cap,
                                 spec0.gather_resp_cap,
                                 specs[-1].max_rounds, cfg.dedup_requests)
        upd = {"pd_rounds": pst["pd_rounds"],
               "pd_msgs": pst["pd_msgs"],
               "undelivered": pst["pd_undelivered"]}
        if plan.telemetry:
            upd["telemetry"] = {"gather": pst["telemetry"]}
        stats = _merge(stats, upd)
    elif cfg.avoid_reversal:
        # forward chasing; the per-level direction flip at level 0 is
        # exactly the paper's §2.5 reversal-avoiding postprocess.
        st = store_lib.make_dense_store(succ_w, rank_w, active, base)
        st, stats = solve_store(plan, cfg, specs, owner_of, st, key, 0, stats,
                                want_sink=True)
    else:
        st = store_lib.make_dense_store(succ_w, rank_w, active, base)
        st, stats = _reverse_instance(plan, spec0, owner_of, st, stats)
        forced = is_term0  # Alg.1 l.2: initial elements of the reversed
        # instance are the original terminals — locally known.
        st, stats = solve_store(plan, cfg, specs, owner_of, st, key, 0, stats,
                                forced=forced, want_sink=False)

    if cfg.local_contraction:
        succ_f, rank_f, stats = _restore_local(
            plan, spec0, owner_of, st, aux, rep, succ_orig, rank_orig, base,
            stats)
    else:
        succ_f, rank_f = st.succ, st.rank

    # make stats replicated for a P() out-spec; telemetry stays per-PE
    # (popped before the psum — the count pins require the telemetry-on
    # program to add zero collectives).
    tele = stats.pop("telemetry", None)
    stats = {k: plan.psum(v) for k, v in stats.items()}
    if tele is not None:
        return succ_f, rank_f, stats, jax.tree.map(lambda v: v[None], tele)
    return succ_f, rank_f, stats


# --------------------------------------------------------------------------
# host driver
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _jitted_solver(mesh, plan, cfg, specs, m):
    fn = functools.partial(_solve_sharded, plan=plan, cfg=cfg, specs=specs,
                           m=m)
    spec_sharded = P(plan.pe_axes)
    out_specs = (spec_sharded, spec_sharded, P())
    if plan.telemetry:
        out_specs = out_specs + (spec_sharded,)
    return transport_lib.device_run(
        mesh, plan.pe_axes, fn,
        in_specs=(spec_sharded, spec_sharded, P()),
        out_specs=out_specs)


def _resolve(n: int, mesh, pe_axes, cfg: ListRankConfig,
             indirection: IndirectionSpec | None):
    """What the front door derives before any data moves: the backend
    (and its mesh), the PE axes, the routing plan, the resolved config
    (``algorithm="auto"`` decided) and the per-PE size m."""
    pe_axes = tuple(pe_axes) if pe_axes is not None else tuple(mesh.axis_names)
    backend, mesh = transport_lib.resolve_backend(cfg.backend, mesh, pe_axes)
    if backend == "simshard":
        transport_lib.check_sim_config(cfg)
    if indirection is None and cfg.auto_indirection:
        axis_sizes = tuple(mesh.shape[a] for a in pe_axes)
        indirection = tuner.choose_indirection(cfg, pe_axes, axis_sizes, n)
    plan = MeshPlan.from_mesh(mesh, pe_axes, indirection,
                              wire_packing=cfg.wire_packing,
                              pallas_pack=cfg.use_pallas_pack,
                              telemetry=cfg.telemetry)
    p = plan.p
    if n % p != 0:
        raise ValueError(f"n={n} must be divisible by p={p} (pad the input)")
    m = n // p
    if cfg.algorithm == "auto":
        # Corollary-1 regime check: PD below the efficiency threshold.
        cfg = cfg.with_(algorithm=tuner.choose_algorithm(
            cfg, p, plan.indirection.depth, m))
    return backend, mesh, pe_axes, plan, cfg, m


def lower_stages(n: int, mesh, pe_axes: Sequence[str] | None = None,
                 cfg: ListRankConfig | None = None,
                 indirection: IndirectionSpec | None = None,
                 term_bound: int = 1, weight_dtype=jnp.int32):
    """Lower every stage program of a :func:`rank_list_with_stats` solve
    of ``n`` elements from shapes alone, as its first attempt runs them
    (default capacity scales). Returns ``[(stage label, Lowered)]``.
    ``term_bound`` is the most list terminals on one PE (1 for a single
    list), which the front door counts from the instance.

    Nothing runs and no array is placed, so ``mesh`` may hold described
    devices (``jax.experimental.topologies``); ``.compile()`` on each
    result then says what the chip's compiler says, and its
    ``memory_analysis()`` what the program needs on the device. A solve
    that escalates its capacities compiles larger programs for its
    retries. ``cfg.capacity_estimation`` sizes mailboxes from the
    instance itself, so it is refused here.
    """
    cfg = cfg or ListRankConfig()
    if cfg.capacity_estimation:
        raise ValueError("lower_stages cannot lower capacity_estimation: "
                         "its mailbox sizes come from the instance")
    backend, mesh, pe_axes, plan, cfg, m = _resolve(n, mesh, pe_axes, cfg,
                                                    indirection)
    if backend != "mesh":
        raise ValueError("lower_stages needs a device mesh")
    return resume_lib.lower_staged(
        mesh=mesh, plan=plan, cfg=cfg, m=m, n=n,
        specs=build_specs(cfg, plan, m, n, term_bound),
        weight_dtype=canonical_weight_dtype(weight_dtype))


def rank_list_with_stats(succ, rank, mesh, pe_axes: Sequence[str] | None = None,
                         cfg: ListRankConfig | None = None,
                         indirection: IndirectionSpec | None = None,
                         seed: int = 0, max_retries: int = 3,
                         term_bound: int | None = None,
                         supervisor=None, inject=None,
                         stage_counters: bool = False, initial_scales=None,
                         tracer=None):
    """Rank lists distributed over ``mesh``. Returns (succ, rank, stats).

    ``succ``/``rank`` may be numpy or jax arrays of length n (divisible
    by the PE count); they are placed block-sharded over ``pe_axes``.

    The solve runs as the level-resumable stage loop
    (:mod:`repro.core.listrank.resume`): a fatal capacity overflow at
    level k resumes from the end of level k-1 with only the implicated
    family escalated. ``supervisor``
    (:class:`repro.runtime.fault_tolerance.SolveSupervisor`) adds
    level-boundary checkpoints, preemption handling, and restore-on-
    restart; ``inject`` (:class:`repro.core.listrank.faults.FaultSpec`
    or a sequence) drives deterministic fault injection;
    ``stage_counters`` records per-stage collective counts;
    ``initial_scales`` pre-seeds the per-level capacity scales
    (CapacityScales or a per-level sequence). A run that exhausts its
    escalation budget raises :class:`SolveExhausted` carrying the full
    escalation path and the per-family fatal stats.

    ``tracer`` (a :class:`repro.obs.Tracer`) records the flight-recorder
    span tree for the whole solve — the root ``solve`` span, the front
    door's ``term_bound`` readback, placement and fingerprint, the
    capacity-estimation pre-pass, every stage execution/retry with its
    dispatch, device wait and counter readback, measured wall time and
    §2.6 predicted time, the final stats readback, and checkpoint
    save/restore — and ingests the final ``host_stats`` into the
    tracer's metrics registry. The ``solve`` span ends with
    ``host_syncs``, the blocking host<->device syncs the solve made,
    ``recursion_skipped``: 1 when contraction left no live link and
    the driver went straight from ``prep`` to ``post``, ``live_links``:
    the links contraction left, and ``sub_capacity``: the slots of the
    recursion's sub-stores.
    Host-side only; the traced programs are bit-identical with tracing
    on or off.
    """
    cfg = cfg or ListRankConfig()
    n = succ.shape[0]
    backend, mesh, pe_axes, plan, cfg, m = _resolve(n, mesh, pe_axes, cfg,
                                                    indirection)
    p = plan.p
    tr = trace_lib.ensure(tracer)
    syncs_before = tr.host_syncs
    solve_span = tr.begin(
        "solve", cat="solve", n=n, p=p, backend=backend,
        algorithm=cfg.algorithm, machine=cfg.machine.name,
        indirection=[list(h) for h in plan.indirection.hops])
    try:
        s_host = None
        if term_bound is None:
            with tr.span("term_bound", cat="frontdoor"):
                s_host = np.asarray(jax.device_get(succ))
                tr.host_sync()
                owners = np.arange(n) // m
                counts = np.bincount(owners[s_host == np.arange(n)],
                                     minlength=p)
                term_bound = int(counts.max()) if counts.size else 0

        estimate = None
        if cfg.capacity_estimation:
            # sampled-splitter pre-pass: size mailboxes for the measured
            # destination skew instead of the static slack guess.
            if s_host is None:
                s_host = np.asarray(jax.device_get(succ))
                tr.host_sync()
            with tr.span("estimate_capacities", cat="tuner") as est_span:
                estimate = tuner.estimate_capacities(s_host, plan, m, cfg,
                                                     seed=seed)
                est_span.annotate(sample_size=estimate.sample_size,
                                  hop_slack=list(estimate.hop_slack),
                                  max_frac=list(estimate.max_frac))

        with tr.span("place", cat="frontdoor"):
            succ_d = transport_lib.put_sharded(mesh, pe_axes,
                                               jnp.asarray(succ, jnp.int32))
            # explicit weight-dtype canonicalization (chase_leaves): int
            # weights stay integer end-to-end — ±1 tour weights
            # round-trip exactly.
            wdt = canonical_weight_dtype(rank.dtype if hasattr(rank, "dtype")
                                         else np.asarray(rank).dtype)
            rank_d = transport_lib.put_sharded(mesh, pe_axes,
                                               jnp.asarray(rank, wdt))

        def build_level_specs(level_scales):
            return build_specs(cfg, plan, m, n, term_bound,
                               scales=level_scales, estimate=estimate)

        if tr.enabled and cfg.algorithm == "srs":
            from repro.obs import cost as cost_lib
            lp = tuner.level_plan(cfg, p, plan.indirection.depth, n)
            solve_span.annotate(predicted_solve_s=cost_lib.predict_solve(
                n, plan, cfg.machine, r_total=lp[0].r_total))

        succ_f, rank_f, host_stats = resume_lib.run_staged(
            succ_d, rank_d, mesh=mesh, plan=plan, cfg=cfg, m=m, n=n,
            seed=seed, build_level_specs=build_level_specs,
            max_retries=max_retries, supervisor=supervisor, inject=inject,
            stage_counters=stage_counters, initial_scales=initial_scales,
            tracer=tracer)
    except BaseException as e:
        tr.end(solve_span, outcome=type(e).__name__,
               host_syncs=tr.host_syncs - syncs_before)
        raise
    tr.end(solve_span, outcome="ok", attempts=host_stats["attempts"],
           recursion_skipped=host_stats["recursion_skipped"],
           live_links=host_stats["live_links"],
           sub_capacity=host_stats["sub_capacity"],
           host_syncs=tr.host_syncs - syncs_before)
    if "telemetry" in host_stats and estimate is not None:
        # back-test the sampled-splitter DKW margins against the skew
        # the solve actually observed (EXPERIMENTS.md §telemetry).
        recs = [tele_lib.StageRecord.from_json(d)
                for d in host_stats["telemetry"]["stages"]]
        host_stats["telemetry"]["dkw"] = tele_lib.dkw_backtest(
            list(estimate.max_frac), int(estimate.sample_size),
            [plan.hop_size(h) for h in plan.indirection.hops], recs)
    if tr.enabled:
        from repro.obs import metrics as metrics_lib
        metrics_lib.ingest_host_stats(tr.metrics, host_stats)
    return succ_f, rank_f, host_stats


def rank_list(succ, rank, mesh, **kw):
    """Convenience wrapper: returns (succ, rank) only."""
    succ_f, rank_f, _ = rank_list_with_stats(succ, rank, mesh, **kw)
    return succ_f, rank_f
