"""Sparse ruling set with ruler spawning (paper Algorithm 1 + §2.2-2.5).

Structure (all inside one ``shard_map``-ed program):

  solve_store(level):
    if base level: pointer doubling (or all-gather) base case
    else:
      chase: bulk-synchronous wave rounds with ruler spawning
      extract ruler∪terminal subproblem into a sparse store
      solve_store(level+1)
      write back + ruler propagation (remote gather, aggregated)

``solve_store`` ranks every element of the instance w.r.t. the *initial*
element of its list (the natural direction of forward chasing). The
caller fixes the direction either by the §2.5 postprocess (default) or
by running on the reversed instance (faithful Algorithm 1) — see api.py.

Static-shape adaptations (see DESIGN.md): fixed-capacity mailboxes with
leftover re-queuing, a windowed permutation scan for spawning, and an
outer restart loop that guarantees coverage regardless of capacity or
spawn-window choices. Every potential overflow is surfaced in ``stats``
and triggers a retry with doubled capacities in the driver.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.listrank import store as store_lib
from repro.core.listrank.config import ListRankConfig
from repro.core.listrank.doubling import allgather_solve, doubling_solve
from repro.core.listrank.exchange import (MeshPlan, compact_queue, prefix_sum,
                                          remote_gather, route_compact)
from repro.obs import telemetry as tele_lib

INT_MAX = jnp.iinfo(jnp.int32).max


@dataclasses.dataclass(frozen=True)
class LevelSpec:
    """Static per-recursion-level capacities (host-derived in api.py)."""
    cap: int                      # store capacity at this level
    r_static: int                 # static ruler-count bound per PE
    mail_caps: tuple[int, ...]    # per-hop mailbox capacity
    queue_cap: int
    spawn_window: int
    max_rounds: int
    cap_sub: int                  # capacity of the next level's store
    gather_req_cap: int
    gather_resp_cap: int
    base: bool                    # True => solve with the base case
    #: ruler fraction of the live instance (tuner.level_plan — the same
    #: derivation that sized r_static, so r_target <= r_static).
    ruler_frac: float
    #: bound on outer chase restarts (ListRankConfig.max_restarts).
    max_restarts: int


#: schema of the solver's stat counters (repro.obs.metrics ingests
#: host_stats under these help strings; keep in sync with zero_stats).
STAT_HELP = {
    "rounds": "chase rounds executed across all levels",
    "restarts": "outer chase restarts (coverage stragglers)",
    "chase_msgs": "chase wave messages routed",
    "spawn_lost": "spawn proposals dropped by the spawn window",
    "rulers": "rulers selected (final attempt, all levels)",
    "sub_size": "recursion subproblem elements extracted",
    "dropped": "FATAL: chase mailbox/queue overflow drops",
    "sub_overflow": "FATAL: recursion sub-store overflow",
    "store_miss": "FATAL: store lookups routed to a non-owner",
    "undelivered": "FATAL: gather/reversal/fixup messages undelivered",
    "pd_rounds": "pointer-doubling rounds (base case or pd algorithm)",
    "pd_msgs": "pointer-doubling gather messages",
    "reversal_msgs": "Algorithm-1 reversal preprocessing messages",
    "fixup_msgs": "\u00a72.3 restoration fixup messages",
    "max_queue": "peak chase queue occupancy (gauge)",
    "attempts": "driver attempts (1 + capacity escalations)",
    "recursion_skipped": "solves sent from prep straight to post: no live "
                         "link left after contraction (0 or 1)",
    "live_links": "links (valid, not a self-loop) that contraction left "
                  "for the recursion, over every PE",
    "sub_capacity": "slots of the recursion sub-stores (every descended "
                    "level and PE), the room sub_size fills",
}


def zero_stats():
    z = jnp.int32(0)
    return {
        "rounds": z, "restarts": z, "chase_msgs": z, "spawn_lost": z,
        "rulers": z, "sub_size": z, "dropped": z, "sub_overflow": z,
        "store_miss": z, "undelivered": z, "pd_rounds": z, "pd_msgs": z,
        "reversal_msgs": z, "fixup_msgs": z, "max_queue": z,
    }


def _merge(a, b):
    out = dict(a)
    for k, v in b.items():
        if k == "telemetry":
            # device-side telemetry pytree (cfg.telemetry): HWM leaves
            # max-merge, counters add — see repro.obs.telemetry.merge.
            out[k] = tele_lib.merge(a.get(k), v)
        elif k == "max_queue":
            out[k] = jnp.maximum(a[k], v)
        else:
            out[k] = a[k] + v
    return out


def gather_until_done(plan: MeshPlan, targets, valid, owner_of, lookup_fn,
                      req_cap, resp_cap, dedup, max_iters=16):
    """remote_gather retried until every valid query is answered.

    Abandoned in-flight fragments from a failed pass are simply dropped
    and re-requested — gathers are read-only, hence idempotent."""
    shapes = jax.eval_shape(lookup_fn, targets, valid)
    results = {k: jnp.zeros(s.shape, s.dtype) for k, s in shapes.items()}

    def cond(c):
        _, _, remaining_n, it, _, _ = c
        return (remaining_n > 0) & (it < max_iters)

    def body(c):
        results, remaining, _, it, msgs, tele = c
        resp, answered, st = remote_gather(plan, targets, remaining, owner_of,
                                           lookup_fn, req_cap, resp_cap, dedup)
        results = {k: jnp.where(answered, resp[k], v) for k, v in results.items()}
        remaining = remaining & ~answered
        rn = plan.psum(jnp.sum(remaining).astype(jnp.int32))
        if plan.telemetry:
            tele = tele_lib.merge(tele, st["telemetry"])
        return (results, remaining, rn, it + 1,
                msgs + st["req_sent"] + st["resp_sent"], tele)

    tele0 = (tele_lib.route_zero(plan.indirection.depth)
             if plan.telemetry else None)
    init = (results, valid, jnp.int32(1), jnp.int32(0), jnp.int32(0), tele0)
    results, remaining, rn, _, msgs, tele = lax.while_loop(cond, body, init)
    out_stats = {"undelivered": rn, "msgs": msgs}
    if plan.telemetry:
        out_stats["telemetry"] = tele
    return results, ~remaining & valid, out_stats


def route_until_done(plan: MeshPlan, caps, payload, dest, valid,
                     deliver_fn, carry, max_iters=64):
    """Route messages, applying deliver_fn(carry, delivered, dvalid) each
    round, re-queuing leftovers until everything is delivered. Leftover
    compaction is fused into the routing sort (route_compact).

    Returns ``(carry, pending, msgs, tele)`` — ``tele`` is the merged
    per-PE routing telemetry (None unless ``plan.telemetry``)."""
    q = dest.shape[0]

    def cond(c):
        return (c[4] > 0) & (c[5] < max_iters)

    def body(c):
        carry, payload, dest, valid, _, it, msgs, tele = c
        delivered, dval, (npl, nd, nv), dropped, st = route_compact(
            plan, caps, [(payload, dest, valid)], q)
        carry = deliver_fn(carry, delivered, dval)
        pending = plan.psum(jnp.sum(nv).astype(jnp.int32) + dropped)
        if plan.telemetry:
            tele = tele_lib.merge(tele, st["telemetry"])
        return (carry, npl, nd, nv, pending, it + 1, msgs + sum(st["sent"]),
                tele)

    tele0 = (tele_lib.route_zero(plan.indirection.depth)
             if plan.telemetry else None)
    pend0 = plan.psum(jnp.sum(valid).astype(jnp.int32))
    init = (carry, payload, dest, valid, pend0, jnp.int32(0), jnp.int32(0),
            tele0)
    carry, _, _, _, pending, _, msgs, tele = lax.while_loop(cond, body, init)
    return carry, pending, msgs, tele


# --------------------------------------------------------------------------
# chase phase
# --------------------------------------------------------------------------

def _make_rulers(st, visited, is_ruler, slots, sel):
    """Mark slots as rulers and build their wave emissions (Alg.1 l.3-5,
    9-11): emit (rank[r], succ[r], r), then succ[r]<-r, rank[r]<-0."""
    cap = st.ids.shape[0]
    slots_i = jnp.minimum(slots, cap - 1)
    slots_c = jnp.where(sel, slots, cap)
    gid = st.ids[slots_i]
    succ_r = st.succ[slots_i]
    rank_r = st.rank[slots_i]
    emit_valid = sel & (succ_r != gid)
    emissions = ({"target": succ_r, "ruler": gid, "weight": rank_r}, emit_valid)
    st = store_lib.scatter_update(st, slots_c, sel, succ=gid,
                                  rank=jnp.zeros_like(rank_r))
    visited = visited.at[slots_c].set(True, mode="drop")
    is_ruler = is_ruler.at[slots_c].set(True, mode="drop")
    return st, visited, is_ruler, emissions


def _launch_from_perm(st, visited, is_ruler, perm, r_target):
    """Exact ruler selection: the first r_target unvisited slots in perm
    order (one O(cap log cap) pass; level start and restarts)."""
    cap = st.ids.shape[0]
    pidx = jnp.minimum(perm, cap - 1)
    ok = (perm < cap) & st.valid[pidx] & ~visited[pidx]
    cnt = prefix_sum(ok.astype(jnp.int32))
    sel = ok & (cnt <= r_target)
    consumed = jnp.minimum(
        jnp.searchsorted(cnt, r_target, side="left").astype(jnp.int32) + 1,
        jnp.int32(perm.shape[0]))
    out = _make_rulers(st, visited, is_ruler, jnp.where(sel, pidx, cap), sel)
    return out, consumed, jnp.sum(sel).astype(jnp.int32)


def _spawn(st, visited, is_ruler, perm, perm_pos, window, k):
    """Windowed spawn of up to k rulers from the unvisited pool (§2.5
    Ruler Selection and Spawning: scan a random permutation onward from
    the current position, skipping visited elements)."""
    cap = st.ids.shape[0]
    w = lax.dynamic_slice(perm, (perm_pos,), (window,))
    widx = jnp.minimum(w, cap - 1)
    ok = (w < cap) & st.valid[widx] & ~visited[widx]
    cnt = prefix_sum(ok.astype(jnp.int32))
    sel = ok & (cnt <= k)
    avail = cnt[-1]
    spawned = jnp.minimum(k, avail)
    consumed = jnp.where(
        avail <= k, jnp.int32(window),
        jnp.searchsorted(cnt, k, side="left").astype(jnp.int32) + 1)
    st, visited, is_ruler, emissions = _make_rulers(
        st, visited, is_ruler, jnp.where(sel, widx, cap), sel)
    new_pos = jnp.minimum(perm_pos + consumed, jnp.int32(cap))
    return st, visited, is_ruler, new_pos, emissions, k - spawned


def _zero_frag(n: int, rank_dtype):
    """An all-invalid chase-message fragment of static size n."""
    payload = {"target": jnp.zeros(n, jnp.int32),
               "ruler": jnp.zeros(n, jnp.int32),
               "weight": jnp.zeros(n, rank_dtype)}
    return payload, jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.bool_)


def _chase(plan: MeshPlan, spec: LevelSpec, owner_of, st, visited, is_ruler,
           is_sub, forced, perm, r_target, stats):
    """The wave loop: launch → (route → process → spawn)*, with an outer
    restart loop guaranteeing coverage.

    The round state is three fixed-shape fragments — the compacted
    leftover queue plus the previous round's forward/spawn emissions —
    routed together by ``route_compact``, whose bucket sort doubles as
    queue compaction: one stable sort per hop per round, no separate
    requeue pass (see DESIGN.md)."""
    cap = st.ids.shape[0]
    qc = spec.queue_cap
    rank_dtype = st.rank.dtype
    inbox = plan.hop_size(plan.indirection.hops[-1]) * spec.mail_caps[-1]

    def emit_frag(emissions):
        pl, ev = emissions
        return pl, owner_of(pl["target"]).astype(jnp.int32), ev

    def fresh_frags(queue):
        return (queue, _zero_frag(inbox, rank_dtype),
                _zero_frag(spec.spawn_window, rank_dtype))

    def rounds(carry):
        def cond(c):
            return (c[-2] > 0) & (c[-1] < spec.max_rounds)

        def body(c):
            (st, visited, is_ruler, is_sub, perm_pos, (queue, fwd, spawn),
             stats, _, rounds_done) = c
            delivered, dval, queue2, dropped, rst = route_compact(
                plan, spec.mail_caps, [queue, fwd, spawn], qc)
            slots, found = store_lib.slot_of(st, delivered["target"])
            ok = dval & found
            old_succ = st.succ[slots]
            old_rank = st.rank[slots]
            die = is_sub[slots]
            # Alg.1: update succ/rank for every reached element (l.14 and
            # the "still update the values" rule for rulers/terminals)
            st = store_lib.scatter_update(
                st, slots, ok, succ=delivered["ruler"], rank=delivered["weight"])
            visited = visited.at[jnp.where(ok, slots, cap)].set(True, mode="drop")
            # forward the wave (l.13) unless it died on a ruler/terminal
            fwd2 = emit_frag(({"target": old_succ, "ruler": delivered["ruler"],
                               "weight": delivered["weight"] + old_rank},
                              ok & ~die))
            # ruler spawning (l.9-11): one new wave per death
            k = jnp.sum(ok & die).astype(jnp.int32)
            st, visited, is_ruler, perm_pos, spawn_emit, lost = _spawn(
                st, visited, is_ruler, perm, perm_pos, spec.spawn_window, k)
            is_sub = is_sub | is_ruler
            spawn2 = emit_frag(spawn_emit)
            qcount = (jnp.sum(queue2[2]) + jnp.sum(fwd2[2])
                      + jnp.sum(spawn2[2])).astype(jnp.int32)
            pending = plan.psum(qcount + dropped)
            upd = {
                "rounds": jnp.int32(1),
                "chase_msgs": sum(rst["sent"]).astype(jnp.int32),
                "spawn_lost": lost,
                "dropped": dropped,
                "store_miss": jnp.sum(dval & ~found).astype(jnp.int32),
                "max_queue": qcount,
            }
            if plan.telemetry:
                upd["telemetry"] = {"chase": rst["telemetry"],
                                    "queue_hwm": qcount}
            stats = _merge(stats, upd)
            return (st, visited, is_ruler, is_sub, perm_pos,
                    (queue2, fwd2, spawn2), stats, pending, rounds_done + 1)

        return lax.while_loop(cond, body, carry)

    # forced rulers (Alg.1 l.2 findInit — known initial elements) + the
    # random initial ruler set, then the main chase.
    st, visited, is_ruler, forced_emit = _make_rulers(
        st, visited, is_ruler,
        jnp.where(forced, jnp.arange(cap, dtype=jnp.int32), cap), forced)
    (st, visited, is_ruler, rand_emit), consumed, n_rulers = _launch_from_perm(
        st, visited, is_ruler, perm, r_target)
    is_sub = is_sub | is_ruler
    qpl, qd, qv, drop0 = compact_queue(
        [emit_frag(forced_emit), emit_frag(rand_emit)], qc)
    stats = _merge(stats, {
        "dropped": drop0,
        "rulers": n_rulers + jnp.sum(forced).astype(jnp.int32)})
    pend0 = plan.psum(jnp.sum(qv).astype(jnp.int32))
    carry = (st, visited, is_ruler, is_sub, consumed,
             fresh_frags((qpl, qd, qv)), stats, pend0, jnp.int32(0))
    carry = rounds(carry)

    # restart loop: cover stragglers (forward-chasing deadlock or spawn-
    # window losses — rare; see DESIGN.md). New rulers from the unvisited
    # pool; the drained fragments are folded into the fresh queue.
    def uncovered_of(c):
        st, visited = c[0], c[1]
        return plan.psum(jnp.sum(st.valid & ~visited).astype(jnp.int32))

    def r_cond(c):
        return (c[1] > 0) & (c[2] < spec.max_restarts)

    def r_body(c):
        carry, _, restarts = c
        (st, visited, is_ruler, is_sub, perm_pos, (queue, fwd, spawn),
         stats, _, rd) = carry
        (st, visited, is_ruler, emit), _, n1 = _launch_from_perm(
            st, visited, is_ruler, perm, r_target)
        is_sub = is_sub | is_ruler
        qpl, qd, qv, drop1 = compact_queue(
            [queue, fwd, spawn, emit_frag(emit)], qc)
        stats = _merge(stats, {"dropped": drop1, "rulers": n1,
                               "restarts": jnp.int32(1)})
        pend = plan.psum(jnp.sum(qv).astype(jnp.int32))
        carry = rounds((st, visited, is_ruler, is_sub, perm_pos,
                        fresh_frags((qpl, qd, qv)), stats, pend, rd))
        return carry, uncovered_of(carry), restarts + 1

    carry, uncovered, _ = lax.while_loop(
        r_cond, r_body, (carry, uncovered_of(carry), jnp.int32(0)))
    (st, visited, is_ruler, is_sub, perm_pos, _, stats, _, _) = carry
    stats = _merge(stats, {"undelivered": uncovered})
    return st, is_sub, stats


def flip_direction(plan: MeshPlan, spec: LevelSpec, owner_of, st, is_term0,
                   stats):
    """Direction flip (paper §2.5): convert initial-ranking into
    sink(terminal)-ranking. Terminals report (their id, list length) to
    the initial element's owner; every element then asks its initial
    (requests aggregated per PE) and sets
      succ <- terminal,  rank <- total - rank.

    Applied at every recursion level: the level's chase+propagation
    produces initial-ranking, while the parent (and the user) need
    sink-ranking. At the top level this *is* the paper's reversal-
    avoiding postprocess, costing O(#lists * p) aggregated messages.
    """
    cap = st.cap
    gid = st.ids
    term_of = jnp.zeros(cap, jnp.int32)
    total_of = jnp.zeros_like(st.rank)
    have = jnp.zeros(cap, jnp.bool_)

    payload = {"target": st.succ, "term": gid, "total": st.rank}
    dest = owner_of(st.succ).astype(jnp.int32)

    def deliver(carry, delivered, dval):
        term_of, total_of, have = carry
        slots, found = store_lib.slot_of(st, delivered["target"])
        ok = dval & found
        idx = jnp.where(ok, slots, cap)
        term_of = term_of.at[idx].set(delivered["term"], mode="drop")
        total_of = total_of.at[idx].set(delivered["total"], mode="drop")
        have = have.at[idx].set(True, mode="drop")
        return term_of, total_of, have

    mail = tuple(max(c, 8) for c in spec.mail_caps)
    (term_of, total_of, have), pending, msgs, rtele = route_until_done(
        plan, mail, payload, dest, is_term0, deliver,
        (term_of, total_of, have))

    def lookup_fn(gids, valid):
        slots, found = store_lib.slot_of(st, gids)
        ok = found & valid & have[slots]
        return {"term": jnp.where(ok, term_of[slots], gids),
                "total": jnp.where(ok, total_of[slots],
                                   jnp.zeros_like(total_of[slots])),
                "found": ok}

    resp, answered, gst = gather_until_done(
        plan, st.succ, st.valid, owner_of, lookup_fn,
        spec.gather_req_cap, spec.gather_resp_cap, dedup=True)
    upd = answered & resp["found"]
    out = st.replace(succ=jnp.where(upd, resp["term"], st.succ),
                     rank=jnp.where(upd, resp["total"] - st.rank, st.rank))
    fix = {
        "fixup_msgs": msgs + gst["msgs"],
        "undelivered": pending + gst["undelivered"] +
        plan.psum(jnp.sum(st.valid & ~upd).astype(jnp.int32))}
    if plan.telemetry:
        # the terminal-report leg rides the chase-family mail caps; the
        # initial lookup rides the gather caps.
        fix["telemetry"] = {"chase": rtele, "gather": gst["telemetry"]}
    stats = _merge(stats, fix)
    return out, stats


# --------------------------------------------------------------------------
# recursion driver
# --------------------------------------------------------------------------

def _extract_sub(st, is_sub, cap_sub):
    cap = st.ids.shape[0]
    member = st.valid & is_sub
    score = jnp.where(member, jnp.arange(cap, dtype=jnp.int32), INT_MAX)
    order = jnp.argsort(score)
    take = order[:cap_sub]
    n_sub = jnp.sum(member).astype(jnp.int32)
    sval = jnp.arange(cap_sub, dtype=jnp.int32) < jnp.minimum(n_sub, cap_sub)
    sub = store_lib.Store(
        ids=jnp.where(sval, st.ids[take], INT_MAX),
        succ=jnp.where(sval, st.succ[take], INT_MAX),
        rank=jnp.where(sval, st.rank[take], jnp.zeros_like(st.rank[take])),
        valid=sval,
        dense=False,
    )
    overflow = jnp.maximum(n_sub - cap_sub, 0)
    return sub, take, overflow


def base_level(plan: MeshPlan, cfg: ListRankConfig, spec: LevelSpec,
               owner_of, st, stats):
    """The recursion's base case: pointer doubling (or all-gather)."""
    if cfg.base_case == "allgather":
        st, pst = allgather_solve(plan, st, spec.max_rounds)
    else:
        st, pst = doubling_solve(plan, st, owner_of, spec.gather_req_cap,
                                 spec.gather_resp_cap, spec.max_rounds,
                                 dedup=cfg.dedup_requests)
    upd = {"pd_rounds": pst["pd_rounds"], "pd_msgs": pst["pd_msgs"],
           "undelivered": pst["pd_undelivered"]}
    if plan.telemetry and "telemetry" in pst:
        upd["telemetry"] = {"gather": pst["telemetry"]}
    stats = _merge(stats, upd)
    return st, stats


def descend_level(plan: MeshPlan, cfg: ListRankConfig, spec: LevelSpec,
                  owner_of, st, key, level: int, stats, forced=None):
    """The downward half of one recursion level: chase + subproblem
    extraction. Returns ``(st, sub, take, is_sub, is_term, stats)`` —
    everything :func:`ascend_level` needs to finish the level once the
    subproblem is solved (the tuple is a level-boundary checkpointable
    pytree, see api/resume)."""
    cap = st.ids.shape[0]
    is_term = st.valid & (st.succ == st.ids)
    visited = is_term | ~st.valid
    is_ruler = jnp.zeros(cap, jnp.bool_)
    is_sub = is_term
    if forced is None:
        forced = jnp.zeros(cap, jnp.bool_)
    forced = forced & st.valid & ~is_term

    pe = plan.my_id()
    k_pe = jax.random.fold_in(jax.random.fold_in(key, level), pe)
    perm = jax.random.permutation(k_pe, cap).astype(jnp.int32)
    perm = jnp.concatenate(
        [perm, jnp.full((spec.spawn_window,), cap, jnp.int32)])

    # ruler target: the level's tuned fraction of the live instance,
    # clipped to the static bound derived from the same fraction
    # (spec.ruler_frac comes from tuner.level_plan via build_specs —
    # there is no separate fallback here).
    n_active = jnp.sum(st.valid).astype(jnp.int32)
    r_target = jnp.maximum(jnp.int32(cfg.min_rulers_per_pe),
                           (spec.ruler_frac * n_active).astype(jnp.int32))
    r_target = jnp.minimum(r_target, jnp.int32(spec.r_static))

    st, is_sub, stats = _chase(plan, spec, owner_of, st, visited, is_ruler,
                               is_sub, forced, perm, r_target, stats)

    sub, take, overflow = _extract_sub(st, is_sub, spec.cap_sub)
    n_sub = jnp.sum(sub.valid).astype(jnp.int32)
    upd = {"sub_overflow": overflow, "sub_size": n_sub}
    if plan.telemetry:
        # sub-store occupancy as a fill record: demand (incl. overflow)
        # over the compiled cap_sub — >1 explains a sub escalation.
        upd["telemetry"] = {"sub": tele_lib.store_fill(
            plan.indirection.depth, n_sub + overflow, spec.cap_sub)}
    stats = _merge(stats, upd)
    return st, sub, take, is_sub, is_term, stats


def ascend_level(plan: MeshPlan, cfg: ListRankConfig, spec: LevelSpec,
                 owner_of, st, sub, take, is_sub, is_term, stats,
                 want_sink: bool = True):
    """The upward half of one recursion level: write back the solved
    subproblem, propagate through rulers, flip direction if the caller
    wants sink-ranking."""
    cap = st.ids.shape[0]
    # write back solved sub elements
    idx = jnp.where(sub.valid, take, cap)
    st = st.replace(succ=st.succ.at[idx].set(sub.succ, mode="drop"),
                    rank=st.rank.at[idx].set(sub.rank, mode="drop"))

    # ruler propagation (Alg.1 l.16-19): non-sub elements ask their ruler
    non_sub = st.valid & ~is_sub
    resp, answered, gst = gather_until_done(
        plan, st.succ, non_sub, owner_of,
        lambda g, v: store_lib.lookup(st, g, v),
        spec.gather_req_cap, spec.gather_resp_cap, cfg.dedup_requests)
    upd = answered & resp["found"]
    st = st.replace(succ=jnp.where(upd, resp["succ"], st.succ),
                    rank=jnp.where(upd, st.rank + resp["rank"], st.rank))
    prop = {
        "undelivered": gst["undelivered"] +
        plan.psum(jnp.sum(non_sub & ~upd).astype(jnp.int32)),
        "fixup_msgs": gst["msgs"]}
    if plan.telemetry:
        prop["telemetry"] = {"gather": gst["telemetry"]}
    stats = _merge(stats, prop)

    if want_sink:
        st, stats = flip_direction(plan, spec, owner_of, st, is_term, stats)
    return st, stats


def solve_store(plan: MeshPlan, cfg: ListRankConfig, specs: list[LevelSpec],
                owner_of, st, key, level: int, stats, forced=None,
                want_sink: bool = True):
    """Recursively solve the instance in ``st``.

    Returns sink-ranking (succ -> the self-loop end of each list, rank =
    weighted distance to it) when ``want_sink``; otherwise the raw
    initial-ranking that forward chasing produces (used by the faithful
    Algorithm-1 variant, whose input is the reversed instance).

    Internal recursion always requests sink-ranking: the extracted
    subproblem's self-loop ends are exactly this level's unreached
    initials, which is what ruler propagation composes with.

    The body is exactly ``descend_level`` → recurse → ``ascend_level``
    (``base_level`` at the bottom) — the same stage functions the
    level-resumable driver (api/resume) runs one at a time, so the
    monolithic and staged programs are op-for-op identical."""
    spec = specs[level]

    if spec.base:
        return base_level(plan, cfg, spec, owner_of, st, stats)

    st, sub, take, is_sub, is_term, stats = descend_level(
        plan, cfg, spec, owner_of, st, key, level, stats, forced)

    sub, stats = solve_store(plan, cfg, specs, owner_of, sub, key, level + 1,
                             stats, want_sink=True)

    return ascend_level(plan, cfg, spec, owner_of, st, sub, take, is_sub,
                        is_term, stats, want_sink)
