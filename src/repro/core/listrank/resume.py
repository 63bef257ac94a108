"""Level-resumable solver: the SRS recursion as an explicit state machine.

The monolithic per-PE program (``api._solve_sharded``) is a composition
of stage bodies (``srs.base_level`` / ``descend_level`` /
``ascend_level`` plus prep/restore) under one jit. This module runs the
*same* bodies one stage at a time, materializing the state at every
level boundary as a checkpointable pytree:

    prep -> descend@0 .. descend@L-1 -> base@L -> ascend@L-1 .. ascend@0 -> post
    prep -> pd@0 -> post                                   (plain doubling)

Because the staged program is built from the exact functions the
monolithic program composes, a straight-through staged solve is
op-for-op identical to the monolithic one — the golden bit-identity
pins (tests/golden) hold for both by construction.

What the explicit boundary state buys (DESIGN.md §11):

- **level resume**: a fatal capacity overflow at stage k re-runs *only*
  stage k with that capacity family escalated for levels >= k
  (``tuner.escalate_levels``); completed levels' scales — and therefore
  the checkpointed store shapes — are untouched. The old driver
  restarted the whole solve from scratch.
- **checkpoint/restart**: a :class:`~repro.runtime.fault_tolerance.
  SolveSupervisor` checkpoints the boundary state (atomic keep-k,
  async); SIGTERM/SIGINT preemption writes a blocking checkpoint and
  raises ``Preempted``; a restarted driver restores and continues from
  the boundary. Checkpoints hold *global* (host-gathered) arrays plus a
  manifest meta, so the restore is elastic: a mesh-backend checkpoint
  resumes under simshard and vice versa, bit-identically.
- **deterministic fault injection** (:mod:`.faults`): PE loss,
  corrupted state planes, forced overflows and preemption fire at named
  stage boundaries, driving the recovery paths in-process under the
  simshard backend for any p.

The boundary state is a dict pytree; every leaf is block-sharded over
the PE axes on axis 0 (per-PE stats ride as (1,)-per-PE slices):

    stores:   (store_0, ..., store_j)   recursion store stack
    takes:    per descended level, the sub-extraction slot map
    is_subs:  per descended level, the sub-membership mask
    is_terms: per descended level, the level's terminal mask
    stats:    per-PE partial stat counters (psum'd once, in post)
    forced:   [srs only, until descend@0] forced-ruler mask
    rep/aux:  [local_contraction only] restoration inputs (§2.3)
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.listrank import faults as faults_lib
from repro.core.listrank import introspect
from repro.core.listrank import local as local_lib
from repro.core.listrank import srs as srs_lib
from repro.core.listrank import store as store_lib
from repro.core.listrank import transport as transport_lib
from repro.core.listrank import tuner
from repro.core.listrank.config import ListRankConfig
from repro.core.listrank.doubling import doubling_solve
from repro.core.listrank.srs import zero_stats, _merge
from repro.obs import telemetry as tele_lib
from repro.obs import trace as trace_lib
from repro.runtime.fault_tolerance import Preempted

#: stat keys whose nonzero value means the attempt is unusable.
FATAL_KEYS = ("dropped", "sub_overflow", "store_miss", "undelivered")

#: capacity family -> the fatal stat the driver synthesizes for an
#: injected overflow of that family (the inverse of tuner.FAMILY_OF
#: restricted to the capacity-exclusive solver families).
FAMILY_STAT = {"chase": "dropped", "sub": "sub_overflow",
               "gather": "undelivered"}


class SolveExhausted(RuntimeError):
    """The retry/escalation budget ran out.

    Structured for assertions: ``attempts`` (total), ``scales_log``
    (the full per-attempt escalation path, as rendered in host_stats),
    ``fatal`` (fatal stat -> its count in the failing attempt),
    ``families`` (the capacity families those stats implicate), and
    ``stats`` (the failing attempt's full host counter dict).
    """

    def __init__(self, attempts: int, scales_log, fatal: dict, stats=None):
        self.attempts = int(attempts)
        self.scales_log = tuple(scales_log)
        self.fatal = {k: int(v) for k, v in fatal.items()}
        self.families = tuple(sorted({
            f for k, v in self.fatal.items() if v
            for f in tuner.FAMILY_OF.get(k, ())}))
        self.stats = dict(stats or {})
        super().__init__(
            f"list ranking did not complete after {self.attempts} attempts")

    def __str__(self) -> str:
        """Readable exhaustion report: the per-attempt escalation path
        (each entry is a ``tuner.format_scales`` rendering, ``@Lk`` for
        level-targeted escalations) and the fatal stats with the
        capacity families they implicate."""
        lines = [f"list ranking did not complete after {self.attempts} "
                 f"attempts (capacity escalation exhausted)",
                 "  escalation path:"]
        for i, entry in enumerate(self.scales_log, start=1):
            lines.append(f"    attempt {i}: {entry}")
        lines.append("  fatal stats of the failing attempt:")
        for key, count in sorted(self.fatal.items()):
            if not count:
                continue
            fams = tuner.FAMILY_OF.get(key, ())
            fam_s = (f" -> escalates {', '.join(fams)}" if fams
                     else " (no capacity family)")
            lines.append(f"    {key}={count}{fam_s}")
        if not any(self.fatal.values()):
            lines.append("    (none recorded)")
        return "\n".join(lines)


# --------------------------------------------------------------------------
# the schedule
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Stage:
    """One stage of the staged solve. ``level`` is the recursion level
    for descend/base/ascend (pd pins 0); -1 for prep/post."""
    kind: str      # prep | descend | base | ascend | pd | post
    level: int

    @property
    def label(self) -> str:
        return self.kind if self.level < 0 else f"{self.kind}@{self.level}"


def schedule_for(cfg: ListRankConfig) -> tuple[Stage, ...]:
    """The stage schedule for a resolved config (algorithm != auto)."""
    if cfg.algorithm == "doubling":
        return (Stage("prep", -1), Stage("pd", 0), Stage("post", -1))
    L = cfg.srs_rounds
    out = [Stage("prep", -1)]
    out += [Stage("descend", k) for k in range(L)]
    out += [Stage("base", L)]
    out += [Stage("ascend", k) for k in reversed(range(L))]
    out += [Stage("post", -1)]
    return tuple(out)


def _stage_specs(stage: Stage, specs) -> tuple:
    """The LevelSpecs a stage body closes over (part of the jit key)."""
    if stage.kind in ("prep", "post"):
        return (specs[0],)
    if stage.kind == "pd":
        return (specs[0], specs[-1])
    if stage.kind == "base":
        return (specs[-1],)
    return (specs[stage.level],)


# --------------------------------------------------------------------------
# stage bodies (per-PE; run under device_run on either backend)
# --------------------------------------------------------------------------

def _owner_fn(m: int):
    def owner_of(g):
        return g // m
    return owner_of


def _stats_out(stats):
    """Per-PE scalar stats -> (1,)-per-PE leaves (shardable on axis 0)."""
    return {k: jnp.reshape(v, (1,)) for k, v in stats.items()}


def _stats_in(stats):
    return {k: jnp.reshape(v, ()) for k, v in stats.items()}


def _tele_seed(stats, plan):
    """Seed the per-stage device telemetry record (cfg.telemetry): a
    fresh ``stage_zero`` per stage so telemetry is attributed per stage
    instead of accumulating through the boundary state. The record is
    popped again by :func:`_tele_pop` before the stats re-enter the
    committed boundary (``boundary_template`` is unchanged — telemetry
    never reaches a checkpoint)."""
    if plan.telemetry:
        stats["telemetry"] = tele_lib.stage_zero(plan.indirection.depth)
    return stats


def _tele_pop(stats, plan):
    """Split a stage's stats into (plain stats, per-PE telemetry-out).
    The telemetry leaves gain a leading (1,)-per-PE axis so the same
    block sharding as the stats applies."""
    if not plan.telemetry:
        return stats, None
    tele = stats.pop("telemetry")
    return stats, jax.tree.map(lambda v: v[None], tele)


def _prep_body(succ, rank, *, plan, cfg, spec0, m):
    """Everything before the recursion: contraction, store build, and
    (faithful Algorithm 1 only) the reversal preprocessing."""
    from repro.core.listrank import api as api_lib
    pe = plan.my_id().astype(jnp.int32)
    base = pe * m
    gid = base + jnp.arange(m, dtype=jnp.int32)
    stats = _tele_seed(zero_stats(), plan)
    owner_of = _owner_fn(m)

    if cfg.local_contraction:
        succ_w, rank_w, rep, aux = local_lib.contract(
            succ, rank, base, m, cfg.use_pallas)
        active = rep
    else:
        rep, aux = None, None
        succ_w, rank_w = succ, rank
        active = jnp.ones(m, jnp.bool_)

    is_term0 = active & (succ_w == gid)
    st = store_lib.make_dense_store(succ_w, rank_w, active, base)

    state = {}
    if cfg.algorithm == "srs":
        if cfg.avoid_reversal:
            # solve_store(forced=None) builds an all-false mask itself;
            # carrying the zeros explicitly is bit-identical.
            state["forced"] = jnp.zeros(m, jnp.bool_)
        else:
            st, stats = api_lib._reverse_instance(plan, spec0, owner_of, st,
                                                  stats)
            state["forced"] = is_term0
    state["stores"] = (st,)
    state["takes"] = ()
    state["is_subs"] = ()
    state["is_terms"] = ()
    if cfg.local_contraction:
        state["rep"] = rep
        state["aux"] = aux
    stats, tele = _tele_pop(stats, plan)
    state["stats"] = _stats_out(stats)
    if tele is not None:
        state["_telemetry"] = tele
    return state


def _descend_body(state, seed, *, plan, cfg, spec, level, m):
    owner_of = _owner_fn(m)
    key = jax.random.PRNGKey(seed)
    stats = _tele_seed(_stats_in(state["stats"]), plan)
    st = state["stores"][-1]
    forced = state.get("forced") if level == 0 else None
    st, sub, take, is_sub, is_term, stats = srs_lib.descend_level(
        plan, cfg, spec, owner_of, st, key, level, stats, forced)
    out = {k: v for k, v in state.items() if k != "forced"}
    out["stores"] = state["stores"][:-1] + (st, sub)
    out["takes"] = state["takes"] + (take,)
    out["is_subs"] = state["is_subs"] + (is_sub,)
    out["is_terms"] = state["is_terms"] + (is_term,)
    stats, tele = _tele_pop(stats, plan)
    out["stats"] = _stats_out(stats)
    if tele is not None:
        out["_telemetry"] = tele
    return out


def _base_body(state, *, plan, cfg, spec, m):
    stats = _tele_seed(_stats_in(state["stats"]), plan)
    st, stats = srs_lib.base_level(plan, cfg, spec, _owner_fn(m),
                                   state["stores"][-1], stats)
    out = dict(state)
    out["stores"] = state["stores"][:-1] + (st,)
    stats, tele = _tele_pop(stats, plan)
    out["stats"] = _stats_out(stats)
    if tele is not None:
        out["_telemetry"] = tele
    return out


def _ascend_body(state, *, plan, cfg, spec, level, m, want_sink):
    stats = _tele_seed(_stats_in(state["stats"]), plan)
    st, sub = state["stores"][-2], state["stores"][-1]
    st, stats = srs_lib.ascend_level(
        plan, cfg, spec, _owner_fn(m), st, sub,
        state["takes"][-1], state["is_subs"][-1], state["is_terms"][-1],
        stats, want_sink)
    out = dict(state)
    out["stores"] = state["stores"][:-2] + (st,)
    out["takes"] = state["takes"][:-1]
    out["is_subs"] = state["is_subs"][:-1]
    out["is_terms"] = state["is_terms"][:-1]
    stats, tele = _tele_pop(stats, plan)
    out["stats"] = _stats_out(stats)
    if tele is not None:
        out["_telemetry"] = tele
    return out


def _pd_body(state, *, plan, cfg, spec0, spec_base, m):
    stats = _tele_seed(_stats_in(state["stats"]), plan)
    st, pst = doubling_solve(plan, state["stores"][-1], _owner_fn(m),
                             spec0.gather_req_cap, spec0.gather_resp_cap,
                             spec_base.max_rounds, cfg.dedup_requests)
    upd = {"pd_rounds": pst["pd_rounds"],
           "pd_msgs": pst["pd_msgs"],
           "undelivered": pst["pd_undelivered"]}
    if plan.telemetry:
        # PD requests ride the gather-family mailboxes (req/resp caps).
        upd["telemetry"] = {"gather": pst["telemetry"]}
    stats = _merge(stats, upd)
    out = dict(state)
    out["stores"] = state["stores"][:-1] + (st,)
    stats, tele = _tele_pop(stats, plan)
    out["stats"] = _stats_out(stats)
    if tele is not None:
        out["_telemetry"] = tele
    return out


def _post_body(state, succ, rank, *, plan, cfg, spec0, m):
    """Everything after the recursion: §2.3 restoration and the final
    stat reduction (the one psum over the carried per-PE partials)."""
    from repro.core.listrank import api as api_lib
    pe = plan.my_id().astype(jnp.int32)
    base = pe * m
    stats = _tele_seed(_stats_in(state["stats"]), plan)
    st = state["stores"][0]
    if cfg.local_contraction:
        succ_f, rank_f, stats = api_lib._restore_local(
            plan, spec0, _owner_fn(m), st, state["aux"], state["rep"],
            succ, rank, base, stats)
    else:
        succ_f, rank_f = st.succ, st.rank
    # telemetry leaves stay per-PE: the one stat psum below must not
    # grow any collectives when telemetry is on (pinned by the
    # transport-audit count tests), so pop before reducing.
    stats, tele = _tele_pop(stats, plan)
    stats = {k: plan.psum(v) for k, v in stats.items()}
    if tele is not None:
        return succ_f, rank_f, stats, tele
    return succ_f, rank_f, stats


@functools.lru_cache(maxsize=512)
def _jitted_stage(mesh, plan, cfg, stage: Stage, key_specs, m):
    """Jit one stage for one backend; keyed exactly on what the traced
    program depends on (the stage's own LevelSpecs, not the full spec
    tuple — escalating level k never retraces completed stages)."""
    sh = P(plan.pe_axes)
    rep = P()
    if stage.kind == "prep":
        fn = functools.partial(_prep_body, plan=plan, cfg=cfg,
                               spec0=key_specs[0], m=m)
        in_specs, out_specs = (sh, sh), sh
    elif stage.kind == "descend":
        fn = functools.partial(_descend_body, plan=plan, cfg=cfg,
                               spec=key_specs[0], level=stage.level, m=m)
        in_specs, out_specs = (sh, rep), sh
    elif stage.kind == "base":
        fn = functools.partial(_base_body, plan=plan, cfg=cfg,
                               spec=key_specs[0], m=m)
        in_specs, out_specs = (sh,), sh
    elif stage.kind == "ascend":
        want_sink = stage.level > 0 or cfg.avoid_reversal
        fn = functools.partial(_ascend_body, plan=plan, cfg=cfg,
                               spec=key_specs[0], level=stage.level, m=m,
                               want_sink=want_sink)
        in_specs, out_specs = (sh,), sh
    elif stage.kind == "pd":
        fn = functools.partial(_pd_body, plan=plan, cfg=cfg,
                               spec0=key_specs[0], spec_base=key_specs[1],
                               m=m)
        in_specs, out_specs = (sh,), sh
    elif stage.kind == "post":
        fn = functools.partial(_post_body, plan=plan, cfg=cfg,
                               spec0=key_specs[0], m=m)
        in_specs = (sh, sh, sh)
        # telemetry-on: the per-PE telemetry record is a 4th output
        # (prefix spec sh covers the whole subtree).
        out_specs = (sh, sh, rep, sh) if plan.telemetry else (sh, sh, rep)
    else:
        raise ValueError(f"unknown stage kind {stage.kind!r}")
    return transport_lib.device_run(mesh, plan.pe_axes, fn,
                                    in_specs=in_specs, out_specs=out_specs)


# --------------------------------------------------------------------------
# boundary-state templates (for elastic checkpoint restore)
# --------------------------------------------------------------------------

def boundary_template(sched, idx: int, cfg: ListRankConfig, specs, m: int,
                      p: int, weight_dtype):
    """The abstract (ShapeDtypeStruct) boundary-state pytree after the
    first ``idx`` stages of ``sched`` — global (host-gathered) shapes,
    so a checkpoint written by either backend restores into it."""
    if idx < 1:
        raise ValueError("no boundary state before the prep stage")
    wdt = jnp.dtype(weight_dtype)
    caps = [m]                      # store-capacity stack
    take_caps: list[int] = []
    has_forced = cfg.algorithm != "doubling"
    for stage in sched[1:idx]:
        if stage.kind == "descend":
            take_caps.append(specs[stage.level].cap_sub)
            caps.append(specs[stage.level].cap_sub)
            if stage.level == 0:
                has_forced = False
        elif stage.kind == "ascend":
            caps.pop()
            take_caps.pop()
        # base / pd leave the structure unchanged

    def arr(cap, dtype):
        return jax.ShapeDtypeStruct((p * cap,), dtype)

    def store_t(j, cap):
        return store_lib.Store(ids=arr(cap, jnp.int32),
                               succ=arr(cap, jnp.int32),
                               rank=arr(cap, wdt),
                               valid=arr(cap, jnp.bool_),
                               dense=(j == 0))

    state = {}
    if has_forced:
        state["forced"] = arr(m, jnp.bool_)
    state["stores"] = tuple(store_t(j, c) for j, c in enumerate(caps))
    state["takes"] = tuple(arr(c, jnp.int32) for c in take_caps)
    # the level-k masks cover the store that was live when level k
    # descended: caps[k] for every descended-but-not-ascended level.
    state["is_subs"] = tuple(arr(c, jnp.bool_) for c in caps[:-1]) \
        if take_caps else ()
    state["is_terms"] = state["is_subs"]
    if cfg.local_contraction:
        state["rep"] = arr(m, jnp.bool_)
        state["aux"] = {"S": arr(m, jnp.int32), "D": arr(m, wdt),
                        "stop_is_term": arr(m, jnp.bool_)}
    state["stats"] = {k: jax.ShapeDtypeStruct((p,), jnp.int32)
                      for k in zero_stats()}
    return state


def state_shardings(mesh, plan, like):
    """Block-sharded placement for every boundary-state leaf (None on a
    SimMesh — the simshard runner folds the PE axis itself)."""
    if transport_lib.is_sim(mesh):
        return None
    sh = NamedSharding(mesh, P(plan.pe_axes))
    return jax.tree.map(lambda _: sh, like)


def solve_fingerprint(succ, rank, n: int, p: int, seed: int,
                      cfg: ListRankConfig) -> str:
    """Identity of a solve for restore validation: instance bytes plus
    the backend-independent config. A checkpoint restores only into the
    same logical solve — on either backend (elastic), since backend and
    kernel toggles never change the computed bits."""
    h = hashlib.sha256()
    h.update(np.asarray(jax.device_get(succ)).astype(np.int32).tobytes())
    h.update(np.asarray(jax.device_get(rank)).tobytes())
    key = (n, p, int(seed),
           cfg.with_(backend="auto", use_pallas=False, use_pallas_pack=False))
    h.update(repr(key).encode())
    return h.hexdigest()


# --------------------------------------------------------------------------
# host-side state validation + corruption
# --------------------------------------------------------------------------

def validate_state(state, n: int) -> None:
    """Host-side invariant check of a boundary state: every valid store
    slot must hold ids/succ inside [0, n). Catches the ``corrupt``
    injection's sentinel (and real bit-rot) before it is checkpointed
    or consumed by the next stage."""
    for j, st in enumerate(state["stores"]):
        valid = np.asarray(jax.device_get(st.valid))
        for plane in ("ids", "succ"):
            v = np.asarray(jax.device_get(getattr(st, plane)))
            bad = valid & ((v < 0) | (v >= n))
            if bad.any():
                k = int(np.argmax(bad))
                raise faults_lib.CorruptedState(
                    f"store {j} plane {plane!r}: invalid global id "
                    f"{int(v[k])} at slot {k} (n={n})")


def _apply_corruption(state, spec: faults_lib.FaultSpec, mesh, plan, m: int):
    """Scribble the corrupt sentinel over PE ``spec.pe``'s slice of the
    top store's ``spec.plane`` — a lost/garbled mailbox plane."""
    st = state["stores"][0]
    leaf = np.asarray(jax.device_get(getattr(st, spec.plane))).copy()
    pe = spec.pe % max(plan.p, 1)
    leaf[pe * m:(pe + 1) * m] = faults_lib.CORRUPT_SENTINEL
    leaf_d = transport_lib.put_sharded(mesh, plan.pe_axes, jnp.asarray(leaf))
    out = dict(state)
    out["stores"] = (st.replace(**{spec.plane: leaf_d}),) \
        + state["stores"][1:]
    return out


def _fatal_totals(stats) -> dict:
    """Global fatal-stat totals from a boundary state's per-PE stats (or
    post's already-reduced dict)."""
    return {k: int(np.sum(np.asarray(jax.device_get(stats[k]))))
            for k in FATAL_KEYS}


# --------------------------------------------------------------------------
# the driver
# --------------------------------------------------------------------------

def run_staged(succ_d, rank_d, *, mesh, plan, cfg: ListRankConfig, m: int,
               n: int, seed: int, build_level_specs, max_retries: int = 3,
               supervisor=None, inject=None, stage_counters: bool = False,
               initial_scales=None, tracer=None):
    """Run the staged solve to completion. Returns (succ, rank, stats).

    ``build_level_specs(level_scales) -> tuple[LevelSpec]`` is the
    host-side capacity derivation (api.build_specs closed over the
    instance parameters). ``supervisor`` (a
    :class:`~repro.runtime.fault_tolerance.SolveSupervisor`) enables
    checkpoint/restart + preemption; ``inject`` (a
    :class:`~repro.core.listrank.faults.FaultInjector`, FaultSpec, or
    sequence of FaultSpecs) drives the recovery paths deterministically;
    ``stage_counters`` records each executed stage's traced collective
    counts in ``host_stats["stage_collectives"]``; ``tracer`` (a
    :class:`repro.obs.Tracer`) records the flight-recorder span tree —
    one ``stage`` span per schedule slot with one nested
    ``stage-attempt`` span per execution, each annotated with the
    §2.6 predicted time and the stage's static collective footprint.
    The tracer is host-side only: it never enters a jit key or a traced
    body, so the executed programs are bit-identical with it on or off.
    """
    p = plan.p
    wdt = rank_d.dtype
    sched = schedule_for(cfg)
    n_levels = cfg.srs_rounds + 1
    tr = trace_lib.ensure(tracer)
    injector = inject
    if injector is not None and not isinstance(injector,
                                               faults_lib.FaultInjector):
        injector = faults_lib.FaultInjector(injector)

    level_scales = tuner.normalize_level_scales(
        initial_scales if initial_scales is not None
        else tuner.CapacityScales(), n_levels)
    attempts = 1
    scales_log = [tuner.format_scales(level_scales[0])]
    stage_log: list[str] = []
    injected_log: list[str] = []
    stage_collectives: list[tuple] = []
    tele_records: list[tele_lib.StageRecord] = []
    crashes = 0
    if supervisor is not None:
        supervisor.tracer = tr

    fp = solve_fingerprint(succ_d, rank_d, n, p, seed, cfg)

    # one stage span per schedule slot stays open across its overflow
    # retries (attempts nest under it); footprints are static per jitted
    # runner, so they are counted once and cached by runner identity
    # (runners are pinned alive by the _jitted_stage lru_cache).
    stage_span, stage_span_idx, stage_attempt = None, -1, 0
    footprint_cache: dict[int, dict] = {}

    def close_stage_span(**kw):
        nonlocal stage_span
        if stage_span is not None:
            tr.end(stage_span, **kw)
            stage_span = None

    def stage_prediction(runner, args):
        """(annotations dict) — static §2.6 prediction of one stage
        execution from its jaxpr collective footprint. Trace-only: no
        device code runs, nothing about the solve changes."""
        from repro.obs import cost as cost_lib
        key = id(runner)
        if key not in footprint_cache:
            footprint_cache[key] = introspect.collective_footprint(
                runner, *args)
        fprint = footprint_cache[key]
        pred = cost_lib.predict_stage(fprint, plan, cfg.machine,
                                      transport_lib.is_sim(mesh))
        count, nbytes = cost_lib.total_collectives(fprint)
        if transport_lib.is_sim(mesh):
            nbytes //= max(p, 1)  # marker operands carry the vPE axis
        return {"predicted_s": pred["total_s"],
                "predicted_startup_s": pred["startup_s"],
                "predicted_volume_s": pred["volume_s"],
                "collective_count": count, "payload_bytes": nbytes,
                "footprint": cost_lib.footprint_summary(fprint)}

    def make_meta(idx):
        return {"format": 1, "idx": idx, "fingerprint": fp, "n": n, "p": p,
                "m": m, "algorithm": cfg.algorithm, "attempts": attempts,
                "scales_log": list(scales_log),
                "scales": [dataclasses.asdict(s) for s in level_scales],
                "weight_dtype": str(wdt)}

    def try_restore():
        """(state, idx, prev_fatal) from the supervisor's latest valid
        checkpoint, or None."""
        if supervisor is None:
            return None
        # drain any in-flight async boundary write: the latest committed
        # boundary must be durable (and its failure surfaced) before we
        # decide where to resume from.
        supervisor.ckpt.wait()
        meta = supervisor.latest_meta()
        if not meta or meta.get("fingerprint") != fp:
            return None
        nonlocal level_scales, attempts, scales_log
        level_scales = tuple(tuner.CapacityScales(**d)
                             for d in meta["scales"])
        attempts = int(meta["attempts"])
        scales_log = list(meta["scales_log"])
        specs = build_level_specs(level_scales)
        like = boundary_template(sched, meta["idx"], cfg, specs, m, p,
                                 jnp.dtype(meta["weight_dtype"]))
        state, _ = supervisor.restore(like, state_shardings(mesh, plan, like))
        supervisor.stats["resumed_from"] = int(meta["idx"])
        return state, int(meta["idx"]), _fatal_totals(state["stats"])

    state, idx = None, 0
    prev_fatal = {k: 0 for k in FATAL_KEYS}
    restored = try_restore()
    if restored is not None:
        state, idx, prev_fatal = restored

    while idx < len(sched):
        stage = sched[idx]
        if supervisor is not None and supervisor.preempted:
            if state is not None:
                supervisor.boundary(idx, state, make_meta(idx),
                                    blocking=True)
            supervisor.stats["preempted"] += 1
            raise Preempted(
                f"preempted at stage boundary {idx}/{len(sched)}")
        if stage_span_idx != idx:
            close_stage_span(outcome="abandoned")  # crash rewound idx
            stage_span = tr.begin(stage.label, cat="stage",
                                  stage=stage.kind, level=stage.level,
                                  schedule_idx=idx)
            stage_span_idx, stage_attempt = idx, 0
        stage_attempt += 1
        specs = build_level_specs(level_scales)
        att = tr.begin(f"{stage.label}#{stage_attempt}", cat="stage-attempt",
                       stage=stage.label, level=stage.level,
                       attempt=stage_attempt,
                       scales=tuner.format_scales(
                           level_scales[max(stage.level, 0)]))
        try:
            if injector is not None:
                injector.crash_before(stage.kind, stage.level)
            runner, args = _stage_call(mesh, plan, cfg, stage, specs, m,
                                       state, succ_d, rank_d,
                                       jnp.int32(seed))
            t0 = time.time()
            out = runner(*args)
            jax.block_until_ready(jax.tree.leaves(out))
            dt = time.time() - t0
            if stage.kind == "post":
                out_state, fatal_src = state, out[2]
            else:
                out_state, fatal_src = out, out["stats"]
            if injector is not None:
                cspec = injector.corrupt_after(stage.kind, stage.level)
                if cspec is not None:
                    injected_log.append(f"corrupt:{stage.label}")
                    tr.instant(f"corrupt:{stage.label}", cat="fault",
                               stage=stage.label, plane=cspec.plane)
                    if stage.kind != "post":
                        out_state = out = _apply_corruption(
                            out, cspec, mesh, plan, m)
                validate_state(out_state, n)
        except (faults_lib.InjectedFault, faults_lib.CorruptedState) as e:
            crashes += 1
            if isinstance(e, faults_lib.InjectedFault):
                injected_log.append(f"pe_loss:{stage.label}")
                tr.instant(f"pe_loss:{stage.label}", cat="fault",
                           stage=stage.label)
            stage_log.append(f"{stage.label}!{type(e).__name__}")
            tr.end(att, outcome=type(e).__name__)
            close_stage_span(outcome="crashed")
            budget_ok = (supervisor.should_retry() if supervisor is not None
                         else crashes <= max_retries)
            if not budget_ok:
                raise
            restored = try_restore()
            if restored is not None:
                state, idx, prev_fatal = restored
            else:
                state, idx = None, 0
                prev_fatal = {k: 0 for k in FATAL_KEYS}
            stage_span_idx = -1  # reopen a fresh stage span after rewind
            continue

        if tr.enabled:
            att.annotate(**stage_prediction(runner, args))
        fatal = _fatal_totals(fatal_src)
        delta = {k: fatal[k] - prev_fatal[k] for k in FATAL_KEYS}
        fam = (injector.overflow_after(stage.kind, stage.level)
               if injector is not None else None)
        if fam is not None:
            injected_log.append(f"overflow:{fam}:{stage.label}")
            tr.instant(f"overflow:{fam}:{stage.label}", cat="fault",
                       stage=stage.label, family=fam)
        if any(v > 0 for v in delta.values()) or fam is not None:
            # the failed attempt's output is discarded: the committed
            # boundary state (end of the previous stage) is the resume
            # point, with only the implicated families escalated at
            # levels >= the faulting level.
            esc_stats = ({k: v for k, v in delta.items() if v > 0}
                         if any(v > 0 for v in delta.values())
                         else {FAMILY_STAT[fam]: 1})
            stage_log.append(f"{stage.label}!overflow")
            tr.end(att, wall_s=dt, outcome="overflow",
                   fatal={k: int(v) for k, v in esc_stats.items()})
            attempts += 1
            if attempts > max_retries + 1:
                fail_stats = {k: int(v) for k, v in fatal.items()}
                close_stage_span(outcome="exhausted")
                raise SolveExhausted(attempts - 1, scales_log, esc_stats,
                                     fail_stats)
            lvl = max(stage.level, 0)
            level_scales = tuner.escalate_levels(level_scales, stage.level,
                                                 esc_stats)
            entry = tuner.format_scales(level_scales[lvl])
            scales_log.append(entry + (f"@L{lvl}" if lvl > 0 else ""))
            tr.instant(f"escalate:{stage.label}", cat="retry",
                       stage=stage.label, scales=entry, level=lvl)
            continue

        # commit the boundary
        if stage_counters:
            counts = introspect.collective_counts(runner, *args)
            stage_collectives.append((stage.label, tuple(sorted(
                counts.items()))))
        stage_log.append(stage.label)
        util = {}
        if plan.telemetry:
            # harvest the stage's per-PE telemetry record before the
            # state is committed/checkpointed (boundary_template does
            # not — and must not — carry it).
            tele_pe = (out[3] if stage.kind == "post"
                       else out_state.pop("_telemetry"))
            agg = tele_lib.aggregate(jax.device_get(tele_pe))
            util = tele_lib.utilization(agg)
            spec_u = _stage_specs(stage, specs)[0]
            tele_records.append(tele_lib.StageRecord(
                label=stage.label, kind=stage.kind, level=stage.level,
                caps={"chase": tuple(spec_u.mail_caps),
                      "sub": (spec_u.cap_sub,),
                      "gather": tuple(
                          max(a, b) for a, b in zip(
                              spec_u.gather_req_cap,
                              spec_u.gather_resp_cap))},
                queue_cap=spec_u.queue_cap, tele=agg))
            tr.counter("telemetry/util_max", util["util_max"])
            tr.counter("telemetry/util_mean", util["util_mean"])
            tr.counter("telemetry/queue_hwm",
                       float(agg.get("queue_hwm", 0)))
        tr.end(att, wall_s=dt, outcome="committed", **util)
        close_stage_span()
        if tr.enabled:
            tr.metrics.histogram(
                "obs/stage_wall_s",
                "device-sync-bounded wall seconds per committed stage"
                ).observe(dt)
            if plan.telemetry:
                tr.metrics.histogram(
                    "telemetry/stage_util_max",
                    tele_lib.TELEMETRY_HELP["util_max"]
                    ).observe(util["util_max"])
        if stage.kind == "post":
            succ_f, rank_f, dev_stats = out[0], out[1], out[2]
            break
        state = out_state
        prev_fatal = fatal
        idx += 1
        if supervisor is not None:
            supervisor.note_stage_time(dt)
            supervisor.boundary(idx, state, make_meta(idx))
        if injector is not None and injector.preempt_after(stage.kind,
                                                           stage.level):
            injected_log.append(f"preempt:{stage.label}")
            tr.instant(f"preempt:{stage.label}", cat="fault",
                       stage=stage.label)
            if supervisor is not None:
                supervisor.preempt()
            else:
                raise Preempted(
                    f"injected preemption after stage {stage.label}")
    else:  # pragma: no cover - schedule always ends with post
        raise AssertionError("schedule ended without a post stage")

    host_stats = {k: int(jax.device_get(v)) for k, v in dev_stats.items()}
    host_stats["attempts"] = attempts
    host_stats["scales_log"] = ";".join(scales_log)
    host_stats["stage_log"] = tuple(stage_log)
    rec = (dict(supervisor.stats) if supervisor is not None else
           {"restarts": crashes, "stragglers": 0, "checkpoints": 0,
            "preempted": 0, "resumed_from": -1})
    rec["injected"] = tuple(injected_log)
    host_stats["recovery"] = rec
    if stage_counters:
        host_stats["stage_collectives"] = tuple(stage_collectives)
    if plan.telemetry:
        host_stats["telemetry"] = {
            "stages": [r.to_json() for r in tele_records],
            "headroom": tele_lib.headroom_rows(tele_records,
                                               scales_log[-1]),
        }
    if supervisor is not None:
        supervisor.ckpt.wait()
    return succ_f, rank_f, host_stats


def _stage_call(mesh, plan, cfg, stage: Stage, specs, m: int, state,
                succ_d, rank_d, seed):
    """(jitted runner, its arguments) for one stage of the schedule."""
    runner = _jitted_stage(mesh, plan, cfg, stage,
                           _stage_specs(stage, specs), m)
    if stage.kind == "prep":
        return runner, (succ_d, rank_d)
    if stage.kind == "descend":
        return runner, (state, seed)
    if stage.kind == "post":
        return runner, (state, succ_d, rank_d)
    return runner, (state,)


def lower_staged(*, mesh, plan, cfg: ListRankConfig, m: int, n: int, specs,
                 weight_dtype):
    """``[(stage label, jax.stages.Lowered)]`` for every stage of the
    schedule, lowered from abstract, block-sharded arguments: the
    programs :func:`run_staged` runs for these ``specs``."""
    sched = schedule_for(cfg)
    sharded = NamedSharding(mesh, P(plan.pe_axes))

    def placed(like):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharded), like)

    succ = placed(jax.ShapeDtypeStruct((n,), jnp.int32))
    rank = placed(jax.ShapeDtypeStruct((n,), weight_dtype))
    seed = jax.ShapeDtypeStruct((), jnp.int32)
    out = []
    for idx, stage in enumerate(sched):
        state = (placed(boundary_template(sched, idx, cfg, specs, m, plan.p,
                                          weight_dtype)) if idx else None)
        runner, args = _stage_call(mesh, plan, cfg, stage, specs, m, state,
                                   succ, rank, seed)
        out.append((stage.label, runner.lower(*args)))
    return out
