"""Exchange-layer unit tests: packed wire format, sort-free compaction,
fused route_compact, dedup gather, and the one-collective-per-hop
guarantee (jaxpr inspection). Single-device (p=1 self-sends) — the
multi-PE device smoke runs in the consolidated subprocess driver
(tests/_subprocess_smoke.py, suite "exchange")."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.core.listrank import introspect
from repro.core.listrank.config import IndirectionSpec
from repro.core.listrank.exchange import (MeshPlan, WireFormat,
                                          compact_queue, remote_gather,
                                          route, route_compact,
                                          sort_and_group)
from repro.kernels.mailbox_pack import ops as mp_ops


def mesh1():
    return make_mesh((1,), ("pe",))


def plan1(packed=True, pallas=False):
    return MeshPlan.from_mesh(mesh1(), ("pe",), wire_packing=packed,
                              pallas_pack=pallas)


def _payload(q, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "ia": jnp.asarray(rng.integers(-5, 100, q), jnp.int32),
        "fb": jnp.asarray(rng.normal(size=q), jnp.float32),
        "bc": jnp.asarray(rng.integers(0, 2, q), bool),
    }


# ------------------------------------------------------------------ wire
def test_wire_roundtrip_exact():
    q = 64
    payload = _payload(q)
    payload["fb"] = payload["fb"].at[0].set(jnp.nan).at[1].set(-0.0)
    valid = jnp.asarray(np.random.default_rng(1).integers(0, 2, q), bool)
    wf = WireFormat.from_payload(payload)
    assert wf.width == 4  # 3 scalar leaves + valid word
    wire = wf.pack(payload, valid)
    assert wire.dtype == jnp.int32 and wire.shape == (q, 4)
    out, valid2 = wf.unpack(wire)
    np.testing.assert_array_equal(np.asarray(valid2), np.asarray(valid))
    for k in payload:
        np.testing.assert_array_equal(
            np.asarray(out[k]).view(np.int32).reshape(-1),
            np.asarray(payload[k]).view(np.int32).reshape(-1))


def test_wire_rejects_wide_dtypes():
    with pytest.raises(TypeError):
        WireFormat.from_payload(
            {"x": jnp.zeros(4, jnp.float16)}).pack(
                {"x": jnp.zeros(4, jnp.float16)}, jnp.ones(4, bool))


# ------------------------------------------------------- sort primitives
def test_sort_and_group():
    key = jnp.asarray([3, 1, 3, 7, 1, 1, 2], jnp.int32)
    valid = jnp.asarray([1, 1, 1, 0, 1, 1, 1], bool)
    order, skey, pos, newrun = sort_and_group(key, valid, 100)
    np.testing.assert_array_equal(np.asarray(skey), [1, 1, 1, 2, 3, 3, 100])
    np.testing.assert_array_equal(np.asarray(pos), [0, 1, 2, 0, 0, 1, 0])
    np.testing.assert_array_equal(np.asarray(newrun),
                                  [1, 0, 0, 1, 1, 0, 1])
    # stability: equal keys keep input order
    np.testing.assert_array_equal(np.asarray(order)[:3], [1, 4, 5])


def test_compact_queue_sort_free():
    rng = np.random.default_rng(2)
    frags = []
    for i in range(3):
        q = int(rng.integers(3, 12))
        pl = {"x": jnp.asarray(rng.integers(0, 50, q), jnp.int32)}
        d = jnp.asarray(rng.integers(0, 4, q), jnp.int32)
        v = jnp.asarray(rng.integers(0, 2, q), bool)
        frags.append((pl, d, v))
    cap = 16
    opl, od, ov, dropped = compact_queue(frags, cap)
    # reference: valid rows in concatenation order, front-packed
    ref_x = np.concatenate([np.asarray(pl["x"])[np.asarray(v)]
                            for pl, _, v in frags])
    ref_d = np.concatenate([np.asarray(d)[np.asarray(v)]
                            for _, d, v in frags])
    n = len(ref_x)
    assert int(dropped) == max(0, n - cap)
    take = min(n, cap)
    np.testing.assert_array_equal(np.asarray(opl["x"])[:take], ref_x[:take])
    np.testing.assert_array_equal(np.asarray(od)[:take], ref_d[:take])
    np.testing.assert_array_equal(np.asarray(ov),
                                  np.arange(cap) < take)


def test_compact_queue_overflow_drops_tail():
    q = 10
    pl = {"x": jnp.arange(q, dtype=jnp.int32)}
    frag = (pl, jnp.zeros(q, jnp.int32), jnp.ones(q, bool))
    opl, _, ov, dropped = compact_queue([frag], 4)
    assert int(dropped) == 6
    np.testing.assert_array_equal(np.asarray(opl["x"]), [0, 1, 2, 3])
    assert int(jnp.sum(ov)) == 4


# ---------------------------------------------------------------- route
def _run_route(plan, cap, payload, dest, valid, track_src=False):
    def fn(*leaves):
        pl = dict(zip(sorted(payload.keys()), leaves[:-2]))
        d, dv, lo, st = route(plan, [cap], pl, leaves[-2], leaves[-1],
                              track_src=track_src)
        left = sum(jnp.sum(lv).astype(jnp.int32) for _, _, lv in lo)
        return d, dv, left
    keys = sorted(payload.keys())
    args = [payload[k] for k in keys] + [dest, valid]
    m = jax.shard_map(fn, mesh=mesh1(),
                      in_specs=tuple(P("pe") for _ in args),
                      out_specs=(
                          {k: P("pe") for k in keys + (
                              ["src"] if track_src else [])},
                          P("pe"), P()), check_vma=False)
    return m(*args)


@pytest.mark.parametrize("packed", [True, False])
def test_route_p1_delivery_and_leftovers(packed):
    q, cap = 12, 5
    payload = _payload(q, seed=3)
    dest = jnp.zeros(q, jnp.int32)
    valid = jnp.asarray([1, 0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1], bool)
    d, dv, left = _run_route(plan1(packed), cap, payload, dest, valid)
    # p=1: the first `cap` valid messages are delivered in input order
    sel = np.flatnonzero(np.asarray(valid))[:cap]
    assert int(jnp.sum(dv)) == cap
    assert int(left) == int(np.sum(np.asarray(valid))) - cap
    for k in payload:
        np.testing.assert_array_equal(
            np.asarray(d[k])[np.asarray(dv)],
            np.asarray(payload[k])[sel])


def test_route_packed_unpacked_bit_identical():
    q, cap = 20, 32
    payload = _payload(q, seed=4)
    dest = jnp.zeros(q, jnp.int32)
    valid = jnp.asarray(np.random.default_rng(5).integers(0, 2, q), bool)
    outs = []
    for packed in (True, False):
        d, dv, _ = _run_route(plan1(packed), cap, payload, dest, valid,
                              track_src=True)
        outs.append((d, dv))
    (d1, v1), (d2, v2) = outs
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    for k in d1:
        np.testing.assert_array_equal(
            np.asarray(d1[k]).view(np.int32), np.asarray(d2[k]).view(np.int32))
    assert int(jnp.sum(jnp.where(v1, d1["src"], 0))) == 0  # p=1 => PE 0


# ------------------------------------------------- collective accounting
@pytest.mark.parametrize("packed,per_hop", [(True, 1), (False, 5)])
def test_route_collectives_per_hop(packed, per_hop):
    """Acceptance: packed route = exactly one all_to_all per hop. The
    unpacked path pays one per payload leaf (+dest +valid)."""
    q, cap = 8, 8
    payload = _payload(q)
    keys = sorted(payload.keys())

    for mesh, axes, ind, hops in [
            (mesh1(), ("pe",), None, 1),
            (make_mesh((1, 1), ("row", "col")), ("row", "col"),
             IndirectionSpec.grid(("row", "col")), 2)]:
        plan = MeshPlan.from_mesh(mesh, axes, ind, wire_packing=packed)

        def fn(*leaves):
            pl = dict(zip(keys, leaves[:-2]))
            d, dv, _, _ = route(plan, [cap] * hops, pl, leaves[-2],
                                leaves[-1])
            return d, dv

        args = [payload[k] for k in keys] + [
            jnp.zeros(q, jnp.int32), jnp.ones(q, bool)]
        m = jax.shard_map(fn, mesh=mesh,
                          in_specs=tuple(P(axes) for _ in args),
                          out_specs=({k: P(axes) for k in keys}, P(axes)),
                          check_vma=False)
        counts = introspect.collective_counts(m, *args)
        assert counts.get("all_to_all", 0) == per_hop * hops, counts


def test_route_compact_matches_route_plus_compact():
    """Fused compaction must agree with route + compact_queue on p=1
    (single bucket => bucket order is input order on both paths)."""
    q, cap, qc = 14, 4, 14
    payload = _payload(q, seed=6)
    dest = jnp.zeros(q, jnp.int32)
    valid = jnp.ones(q, bool)
    keys = sorted(payload.keys())
    plan = plan1(True)

    def fused(*leaves):
        pl = dict(zip(keys, leaves[:-2]))
        d, dv, (qpl, qd, qv), dropped, _ = route_compact(
            plan, [cap], [(pl, leaves[-2], leaves[-1])], qc)
        return d, dv, qpl, qd, qv, dropped

    def legacy(*leaves):
        pl = dict(zip(keys, leaves[:-2]))
        d, dv, lo, _ = route(plan, [cap], pl, leaves[-2], leaves[-1])
        qpl, qd, qv, dropped = compact_queue(lo, qc)
        return d, dv, qpl, qd, qv, dropped

    args = [payload[k] for k in keys] + [dest, valid]
    specs = tuple(P("pe") for _ in args)
    ospec = ({k: P("pe") for k in keys}, P("pe"),
             {k: P("pe") for k in keys}, P("pe"), P("pe"), P())
    a = jax.shard_map(fused, mesh=mesh1(), in_specs=specs, out_specs=ospec,
                      check_vma=False)(*args)
    b = jax.shard_map(legacy, mesh=mesh1(), in_specs=specs, out_specs=ospec,
                      check_vma=False)(*args)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -------------------------------------------------------- remote gather
@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("dedup", [True, False])
def test_remote_gather_p1(packed, dedup):
    q = 16
    rng = np.random.default_rng(7)
    targets = jnp.asarray(rng.integers(0, 8, q), jnp.int32)  # duplicates
    valid = jnp.asarray(rng.integers(0, 2, q), bool)
    plan = plan1(packed)

    def lookup_fn(g, v):
        return {"val": g * 2 + 1, "flag": v}

    def fn(t, v):
        out, answered, st = remote_gather(
            plan, t, v, lambda g: jnp.zeros_like(g), lookup_fn,
            req_cap=q, resp_cap=q, dedup=dedup)
        return out, answered

    m = jax.shard_map(fn, mesh=mesh1(), in_specs=(P("pe"), P("pe")),
                      out_specs=({"val": P("pe"), "flag": P("pe")},
                                 P("pe")), check_vma=False)
    out, answered = m(targets, valid)
    np.testing.assert_array_equal(np.asarray(answered), np.asarray(valid))
    got = np.asarray(out["val"])[np.asarray(valid)]
    want = np.asarray(targets)[np.asarray(valid)] * 2 + 1
    np.testing.assert_array_equal(got, want)


def test_route_rejects_reserved_payload_keys():
    plan = plan1(True)
    with pytest.raises(ValueError):
        route(plan, [4], {"_dest": jnp.zeros(4, jnp.int32)},
              jnp.zeros(4, jnp.int32), jnp.ones(4, bool))
    with pytest.raises(ValueError):
        route(plan, [4], {"src": jnp.zeros(4, jnp.int32)},
              jnp.zeros(4, jnp.int32), jnp.ones(4, bool), track_src=True)


# ----------------------------------------------------- request / reply
def test_request_reply_owner_computed_addressing():
    """request_reply: the owner regroups the delivered batch and
    addresses its own replies (the euler.py / graphalg round shape).
    p=1 self-sends make the data flow fully checkable."""
    from repro.core.listrank.exchange import request_reply
    plan = plan1()
    q = 16
    slot = jnp.arange(q, dtype=jnp.int32)
    val = jnp.asarray(np.random.default_rng(0).integers(0, 50, q),
                      jnp.int32)
    valid = slot % 3 != 0

    def reply_fn(dlv, dval):
        # owner doubles the value and addresses the requester's slot
        aux = jnp.sum(dval).astype(jnp.int32)
        return ({"slot": dlv["slot"], "twice": 2 * dlv["val"]},
                jnp.zeros_like(dlv["slot"]), dval, aux)

    def fn(slot, val, valid):
        rdel, rval, aux, st = request_reply(
            plan, 16, 16, {"slot": slot, "val": val},
            jnp.zeros(q, jnp.int32), valid, reply_fn)
        out = jnp.zeros(q, jnp.int32).at[
            jnp.where(rval, rdel["slot"], q)].set(rdel["twice"],
                                                  mode="drop")
        return out, aux, st["leftover"], st["sent"]

    m = jax.shard_map(fn, mesh=mesh1(),
                      in_specs=(P("pe"), P("pe"), P("pe")),
                      out_specs=(P("pe"), P(), P(), P()),
                      check_vma=False)
    out, aux, leftover, sent = m(slot, val, valid)
    np.testing.assert_array_equal(
        np.asarray(out), np.where(np.asarray(valid), 2 * np.asarray(val),
                                  0))
    assert int(aux) == int(np.sum(np.asarray(valid)))  # aux passthrough
    assert int(leftover) == 0
    assert int(sent) == 2 * int(np.sum(np.asarray(valid)))
    # the two legs cost exactly one packed collective each
    counts = introspect.collective_counts(m, slot, val, valid)
    assert counts.get("all_to_all", 0) == 2


# ------------------------------------------------- payload accounting
def test_route_collective_payload_bytes_exact():
    """The coalescing invariant, sharpened: the packed hop's single
    all_to_all must ship exactly width * hop_size * cap int32 words —
    per-collective payload bytes catch a hidden extra word-plane that
    the op count alone would miss."""
    q, cap = 8, 8
    payload = _payload(q)
    keys = sorted(payload.keys())
    plan = plan1()

    def fn(*leaves):
        pl = dict(zip(keys, leaves[:-2]))
        d, dv, _, _ = route(plan, [cap], pl, leaves[-2], leaves[-1])
        return d, dv

    args = [payload[k] for k in keys] + [
        jnp.zeros(q, jnp.int32), jnp.ones(q, bool)]
    m = jax.shard_map(fn, mesh=mesh1(),
                      in_specs=tuple(P("pe") for _ in args),
                      out_specs=({k: P("pe") for k in keys}, P("pe")),
                      check_vma=False)
    fp = introspect.collective_footprint(m, *args)
    width = WireFormat.for_leaves(
        {**{k: payload[k].dtype for k in keys}, "_dest": jnp.int32}).width
    assert fp["all_to_all"] == (1, width * 1 * cap * 4), fp


#: (all_to_all count, all_to_all payload bytes) of the fixed solve
#: config below — the committed coalescing baseline. The count is the
#: number of packed hops the traced program contains (while_loop bodies
#: count once); the bytes are their summed wire matrices. Both are
#: functions of our routing code and the host-derived capacities only,
#: so any change here is a real change to the wire protocol.
PINNED_SOLVE_FOOTPRINT = (9, 59200)


def solve_footprint(n, mesh, cfg):
    """Collective (count, bytes) footprint of the traced solver
    program for an n-element instance (test_treealg pins counts only;
    this adds the payload-volume dimension)."""
    import functools
    from repro.core.listrank import api as api_lib
    plan = MeshPlan.from_mesh(mesh, ("pe",), None,
                              wire_packing=cfg.wire_packing)
    specs = api_lib.build_specs(cfg, plan, n // plan.p, n, term_bound=8)
    fn = functools.partial(api_lib._solve_sharded, plan=plan, cfg=cfg,
                           specs=specs, m=n // plan.p)
    m = jax.shard_map(fn, mesh=mesh, in_specs=(P("pe"), P("pe"), P()),
                      out_specs=(P("pe"), P("pe"), P()),
                      check_vma=False)
    succ = jnp.arange(n, dtype=jnp.int32)
    rank = jnp.zeros(n, jnp.int32)
    return introspect.collective_footprint(m, succ, rank, jnp.int32(0))


def test_solver_collective_footprint_pinned():
    """Count AND bytes of one fixed solve config, pinned: the solver's
    mesh program must not grow a collective or a hidden word-plane
    without this test noticing — a sharper guard on the coalescing
    invariant than the op count alone."""
    from repro.core.listrank.config import ListRankConfig
    cfg = ListRankConfig(srs_rounds=1, local_contraction=False)
    fp = solve_footprint(256, mesh1(), cfg)
    assert fp["all_to_all"] == PINNED_SOLVE_FOOTPRINT, fp


# ------------------------------------------------------- mailbox kernel
def test_mailbox_pack_pallas_matches_ref():
    rng = np.random.default_rng(8)
    q, n_rows, w = 40, 24, 5
    cols = [jnp.asarray(rng.integers(-1000, 1000, q), jnp.int32)
            for _ in range(w)]
    # unique in-range slots plus some out-of-range (non-shipping rows)
    slots = rng.permutation(n_rows + 16)[:q].astype(np.int32)
    slots = jnp.asarray(slots)
    a = mp_ops.mailbox_pack(cols, slots, n_rows, use_pallas=True)
    b = mp_ops.mailbox_pack(cols, slots, n_rows, use_pallas=False)
    assert a.shape == (w, n_rows)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # oracle
    want = np.zeros((w, n_rows), np.int32)
    for i, s in enumerate(np.asarray(slots)):
        if s < n_rows:
            for j in range(w):
                want[j, s] = int(cols[j][i])
    np.testing.assert_array_equal(np.asarray(b), want)


# The multi-PE exchange smoke moved to the consolidated subprocess
# driver: tests/test_listrank_multi.py::test_subprocess_smoke[exchange].
