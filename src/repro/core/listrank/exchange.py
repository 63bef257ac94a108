"""Bucketed message routing over a JAX device mesh (paper §2.4).

The paper sends sparse point-to-point messages over MPI; on a TPU mesh
we realize each communication round as one (or ``d``, with indirection)
dense, fixed-capacity ``all_to_all`` per hop. A *hop* fixes the
destination coordinate along one mesh-axis group. Direct delivery is a
single hop over all PE axes; grid indirection is one hop per axis
(minor axis first — the paper's column-then-row routing); topology-aware
indirection hops over the intra-node axis first.

Static shapes force a per-peer mailbox capacity. Messages that do not
fit are *leftovers*: they stay on the holding PE and re-enter routing in
the caller's next round (re-routing from an intermediate PE is correct
because every hop fixes its own coordinate, so partially-routed messages
simply self-send on already-fixed hops). Capacity overflow therefore
costs rounds, never correctness; the amount is tracked in ``stats``.

Packed wire format (see DESIGN.md): with ``MeshPlan.wire_packing`` all
payload leaves of a message batch are bit-packed into a single
``(Q, W)`` int32 matrix — the layout is a static :class:`WireFormat`
derived from the payload pytree at trace time — so each hop costs
exactly **one** ``all_to_all`` regardless of leaf count. The unpacked
path (one collective per leaf plus one for validity) is kept behind the
same API for A/B testing; both paths share every index computation, so
they are bit-identical.

Sorting discipline: the only O(Q log Q) sort in the routing hot path is
the per-hop bucket sort (:func:`sort_and_group`, shared with request
deduplication in :func:`remote_gather`). Queue compaction is sort-free
(stream compaction by prefix sum), and :func:`route_compact` fuses it
into the bucket sort — leftovers come out compacted for free.

All functions here run *inside* ``shard_map`` — per-PE arrays,
collectives by axis name.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.listrank.config import IndirectionSpec
from repro.core.listrank import transport as transport_lib
from repro.obs import telemetry as tele_lib

Pytree = Any

INT_MAX = jnp.iinfo(jnp.int32).max

#: payload keys reserved for the router itself.
RESERVED_KEYS = ("_dest", "_src")


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Static routing metadata for a PE grid embedded in a mesh.

    PE ids are flattened row-major over ``pe_axes`` (matching
    ``axis_index`` over the full axis tuple).

    ``wire_packing`` selects the packed wire format (one collective per
    hop); ``pallas_pack`` additionally routes the pack+bucket-scatter
    through the ``repro.kernels.mailbox_pack`` Pallas kernel.

    ``transport`` is how the program reaches the interconnect: raw mesh
    collectives under ``shard_map``, or the simshard virtual-PE
    emulation under ``vmap`` (see :mod:`repro.core.listrank.transport`).
    Every collective in this package goes through the :meth:`my_id` /
    :meth:`all_to_all` / :meth:`psum` / :meth:`all_gather` delegates —
    nothing may call ``lax`` collectives directly (enforced by
    ``tests/test_transport_audit.py``).
    """

    pe_axes: tuple[str, ...]
    axis_sizes: tuple[int, ...]
    indirection: IndirectionSpec
    wire_packing: bool = True
    pallas_pack: bool = False
    transport: transport_lib.Transport = transport_lib.MeshTransport()
    #: mirror of ``ListRankConfig.telemetry`` (static; part of every
    #: jitted-program key through the plan). When set, routing emits a
    #: per-PE ``repro.obs.telemetry`` record in ``stats["telemetry"]``
    #: — pure local arithmetic, zero added collectives.
    telemetry: bool = False

    @property
    def p(self) -> int:
        out = 1
        for s in self.axis_sizes:
            out *= s
        return out

    def axis_size(self, name: str) -> int:
        return self.axis_sizes[self.pe_axes.index(name)]

    def hop_size(self, hop: tuple[str, ...]) -> int:
        out = 1
        for a in hop:
            out *= self.axis_size(a)
        return out

    def my_id(self) -> jax.Array:
        return self.transport.axis_index(self.pe_axes)

    def all_to_all(self, x: jax.Array, hop: tuple[str, ...],
                   split_axis: int, concat_axis: int) -> jax.Array:
        """One routing collective over the axis group ``hop``."""
        return self.transport.all_to_all(x, hop, split_axis, concat_axis,
                                         tiled=True)

    def psum(self, x):
        """Sum-reduce over every PE axis (stats and convergence tests)."""
        return self.transport.psum(x, self.pe_axes)

    def all_gather(self, x: jax.Array) -> jax.Array:
        """Tiled gather over every PE axis (allgather base case)."""
        return self.transport.all_gather(x, self.pe_axes, tiled=True)

    def hop_coord(self, pe_id: jax.Array, hop: tuple[str, ...]) -> jax.Array:
        """Coordinate of ``pe_id`` along the (possibly non-contiguous)
        axis group ``hop``, flattened row-major within the group."""
        coord = jnp.zeros_like(pe_id)
        for a in hop:
            i = self.pe_axes.index(a)
            stride = 1
            for s in self.axis_sizes[i + 1:]:
                stride *= s
            c = (pe_id // stride) % self.axis_sizes[i]
            coord = coord * self.axis_sizes[i] + c
        return coord

    def hop_coord_to_pe(self, hop: tuple[str, ...]) -> np.ndarray:
        """Inverse of :meth:`hop_coord` restricted to the group: the
        contribution of group coordinate ``b`` to the flat PE id (the
        remaining axes contribute the *receiver's own* coordinates).
        Static (numpy) — used to rebuild sender ids from receive-buffer
        row indices."""
        s = self.hop_size(hop)
        b = np.arange(s, dtype=np.int32)
        rem, acc = b, np.zeros(s, np.int32)
        for a in reversed(hop):
            i = self.pe_axes.index(a)
            stride = 1
            for sz in self.axis_sizes[i + 1:]:
                stride *= sz
            c = rem % self.axis_sizes[i]
            rem = rem // self.axis_sizes[i]
            acc = acc + c.astype(np.int32) * stride
        return acc

    @staticmethod
    def from_mesh(mesh, pe_axes: Sequence[str],
                  indirection: IndirectionSpec | None = None,
                  wire_packing: bool = True,
                  pallas_pack: bool = False,
                  transport: transport_lib.Transport | None = None,
                  telemetry: bool = False,
                  ) -> "MeshPlan":
        """Plan for a real mesh OR a :class:`transport.SimMesh` — the
        transport defaults to whichever backend the mesh object implies."""
        pe_axes = tuple(pe_axes)
        sizes = tuple(mesh.shape[a] for a in pe_axes)
        if indirection is None:
            indirection = IndirectionSpec.direct(pe_axes)
        for hop in indirection.hops:
            for a in hop:
                if a not in pe_axes:
                    raise ValueError(f"hop axis {a} not in pe_axes {pe_axes}")
        if transport is None:
            transport = (transport_lib.SimShardTransport()
                         if transport_lib.is_sim(mesh)
                         else transport_lib.MeshTransport())
        return MeshPlan(pe_axes=pe_axes, axis_sizes=sizes,
                        indirection=indirection, wire_packing=wire_packing,
                        pallas_pack=pallas_pack, transport=transport,
                        telemetry=telemetry)


# --------------------------------------------------------------------------
# wire format
# --------------------------------------------------------------------------

def to_wire_word(x: jax.Array) -> jax.Array:
    """Reinterpret a 32-bit-or-narrower leaf as int32 words, exactly."""
    dt = x.dtype
    if dt == jnp.int32:
        return x
    if dt in (jnp.float32, jnp.uint32):
        return lax.bitcast_convert_type(x, jnp.int32)
    if dt == jnp.bool_:
        return x.astype(jnp.int32)
    if jnp.issubdtype(dt, jnp.integer) and jnp.dtype(dt).itemsize < 4:
        return x.astype(jnp.int32)
    raise TypeError(f"wire format does not support dtype {dt}")


def from_wire_word(w: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`to_wire_word`."""
    dt = jnp.dtype(dtype)
    if dt == jnp.int32:
        return w
    if dt in (jnp.float32, jnp.uint32):
        return lax.bitcast_convert_type(w, dt)
    if dt == jnp.bool_:
        return w != 0
    return w.astype(dt)


@dataclasses.dataclass(frozen=True)
class WireFormat:
    """Static descriptor of the packed on-wire layout of a message batch.

    Each payload leaf of shape ``(Q, *trail)`` occupies
    ``prod(trail)`` int32 words per message; the final word is the
    validity flag. Leaves are laid out in sorted-key order so the format
    depends only on the payload *structure* — it is derived host-side
    (at trace time, and for capacity budgeting in ``build_specs``).
    """

    keys: tuple[str, ...]
    dtypes: tuple[str, ...]
    trails: tuple[tuple[int, ...], ...]

    @classmethod
    def from_payload(cls, payload: dict[str, jax.Array]) -> "WireFormat":
        keys = tuple(sorted(payload.keys()))
        dtypes, trails = [], []
        for k in keys:
            v = payload[k]
            dtypes.append(jnp.dtype(v.dtype).name)
            trails.append(tuple(int(d) for d in v.shape[1:]))
        return cls(keys=keys, dtypes=tuple(dtypes), trails=tuple(trails))

    @classmethod
    def for_leaves(cls, leaves: dict[str, Any]) -> "WireFormat":
        """Host-side construction from {name: dtype} scalar leaves."""
        keys = tuple(sorted(leaves.keys()))
        return cls(keys=keys,
                   dtypes=tuple(jnp.dtype(leaves[k]).name for k in keys),
                   trails=((),) * len(keys))

    def leaf_words(self, i: int) -> int:
        out = 1
        for d in self.trails[i]:
            out *= d
        return out

    @property
    def width(self) -> int:
        """Total int32 words per message, incl. the validity word."""
        return sum(self.leaf_words(i) for i in range(len(self.keys))) + 1

    def columns(self, payload: dict[str, jax.Array],
                valid: jax.Array) -> list[jax.Array]:
        """The ``width`` int32 columns of the wire matrix, unstacked."""
        q = valid.shape[0]
        cols: list[jax.Array] = []
        for i, k in enumerate(self.keys):
            w = to_wire_word(payload[k]).reshape(q, -1)
            cols.extend(w[:, j] for j in range(w.shape[1]))
        cols.append(valid.astype(jnp.int32))
        return cols

    def pack(self, payload: dict[str, jax.Array],
             valid: jax.Array) -> jax.Array:
        """Bit-pack a message batch into a ``(Q, width)`` int32 matrix."""
        return jnp.stack(self.columns(payload, valid), axis=1)

    def unpack(self, wire: jax.Array) -> tuple[dict[str, jax.Array], jax.Array]:
        """Inverse of :meth:`pack` (exact, incl. float bit patterns)."""
        return self.unpack_cols(wire.T)

    def unpack_cols(self, cols: jax.Array) -> tuple[dict[str, jax.Array],
                                                    jax.Array]:
        """Unpack from column-major wire words: ``cols`` is (width, R).

        This is the on-wire layout of the packed exchange — word-planes
        are contiguous, so packing/unpacking is plane-wise data movement
        with no transposes.
        """
        r = cols.shape[1]
        payload = {}
        off = 0
        for i, k in enumerate(self.keys):
            w = self.leaf_words(i)
            leaf = jnp.moveaxis(cols[off:off + w], 0, -1).reshape(
                (r,) + self.trails[i])
            payload[k] = from_wire_word(leaf, self.dtypes[i])
            off += w
        valid = cols[off] != 0
        return payload, valid


# --------------------------------------------------------------------------
# shared sort/scatter primitives
# --------------------------------------------------------------------------

#: lane width of the blocked scans.
_LANES = 128


def _blocked_scan(op, x: jax.Array, fill) -> jax.Array:
    """Inclusive scan of a 1-D array under the associative ``op``, with
    ``fill`` its identity, computed on ``(rows, 128)`` blocks: a
    log-step shifted scan along each row, then the row totals scanned
    the same way and carried in.

    ``jnp.cumsum`` and ``lax.associative_scan`` give the same values,
    but the TPU compiler's time for them grows with the length: on a
    described v5e a 2^18-element ``jnp.cumsum`` compiles in 5 s and a
    2^20-element ``associative_scan`` in 105 s, while this form compiles
    in about a second up to 2^26 elements.
    """
    n = x.shape[0]
    rows = -(-n // _LANES)
    xb = jnp.pad(x, (0, rows * _LANES - n),
                 constant_values=fill).reshape(rows, _LANES)
    shift = 1
    while shift < _LANES:
        xb = op(xb, jnp.pad(xb[:, :-shift], ((0, 0), (shift, 0)),
                            constant_values=fill))
        shift *= 2
    if rows > 1:
        carry = _blocked_scan(op, xb[:, -1], fill)
        xb = op(xb, jnp.concatenate(
            [jnp.full((1,), fill, x.dtype), carry[:-1]])[:, None])
    return xb.reshape(-1)[:n]


def prefix_sum(x: jax.Array) -> jax.Array:
    """``jnp.cumsum`` of a 1-D integer array (see :func:`_blocked_scan`)."""
    return _blocked_scan(jnp.add, x, 0)


def sort_and_group(key: jax.Array, valid: jax.Array, sentinel):
    """One stable sort, shared by bucketing and request dedup.

    Invalid rows sort to the back (keyed ``sentinel``, which must
    compare greater than every valid key). Returns

      order:  (Q,) the sort permutation,
      skey:   (Q,) keys in sorted order,
      pos:    (Q,) rank of each sorted row within its run of equal keys,
      newrun: (Q,) True at the first row of each run.
    """
    q = key.shape[0]
    k = jnp.where(valid, key, sentinel)
    order = jnp.argsort(k, stable=True)
    skey = k[order]
    i = jnp.arange(q, dtype=jnp.int32)
    newrun = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), skey[1:] != skey[:-1]])
    run_start = _blocked_scan(jnp.maximum, jnp.where(newrun, i, 0), 0)
    return order, skey, i - run_start, newrun


def _bucket_indices(coord: jax.Array, valid: jax.Array, n_buckets: int,
                    cap: int):
    """Mailbox scatter coordinates for one hop.

    Returns (order, row, col, fits, leftover_sorted, pos); ``row``/
    ``col`` address the ``(n_buckets, cap)`` mailbox grid in *sorted*
    order with out-of-range sentinels for rows that don't ship this hop.
    ``leftover_sorted`` marks valid messages beyond bucket capacity;
    ``pos`` is the within-bucket rank (telemetry reads bucket demand
    from it).
    """
    order, skey, pos, _ = sort_and_group(coord, valid, n_buckets)
    infit = skey < n_buckets
    fits = infit & (pos < cap)
    row = jnp.where(fits, skey, n_buckets).astype(jnp.int32)
    col = jnp.where(fits, pos, cap).astype(jnp.int32)
    return order, row, col, fits, infit & ~fits, pos


def _scatter_leaf(leaf_sorted: jax.Array, flat: jax.Array, n_rows: int):
    """Scatter sorted rows to flat mailbox slots (OOB slots dropped)."""
    buf = jnp.zeros((n_rows,) + leaf_sorted.shape[1:], leaf_sorted.dtype)
    return buf.at[flat].set(leaf_sorted, mode="drop")


# --------------------------------------------------------------------------
# routing
# --------------------------------------------------------------------------

def _check_payload(payload: dict[str, jax.Array], track_src: bool):
    for k in RESERVED_KEYS:
        if k in payload:
            raise ValueError(f"payload key {k!r} is reserved")
    if track_src and "src" in payload:
        raise ValueError("track_src=True would overwrite payload key 'src'")


def _route_impl(plan: MeshPlan, caps: Sequence[int],
                payload: dict[str, jax.Array], dest: jax.Array,
                valid: jax.Array, track_src: bool, queue_cap: int | None):
    """Shared body of :func:`route` and :func:`route_compact`.

    With ``queue_cap`` set, per-hop leftovers are compacted into a
    single queue *by the bucket sort itself* (prefix-sum slots over the
    sorted order — no extra sort); otherwise they are returned as the
    legacy per-hop fragment list.
    """
    hops = plan.indirection.hops
    assert len(caps) == len(hops)
    _check_payload(payload, track_src)
    user_keys = tuple(payload.keys())

    cur = dict(payload)
    cur["_dest"] = dest.astype(jnp.int32)
    cur_valid = valid
    src_acc = None
    leftovers = []
    if queue_cap is not None:
        lq = {k: jnp.zeros((queue_cap,) + v.shape[1:], v.dtype)
              for k, v in payload.items()}
        lq_dest = jnp.zeros(queue_cap, jnp.int32)
        nleft = jnp.int32(0)
    stats = {"sent": [], "leftover": jnp.int32(0)}
    tele_hops, tele_hist = [], None

    for h, (hop, cap) in enumerate(zip(hops, caps)):
        s = plan.hop_size(hop)
        q = cur_valid.shape[0]
        coord = plan.hop_coord(cur["_dest"], hop)
        order, row, col, fits, leftover_sorted, pos = _bucket_indices(
            coord, cur_valid, s, cap)
        flat = row * cap + col  # ≥ s*cap for non-shipping rows
        if plan.telemetry:
            # per-PE occupancy/skew sample of this hop: pure local
            # arithmetic on indices already computed — no collectives.
            infit = fits | leftover_sorted
            tele_hops.append({
                "demand_max": jnp.max(
                    jnp.where(infit, pos + 1, 0)).astype(jnp.int32),
                "delivered": jnp.sum(fits).astype(jnp.int32),
                "total": jnp.sum(infit).astype(jnp.int32),
                "cap": cap, "s": s,
            })
            if h == 0:
                bins = jnp.where(cur_valid,
                                 (coord * tele_lib.HIST_BINS) // max(s, 1),
                                 tele_lib.HIST_BINS)
                tele_hist = jnp.zeros(tele_lib.HIST_BINS, jnp.int32
                                      ).at[bins].add(1, mode="drop")
        # input-aligned mailbox slot: message i ships to slot io_flat[i]
        # (out of range => stays). One index scatter replaces a sorted
        # gather per payload leaf below.
        io_flat = jnp.full(q, s * cap + cap, jnp.int32).at[order].set(flat)

        nl = jnp.sum(leftover_sorted).astype(jnp.int32)
        if queue_cap is None:
            left_mask = jnp.zeros(q, jnp.bool_).at[order].set(leftover_sorted)
            leftovers.append(({k: cur[k] for k in user_keys},
                              cur["_dest"], cur_valid & left_mask))
        else:
            lpos = nleft + prefix_sum(leftover_sorted.astype(jnp.int32)) - 1
            lslot = jnp.where(leftover_sorted, lpos, queue_cap)
            io_lslot = jnp.full(q, queue_cap, jnp.int32).at[order].set(lslot)
            for k in lq:
                lq[k] = lq[k].at[io_lslot].set(cur[k], mode="drop")
            lq_dest = lq_dest.at[io_lslot].set(cur["_dest"], mode="drop")
            nleft = nleft + nl
        stats["sent"].append(jnp.sum(fits))
        stats["leftover"] = stats["leftover"] + nl

        # exchange: mailbox row b goes to the peer with coordinate b
        # along `hop`. The packed buffer is column-major (word-planes
        # first) so pack/unpack stay plane-contiguous; the collective
        # splits/concats the mailbox-row axis.
        if plan.wire_packing:
            wf = WireFormat.from_payload(cur)
            buf = _pack_scatter(plan, wf, cur, cur_valid, io_flat, s, cap)
            recv = plan.all_to_all(buf, hop, 1, 1)  # 1 collective
            cur, cur_valid = wf.unpack_cols(recv.reshape(wf.width, s * cap))
        else:
            recv = {}
            for k, v in cur.items():
                b = _scatter_leaf(v, io_flat, s * cap
                                  ).reshape((s, cap) + v.shape[1:])
                recv[k] = plan.all_to_all(b, hop, 0, 0)
            bval = _scatter_leaf(cur_valid, io_flat, s * cap).reshape(s, cap)
            rval = plan.all_to_all(bval, hop, 0, 0)
            cur = {k: v.reshape((s * cap,) + v.shape[2:])
                   for k, v in recv.items()}
            cur_valid = rval.reshape(s * cap)

        if track_src:
            # Sender reconstruction from the receive-buffer row index:
            # mailbox row b was filled by the peer whose coordinate
            # along `hop` is b (remaining axes match the receiver's
            # own), so accumulating the per-hop contributions over all
            # hops yields the full origin PE id — no 'src' leaf ever
            # leaves the origin. Valid only for messages that traverse
            # every hop in this call (leftovers are *not* re-routable
            # with track_src; remote_gather re-requests from origin).
            contrib = jnp.asarray(
                np.repeat(plan.hop_coord_to_pe(hop), cap), jnp.int32)
            prev = cur.pop("_src", None)
            src_acc = contrib if prev is None else prev + contrib
            if h < len(hops) - 1:
                cur["_src"] = src_acc

    if plan.telemetry:
        stats["telemetry"] = tele_lib.route_wave(tele_hops, tele_hist)
    delivered = {k: cur[k] for k in user_keys}
    if track_src:
        delivered["src"] = src_acc
    if queue_cap is not None:
        qv = jnp.arange(queue_cap, dtype=jnp.int32) < jnp.minimum(
            nleft, queue_cap)
        dropped = jnp.maximum(nleft - queue_cap, 0)
        return delivered, cur_valid, (lq, lq_dest, qv, dropped), stats
    return delivered, cur_valid, leftovers, stats


def _pack_scatter(plan: MeshPlan, wf: WireFormat, payload, valid,
                  io_flat, n_buckets: int, cap: int) -> jax.Array:
    """Pack + bucket-scatter into the (W, n_buckets, cap) send buffer."""
    from repro.kernels.mailbox_pack import ops as mp_ops
    cols = wf.columns(payload, valid)
    buf = mp_ops.mailbox_pack(cols, io_flat, n_buckets * cap,
                              use_pallas=plan.pallas_pack)
    return buf.reshape(wf.width, n_buckets, cap)


def route(plan: MeshPlan, caps: Sequence[int], payload: dict[str, jax.Array],
          dest: jax.Array, valid: jax.Array, track_src: bool = False):
    """Route messages to their destination PE through the plan's hops.

    Args:
      caps: per-peer mailbox capacity per hop (len == #hops).
      payload: dict of (Q, ...) arrays.
      dest: (Q,) destination PE ids (flattened over pe_axes).
      valid: (Q,) mask.
      track_src: reconstruct each message's origin PE from receive-
        buffer row indices (see :func:`_route_impl`); the result is
        returned as ``delivered["src"]`` without shipping a source leaf.

    Returns:
      delivered: dict of (R, ...) arrays (R = hop_size[-1] * caps[-1]),
      delivered_valid: (R,),
      leftovers: list of (payload_dict, dest, valid) per hop — messages
        stuck on this PE awaiting the next round,
      stats: dict with per-hop sent counts and total leftover count.
    """
    return _route_impl(plan, caps, payload, dest, valid, track_src,
                       queue_cap=None)


def route_compact(plan: MeshPlan, caps: Sequence[int],
                  frags: Sequence[tuple[dict[str, jax.Array], jax.Array,
                                        jax.Array]],
                  queue_cap: int):
    """Route concatenated fragments; leftovers come back as one compact
    queue. The first-hop bucket sort *is* the queue compaction — a chase
    round costs a single stable sort per hop, with no separate
    ``compact_queue`` pass.

    Returns (delivered, delivered_valid, (queue_payload, queue_dest,
    queue_valid), dropped, stats).
    """
    payload, dest, valid = _concat_frags(frags)
    delivered, dval, (qpl, qd, qv, dropped), stats = _route_impl(
        plan, caps, payload, dest, valid, track_src=False,
        queue_cap=queue_cap)
    return delivered, dval, (qpl, qd, qv), dropped, stats


def _concat_frags(entries):
    keys = tuple(entries[0][0].keys())
    for pl, _, _ in entries:
        if tuple(pl.keys()) != keys and set(pl.keys()) != set(keys):
            raise ValueError("fragments must share payload keys")
    payload = {k: jnp.concatenate([pl[k] for pl, _, _ in entries], axis=0)
               for k in keys}
    dest = jnp.concatenate([d for _, d, _ in entries], axis=0)
    valid = jnp.concatenate([v for _, _, v in entries], axis=0)
    return payload, dest, valid


def compact_queue(entries: Sequence[tuple[dict[str, jax.Array], jax.Array,
                                          jax.Array]],
                  cap: int):
    """Merge (payload, dest, valid) fragments into one queue of size cap.

    Valid entries are packed to the front *in order* by a prefix-sum
    scatter — O(Q), no sort. Returns (payload, dest, valid,
    dropped_count) — dropped_count > 0 means ``cap`` was too small and
    the run must be retried with larger capacities.
    """
    cat_payload, cat_dest, cat_valid = _concat_frags(entries)
    pos = prefix_sum(cat_valid.astype(jnp.int32)) - 1
    slot = jnp.where(cat_valid, pos, cap)  # cap => dropped by mode="drop"
    out_payload = {
        k: jnp.zeros((cap,) + v.shape[1:], v.dtype).at[slot].set(
            v, mode="drop")
        for k, v in cat_payload.items()}
    out_dest = jnp.zeros(cap, cat_dest.dtype).at[slot].set(
        cat_dest, mode="drop")
    n_valid = jnp.sum(cat_valid).astype(jnp.int32)
    out_valid = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(n_valid, cap)
    dropped = jnp.maximum(n_valid - cap, 0)
    return out_payload, out_dest, out_valid, dropped


# --------------------------------------------------------------------------
# request/response gather
# --------------------------------------------------------------------------

def request_reply(plan: MeshPlan, req_caps, resp_caps,
                  payload: dict[str, jax.Array], dest: jax.Array,
                  valid: jax.Array, reply_fn):
    """Two-leg owner-computes exchange (request round + reply round).

    Route ``payload`` to ``dest``; on the receiving PE, ``reply_fn``
    turns the delivered batch into a reply batch with its *own*
    addressing; route those replies and return them. This is the shared
    shape of ``treealg.euler``'s report/reply tour construction and
    ``graphalg``'s adjacency-linking round — unlike
    :func:`remote_gather` (keyed fetch by target id, origin
    reconstructed from receive rows), the owner computes both the reply
    content and the reply destinations, typically after regrouping the
    requests with :func:`sort_and_group`.

    Args:
      req_caps / resp_caps: per-hop mailbox capacities for the two legs
        (int => replicated over hops).
      reply_fn: (delivered_payload, delivered_valid) ->
        (reply_payload, reply_dest, reply_valid[, aux]); ``aux`` is any
        pytree of side outputs the owner derives while grouping (e.g.
        per-local-node marks) and is returned through untouched.

    Returns:
      (reply_delivered, reply_valid, aux, stats) with ``aux`` None when
      ``reply_fn`` returns a 3-tuple and ``stats = {"sent", "leftover"}``
      summed over both legs (a nonzero leftover means a capacity
      overflow somewhere; the caller must retry with larger caps).
    """
    def as_caps(c):
        return list(c) if isinstance(c, (tuple, list)) \
            else [c] * plan.indirection.depth

    delivered, dval, _, st1 = route(plan, as_caps(req_caps), payload, dest,
                                    valid)
    out = reply_fn(delivered, dval)
    rpl, rdest, rvalid = out[:3]
    aux = out[3] if len(out) > 3 else None
    rdel, rval, _, st2 = route(plan, as_caps(resp_caps), rpl,
                               rdest.astype(jnp.int32), rvalid)
    stats = {"sent": sum(st1["sent"] + st2["sent"]).astype(jnp.int32),
             "leftover": st1["leftover"] + st2["leftover"]}
    if plan.telemetry:
        stats["telemetry"] = tele_lib.merge(st1["telemetry"],
                                            st2["telemetry"])
    return rdel, rval, aux, stats


def remote_gather(plan: MeshPlan, targets: jax.Array, valid: jax.Array,
                  owner_of: Callable[[jax.Array], jax.Array],
                  lookup_fn: Callable[[jax.Array, jax.Array], dict[str, jax.Array]],
                  req_cap, resp_cap, dedup: bool = True):
    """Fetch per-element data about remote ``targets`` (request/response).

    The paper's ruler-propagation and §2.5 postprocessing both reduce to
    this primitive; ``dedup=True`` implements the paper's per-PE request
    aggregation (identical targets are asked once, then fanned back out).
    Requests carry no source-PE leaf: the responder rebuilds the origin
    from receive-buffer row indices (``route(track_src=True)``).

    Args:
      targets: (Q,) global element ids to query.
      valid: (Q,) mask.
      owner_of: global id -> owning PE id.
      lookup_fn: (ids (R,), valid (R,)) -> dict of (R, ...) response
        leaves, evaluated on the owning PE.
      req_cap/resp_cap: per-peer mailbox capacity for the two legs.

    Returns:
      values: dict of (Q, ...) arrays aligned with ``targets``,
      answered: (Q,) mask of queries answered (False => capacity
        overflow somewhere; caller must retry with larger caps),
      stats: message-count stats.
    """
    q = targets.shape[0]
    if dedup:
        order, skey, _, newrun = sort_and_group(targets, valid, INT_MAX)
        is_uniq = newrun & (skey != INT_MAX)
        group = prefix_sum(is_uniq.astype(jnp.int32)) - 1
        uniq_slot = jnp.where(is_uniq, group, q)
        req_targets = jnp.zeros(q, targets.dtype).at[uniq_slot].set(
            skey, mode="drop")
        n_uniq = jnp.sum(is_uniq).astype(jnp.int32)
        req_valid = jnp.arange(q, dtype=jnp.int32) < n_uniq
        # original slot i -> unique slot group[rank of i in sort]
        inv = jnp.zeros(q, jnp.int32).at[order].set(group)
    else:
        req_targets, req_valid = targets, valid
        inv = jnp.arange(q, dtype=jnp.int32)

    payload = {
        "target": req_targets,
        "slot": jnp.arange(q, dtype=jnp.int32),
    }
    dest = owner_of(req_targets).astype(jnp.int32)
    caps_req = list(req_cap) if isinstance(req_cap, (tuple, list)) \
        else [req_cap] * plan.indirection.depth
    delivered, dval, leftovers, st_req = route(plan, caps_req, payload, dest,
                                               req_valid, track_src=True)
    req_left = sum(jnp.sum(lv).astype(jnp.int32) for _, _, lv in leftovers)

    # answer on the owner
    values = lookup_fn(delivered["target"], dval)
    resp_payload = dict(values)
    resp_payload["slot"] = delivered["slot"]
    resp_dest = delivered["src"]
    caps_resp = list(resp_cap) if isinstance(resp_cap, (tuple, list)) \
        else [resp_cap] * plan.indirection.depth
    rdel, rval, rleft, st_resp = route(plan, caps_resp, resp_payload,
                                       resp_dest, dval)
    resp_left = sum(jnp.sum(lv).astype(jnp.int32) for _, _, lv in rleft)

    # scatter responses into the unique-request table
    slot = jnp.where(rval, rdel["slot"], q).astype(jnp.int32)
    uniq_values = {}
    uniq_answered = jnp.zeros(q + 1, jnp.bool_).at[slot].set(
        rval, mode="drop")[:q]
    for k in values:
        leaf = rdel[k]
        buf = jnp.zeros((q + 1,) + leaf.shape[1:], leaf.dtype
                        ).at[slot].set(leaf, mode="drop")
        uniq_values[k] = buf[:q]
    out = {k: v[inv] for k, v in uniq_values.items()}
    answered = uniq_answered[inv] & valid
    stats = {
        "req_sent": sum(st_req["sent"]),
        "resp_sent": sum(st_resp["sent"]),
        "undelivered": req_left + resp_left,
    }
    if plan.telemetry:
        stats["telemetry"] = tele_lib.merge(st_req["telemetry"],
                                            st_resp["telemetry"])
    return out, answered, stats
