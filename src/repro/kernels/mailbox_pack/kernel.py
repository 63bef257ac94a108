"""Pallas TPU kernel: fused wire-pack + mailbox bucket-scatter.

The packed exchange path (see ``repro.core.listrank.exchange``) needs,
per hop, the column-major ``(W, n_buckets*cap)`` int32 send buffer

    out[w, slots[i]] = cols[w][i]   for every shipping message i

where ``cols`` are the W bit-cast wire word-planes of the payload and
``slots`` the input-aligned mailbox slot (out-of-range => the message
does not ship this hop). XLA runs one scatter per word-plane, touching
the slot indices W times; this kernel walks the messages once and
writes each message's W words together.

Layout: the messages stream through SMEM in blocks of ``BLOCK`` (slot
indices and word-planes are scalars there, so any slot is one load).
The send buffer stays resident in VMEM for the whole grid as
``(W, rows, 128)``: a message's word goes to row ``slot // 128``, a
dynamic sublane index Mosaic accepts, and lane ``slot % 128`` by a
masked read-modify-write of that one row. ``ops.uses_kernel`` bounds
the resident buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: messages per grid step (SMEM block of every input plane).
BLOCK = 1024


def _pack_kernel(slots_ref, *refs, n_cols: int, n_rows: int):
    col_refs = refs[:n_cols]
    out_ref = refs[n_cols]

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(i, carry):
        f = slots_ref[i]

        @pl.when(f < n_rows)
        def _():
            row = pl.ds(f // LANES, 1)
            hit = lane == f % LANES
            for w in range(n_cols):
                out_ref[w, row, :] = jnp.where(hit, col_refs[w][i],
                                               out_ref[w, row, :])

        return carry

    jax.lax.fori_loop(0, BLOCK, body, 0)


@functools.partial(jax.jit, static_argnames=("n_rows", "interpret"))
def mailbox_pack_pallas(cols, slots: jax.Array, n_rows: int,
                        interpret: bool = True) -> jax.Array:
    """(Q,)*W word-planes + slot indices -> (W, n_rows) send buffer."""
    n_cols = len(cols)
    q = slots.shape[0]
    pad = -q % BLOCK
    # padded messages carry slot n_rows: they never ship
    slots = jnp.pad(slots, (0, pad), constant_values=n_rows)
    cols = [jnp.pad(c, (0, pad)) for c in cols]
    rows = pl.cdiv(n_rows, LANES)
    block = pl.BlockSpec((BLOCK,), lambda j: (j,), memory_space=pltpu.SMEM)
    kernel = functools.partial(_pack_kernel, n_cols=n_cols, n_rows=n_rows)
    out = pl.pallas_call(
        kernel,
        grid=((q + pad) // BLOCK,),
        in_specs=[block] * (n_cols + 1),
        out_specs=pl.BlockSpec((n_cols, rows, LANES), lambda j: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_cols, rows, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slots, *cols)
    return out.reshape(n_cols, rows * LANES)[:, :n_rows]
