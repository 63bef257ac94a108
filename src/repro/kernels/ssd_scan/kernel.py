"""Pallas TPU kernel: Mamba-2 SSD chunked scan (arXiv:2405.21060 §6).

Two-level structure (deliberately the same shape as locality-aware list
ranking — DESIGN.md §3): the sequence is tiled into chunks of length Q;
each chunk is *contracted* locally with dense MXU matmuls (the analogue
of local contraction), the per-chunk states form a tiny sequential
recurrence across chunks (the base case), and the inter-chunk state is
*propagated* back into each position's output.

Per chunk (head h, group g, state dim N, head dim P):
  lc[t]   = cumsum_s<=t dt[s]*A                      (log decay prefix)
  y_intra = ((C B^T) ⊙ M) @ (dt ⊙ x),  M[t,s] = exp(lc[t]-lc[s])·[s<=t]
  y_inter = exp(lc[t]) * C[t] @ S_prev
  S_new   = exp(lc[Q-1]) * S_prev
            + sum_s exp(lc[Q-1]-lc[s]) dt[s] B[s]^T x[s]

TPU mapping: grid = (batch, heads, n_chunks), chunk dimension minor so
the (N, P) running state lives in VMEM scratch across sequential grid
steps. Blocks: (Q, P) x-tile, (Q, N) B/C tiles (GQA-style group fetch
folded into the index map), all VMEM-resident; the two (Q,Q) and (Q,N/P)
GEMMs hit the MXU. fp32 accumulation throughout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
                n_chunks: int, has_skip: bool, d_ref=None):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[...].astype(jnp.float32)      # (Q,)
    a = a_ref[0].astype(jnp.float32)          # scalar A[h]
    b = b_ref[...].astype(jnp.float32)        # (Q, N)
    c = c_ref[...].astype(jnp.float32)        # (Q, N)
    q = x.shape[0]

    lc = jnp.cumsum(dt * a)                   # (Q,) log-decay prefix
    # intra-chunk: masked decay kernel (the "attention duality" matmul)
    seg = lc[:, None] - lc[None, :]           # lc[t]-lc[s]
    tri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    m = jnp.where(tri, jnp.exp(seg), 0.0)     # (Q, Q)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    w = cb * m                                # (Q, Q)
    dtx = dt[:, None] * x                     # (Q, P)
    y = jax.lax.dot_general(w, dtx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk: propagate carried state into this chunk's outputs
    s_prev = state_ref[...]                   # (N, P)
    y += jnp.exp(lc)[:, None] * jax.lax.dot_general(
        c, s_prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # chunk state contraction + carry update
    decay_to_end = jnp.exp(lc[-1] - lc)       # (Q,)
    bw = b * (decay_to_end * dt)[:, None]     # (Q, N)
    s_chunk = jax.lax.dot_general(bw, x, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state_ref[...] = jnp.exp(lc[-1]) * s_prev + s_chunk

    if has_skip:
        y += d_ref[0].astype(jnp.float32) * x
    y_ref[...] = y.astype(y_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, dt, A, B, C, D=None, *, chunk=128, interpret=True):
    """x: (Bt,L,H,P); dt: (Bt,L,H); A,(D): (H,); B,C: (Bt,L,G,N)."""
    bt, l, h, p = x.shape
    _, _, g, n = B.shape
    assert h % g == 0
    rep = h // g
    chunk = min(chunk, l)
    assert l % chunk == 0, "sequence length must be divisible by chunk"
    n_chunks = l // chunk

    # layout: time-major per (batch, head) for clean chunk BlockSpecs
    xt = jnp.moveaxis(x, 2, 1)                       # (Bt,H,L,P)
    dtt = jnp.moveaxis(dt, 2, 1)                     # (Bt,H,L)
    bb = jnp.moveaxis(B, 2, 1)                       # (Bt,G,L,N)
    cc = jnp.moveaxis(C, 2, 1)                       # (Bt,G,L,N)

    has_skip = D is not None
    args = [xt, dtt, A, bb, cc]
    in_specs = [
        pl.BlockSpec((None, None, chunk, p), lambda b_, h_, ic: (b_, h_, ic, 0)),
        pl.BlockSpec((None, None, chunk), lambda b_, h_, ic: (b_, h_, ic)),
        pl.BlockSpec((1,), lambda b_, h_, ic: (h_,)),
        pl.BlockSpec((None, None, chunk, n),
                     lambda b_, h_, ic: (b_, h_ // rep, ic, 0)),
        pl.BlockSpec((None, None, chunk, n),
                     lambda b_, h_, ic: (b_, h_ // rep, ic, 0)),
    ]
    if has_skip:
        args.append(D)
        in_specs.append(pl.BlockSpec((1,), lambda b_, h_, ic: (h_,)))

    def kern(*refs):
        if has_skip:
            x_r, dt_r, a_r, b_r, c_r, d_r, y_r, s_r = refs
            _ssd_kernel(x_r, dt_r, a_r, b_r, c_r, y_r, s_r,
                        n_chunks=n_chunks, has_skip=True, d_ref=d_r)
        else:
            x_r, dt_r, a_r, b_r, c_r, y_r, s_r = refs
            _ssd_kernel(x_r, dt_r, a_r, b_r, c_r, y_r, s_r,
                        n_chunks=n_chunks, has_skip=False)

    yt = pl.pallas_call(
        kern,
        grid=(bt, h, n_chunks),
        in_specs=tuple(in_specs),
        out_specs=pl.BlockSpec((None, None, chunk, p),
                               lambda b_, h_, ic: (b_, h_, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((bt, h, l, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return jnp.moveaxis(yt, 1, 2)  # (Bt,L,H,P)
