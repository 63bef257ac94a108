"""Pallas TPU kernel: pointer doubling over a PE-local index space in SMEM.

The paper's local contraction (§2.3) chases PE-local chains:

  dist <- dist + dist[succ];  succ <- succ[succ]     (x ``steps``)

Every step is a data-dependent gather over the whole local array. Mosaic
lowers no general vector gather over VMEM (``jnp.take`` on a VMEM vector
is refused: "Only 2D gather is supported"), so the kernel runs the
doubling as scalar code over SMEM, where any index is one load. Each step
reads the current (succ, dist) pair and writes the next one into scratch
before copying it back, so every element sees the previous step's values
exactly as the XLA reference does: results are bit-identical to
``ref.local_chase_ref``, float weights included.

SMEM holds the input, output and scratch copies of both planes
(6 x 4 B x m), which bounds m; ``ops.uses_kernel`` is that bound.

Grid: one program per batch row (independent chases).
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chase_kernel(succ_ref, dist_ref, out_succ_ref, out_dist_ref,
                  next_succ, next_dist, *, steps: int, m: int):
    def copy(src_s, src_d, dst_s, dst_d):
        def body(i, carry):
            dst_s[i] = src_s[i]
            dst_d[i] = src_d[i]
            return carry
        jax.lax.fori_loop(0, m, body, 0)

    def jump(i, carry):
        t = out_succ_ref[i]
        next_succ[i] = out_succ_ref[t]
        next_dist[i] = out_dist_ref[i] + out_dist_ref[t]
        return carry

    def step(_, carry):
        jax.lax.fori_loop(0, m, jump, 0)
        copy(next_succ, next_dist, out_succ_ref, out_dist_ref)
        return carry

    copy(succ_ref, dist_ref, out_succ_ref, out_dist_ref)
    jax.lax.fori_loop(0, steps, step, 0)


@functools.partial(jax.jit, static_argnames=("steps", "interpret"))
def local_chase_pallas(succ: jax.Array, dist: jax.Array, steps: int,
                       interpret: bool = True):
    """(B, m) batched pointer doubling in SMEM. See module docstring."""
    if succ.ndim == 1:
        return jax.tree.map(
            lambda x: x[0],
            local_chase_pallas(succ[None], dist[None], steps, interpret))
    b, m = succ.shape
    kernel = functools.partial(_chase_kernel, steps=steps, m=m)
    out_shapes = (
        jax.ShapeDtypeStruct((b, m), succ.dtype),
        jax.ShapeDtypeStruct((b, m), dist.dtype),
    )
    row = pl.BlockSpec((None, m), lambda i: (i, 0),
                       memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=(row, row),
        out_specs=(row, row),
        out_shape=out_shapes,
        scratch_shapes=[pltpu.SMEM((m,), succ.dtype),
                        pltpu.SMEM((m,), dist.dtype)],
        interpret=interpret,
    )(succ, dist)
