"""Pallas TPU kernel: blockwise flash attention (fwd) with GQA,
causal/sliding-window masking and logit soft-capping.

TPU mapping: grid = (batch, q_heads, q_blocks, kv_blocks) with the
kv-block dimension minor — TPU executes the grid sequentially, so the
running-softmax state (m, l, acc) lives in VMEM scratch and carries
across kv steps (the standard TPU flash-attention schedule; the
HBM->VMEM block streaming replaces the GPU's SMEM tiling).

BlockSpecs pin one (block_q, d) query tile and one (block_k, d) KV tile
in VMEM per step; the GQA index map folds the q-head -> kv-head mapping
into the K/V block fetch, so grouped heads re-stream the same KV tile
instead of materializing repeated heads in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int | None,
                  softcap: float | None, q_offset: int, n_kv: int,
                  lq_valid: int, lk_valid: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    blq, d = q_ref.shape
    blk = k_ref.shape[0]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = q_offset + iq * blq + jax.lax.broadcasted_iota(jnp.int32, (blq, blk), 0)
    k_pos = ik * blk + jax.lax.broadcasted_iota(jnp.int32, (blq, blk), 1)
    mask = (q_pos < q_offset + lq_valid) & (k_pos < lk_valid)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ik == n_kv - 1)
    def _finish():
        l = l_ref[...]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[...] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "q_offset",
                     "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=None, softcap=None,
                           scale=None, q_offset=0, block_q=128, block_k=128,
                           interpret=True):
    """q: (B, Hq, Lq, D); k/v: (B, Hkv, Lk, D). Returns (B, Hq, Lq, D)."""
    b, hq, lq, d = q.shape
    _, hkv, lk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    block_q = min(block_q, max(lq, 8))
    block_k = min(block_k, max(lk, 8))
    lq_pad = -(-lq // block_q) * block_q
    lk_pad = -(-lk // block_k) * block_k
    if lq_pad != lq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, lq_pad - lq), (0, 0)))
    if lk_pad != lk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, lk_pad - lk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, lk_pad - lk), (0, 0)))
    n_q = lq_pad // block_q
    n_kv = lk_pad // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, n_kv=n_kv, lq_valid=lq,
        lk_valid=lk)

    out = pl.pallas_call(
        kernel,
        grid=(b, hq, n_q, n_kv),
        in_specs=(
            pl.BlockSpec((None, None, block_q, d),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
        ),
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, lq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :lq]
