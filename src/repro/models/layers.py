"""Model building blocks, pure-JAX with logical-axis annotated params.

Every block has a ``*_specs(cfg)`` (ParamSpec tree) and an apply
function. Attention and the SSD scan dispatch to the Pallas kernels
when ``cfg.use_kernels`` (smoke tests / real TPU); the dry-run path
lowers the pure-jnp references so the 512-device SPMD partitioner sees
plain XLA ops.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models.params import spec

# ---------------------------------------------------------------------------
# norms / rope / embedding
# ---------------------------------------------------------------------------


def rms_norm_spec(d):
    return {"scale": spec((d,), ("embed",), init="ones")}


def rms_norm(p, x, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * p["scale"].astype(x.dtype)


def rope(x, positions, theta):
    """x: (..., L, H, D) rotary over last dim; positions: (..., L)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., L, half)
    ang = ang[..., None, :]  # broadcast over heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def embed_specs(cfg):
    return {"embedding": spec((cfg.padded_vocab, cfg.d_model),
                              ("vocab", "embed"), cfg.dtype, "small_normal")}


def embed(p, tokens, cfg):
    x = jnp.take(p["embedding"], tokens, axis=0)
    return x * jnp.asarray(cfg.d_model ** 0.5, x.dtype) if cfg.scale_embeddings else x


def unembed(p, x, cfg):
    logits = jnp.einsum("...d,vd->...v", x, p["embedding"]).astype(jnp.float32)
    # pin the logits layout (batch over dp axes, vocab over tp): without
    # this, sharding propagation may replicate the tied embedding at the
    # unembed site and compute full-vocab logits per device (§Perf
    # P-dense: a 9x per-device FLOP regression under pure-DP mappings).
    from repro.runtime import context as _rc
    ctx = _rc.current()
    if ctx is not None:
        from jax.sharding import PartitionSpec as P
        mesh = ctx.mesh
        bdim = logits.shape[0]
        dp = tuple(a for a in ctx.dp_axes if a in mesh.axis_names)
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        spec = [None] * logits.ndim
        if dp and bdim % dp_size == 0:
            spec[0] = dp if len(dp) > 1 else dp[0]
        if ctx.tp_axis and logits.shape[-1] % mesh.shape[ctx.tp_axis] == 0:
            spec[-1] = ctx.tp_axis
        logits = jax.lax.with_sharding_constraint(
            logits, jax.NamedSharding(mesh, P(*spec)))
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: jax.Array  # (B, Hkv, S, Dh)
    v: jax.Array


def attention_specs(cfg, cross: bool = False):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    s = {
        "wq": spec((d, hq * dh), ("embed", "qkv_features"), cfg.dtype),
        "wk": spec((d, hkv * dh), ("embed", "kv_features"), cfg.dtype),
        "wv": spec((d, hkv * dh), ("embed", "kv_features"), cfg.dtype),
        "wo": spec((hq * dh, d), ("qkv_features", "embed"), cfg.dtype),
    }
    if cfg.qkv_bias and not cross:
        s["bq"] = spec((hq * dh,), ("qkv_features",), cfg.dtype, "zeros")
        s["bk"] = spec((hkv * dh,), ("kv_features",), cfg.dtype, "zeros")
        s["bv"] = spec((hkv * dh,), ("kv_features",), cfg.dtype, "zeros")
    return s


def _project_qkv(p, xq, xkv, cfg):
    b, lq, _ = xq.shape
    lk = xkv.shape[1]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = xq @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, lq, hq, dh)
    k = k.reshape(b, lk, hkv, dh)
    v = v.reshape(b, lk, hkv, dh)
    return q, k, v


def _sdpa(q, k, v, cfg, *, causal, window, q_offset):
    """q: (B,L,H,D) -> (B,L,H,D); dispatches kernel vs reference."""
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    scale = cfg.attn_scale if cfg.attn_scale else cfg.resolved_head_dim ** -0.5
    if cfg.use_kernels:
        from repro.kernels.flash_attention import ops as fa
        out = fa.flash_attention(qh, kh, vh, causal, window,
                                 cfg.attn_softcap, scale, q_offset, True)
    else:
        from repro.kernels.flash_attention import ref as fa_ref
        out = fa_ref.attention_ref(qh, kh, vh, causal=causal, window=window,
                                   softcap=cfg.attn_softcap, scale=scale,
                                   q_offset=q_offset)
    return jnp.swapaxes(out, 1, 2)


def attention(p, x, cfg, *, positions, causal=True, is_local=None,
              cache: KVCache | None = None, cache_pos=None,
              kv_x=None, kv_positions=None):
    """Self/cross attention with optional KV cache.

    is_local: traced bool scalar — sliding-window layers inside a layer
    scan (lax.cond between windowed and global paths).
    kv_x: encoder output for cross-attention (no cache update path).
    """
    b, lq, _ = x.shape
    xkv = kv_x if kv_x is not None else x
    q, k, v = _project_qkv(p, x, xkv, cfg)
    if kv_x is None:  # rope only for self-attention
        q = rope(q, positions, cfg.rope_theta)
        kv_pos = kv_positions if kv_positions is not None else positions
        k = rope(k, kv_pos, cfg.rope_theta)

    q_offset = 0
    if cache is not None:
        kh = jnp.swapaxes(k, 1, 2)
        vh = jnp.swapaxes(v, 1, 2)
        cp = jnp.asarray(cache_pos)
        if cp.ndim == 1:
            # per-slot positions (continuous-batching decode, lq == 1)
            bidx = jnp.arange(b, dtype=jnp.int32)
            k = cache.k.at[bidx, :, cp].set(kh[:, :, 0])
            v = cache.v.at[bidx, :, cp].set(vh[:, :, 0])
        else:
            # uniform position: contiguous append
            k = jax.lax.dynamic_update_slice(cache.k, kh, (0, 0, cache_pos, 0))
            v = jax.lax.dynamic_update_slice(cache.v, vh, (0, 0, cache_pos, 0))
        new_cache = KVCache(k, v)
        k = jnp.swapaxes(k, 1, 2)
        v = jnp.swapaxes(v, 1, 2)
        q_offset = cache_pos
    else:
        new_cache = None

    qo = q_offset
    if cfg.use_kernels and not isinstance(qo, int):
        # the Pallas kernel takes a static offset; traced/per-slot
        # offsets use the reference path
        cfg = cfg.with_(use_kernels=False)

    def run(window):
        return _sdpa(q, k, v, cfg, causal=causal, window=window,
                     q_offset=qo)

    if is_local is None or cfg.local_window is None:
        out = run(cfg.local_window if cfg.layer_pattern == "local_only" else None)
    else:
        out = jax.lax.cond(is_local, lambda: run(cfg.local_window),
                           lambda: run(None))
    out = out.reshape(b, lq, -1) @ p["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# feed-forward: dense SwiGLU and MoE
# ---------------------------------------------------------------------------


def swiglu_specs(cfg, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w_gate": spec((d, f), ("embed", "mlp"), cfg.dtype),
        "w_up": spec((d, f), ("embed", "mlp"), cfg.dtype),
        "w_down": spec((f, d), ("mlp", "embed"), cfg.dtype),
    }


def swiglu(p, x):
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    return h @ p["w_down"]


def moe_specs(cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    s = {
        "router": spec((d, e), ("embed", "experts"), jnp.float32,
                       "small_normal"),
        "w_gate": spec((e, d, f), ("experts", "embed", "expert_mlp"), cfg.dtype),
        "w_up": spec((e, d, f), ("experts", "embed", "expert_mlp"), cfg.dtype),
        "w_down": spec((e, f, d), ("experts", "expert_mlp", "embed"), cfg.dtype),
    }
    if cfg.num_shared_experts:
        s["shared"] = swiglu_specs(cfg, d_ff=cfg.d_ff * cfg.num_shared_experts)
    return s


def moe_ffn(p, x, cfg):
    """MoE FFN dispatcher: expert-parallel shard_map path when a mesh
    context is active (production), single-program sort-based dispatch
    otherwise (single-device tests; also the GSPMD-auto baseline that
    EXPERIMENTS.md §Perf measures against).
    """
    from repro.runtime import context as runtime_context
    ctx = runtime_context.current()
    if ctx is not None and cfg.num_experts % ctx.mesh.shape[ctx.ep_axis] == 0:
        y, aux = moe_ffn_ep(p, x, cfg, ctx)
        # name the output so remat policies can save/offload it instead
        # of re-running the dispatch all_to_alls in the backward pass
        from jax.ad_checkpoint import checkpoint_name
        y = checkpoint_name(y, "moe_out")
        return y, aux
    return _moe_ffn_dense(p, x, cfg)


def _moe_ffn_dense(p, x, cfg):
    """Sort-based top-k dispatch with per-expert capacity (dropless-lite).

    Tokens are flattened, their top-k expert assignments sorted by
    expert id, and packed into an (E, C, D) buffer (overflow dropped —
    capacity_factor controls the drop rate). Expert GEMMs run as one
    batched einsum; results scatter back weighted by the (re-normalized)
    router gates. Aux load-balancing loss is returned for training.
    """
    b, l, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    n = b * l
    xf = x.reshape(n, d)
    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eidx = jax.lax.top_k(probs, k)          # (n, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # aux loss (Switch-style load balancing)
    me = probs.mean(axis=0)
    ce = jnp.zeros(e, jnp.float32).at[eidx.reshape(-1)].add(1.0) / (n * k)
    aux_loss = e * jnp.sum(me * ce)

    flat_e = eidx.reshape(-1)                           # (n*k,)
    flat_tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e)
    se, stok, sgate = flat_e[order], flat_tok[order], flat_gate[order]
    # position of each assignment within its expert
    starts = jnp.searchsorted(se, jnp.arange(e + 1, dtype=se.dtype))
    pos = jnp.arange(n * k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    cap = max(8, int(cfg.capacity_factor * n * k / e)) if e > 1 else n * k
    keep = pos < cap
    row = jnp.where(keep, se, e).astype(jnp.int32)
    col = jnp.where(keep, pos, cap).astype(jnp.int32)

    buf = jnp.zeros((e + 1, cap + 1, d), x.dtype).at[row, col].set(
        xf[stok], mode="drop")[:e, :cap]
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    yb = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, p["w_down"])
    # combine back
    gathered = yb[jnp.minimum(row, e - 1), jnp.minimum(col, cap - 1)]
    contrib = jnp.where(keep[:, None], gathered * sgate[:, None].astype(x.dtype), 0)
    y = jnp.zeros((n, d), x.dtype).at[stok].add(contrib)
    if cfg.num_shared_experts:
        y = y + swiglu(p["shared"], xf)
    return y.reshape(b, l, d), aux_loss


# ---------------------------------------------------------------------------
# Mamba-2 mixer
# ---------------------------------------------------------------------------


class SSMCache(NamedTuple):
    conv: jax.Array   # (B, K-1, conv_dim)
    state: jax.Array  # (B, H, N, P)


def _mamba_dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return d_inner, n_heads, conv_dim


def mamba_specs(cfg):
    d = cfg.d_model
    d_inner, h, conv_dim = _mamba_dims(cfg)
    g, n = cfg.ssm_groups, cfg.ssm_state
    proj_out = 2 * d_inner + 2 * g * n + h
    return {
        "in_proj": spec((d, proj_out), ("embed", "mlp"), cfg.dtype),
        "conv_w": spec((cfg.ssm_conv, conv_dim), ("conv", "mlp"), cfg.dtype),
        "conv_b": spec((conv_dim,), ("mlp",), cfg.dtype, "zeros"),
        "a_log": spec((h,), ("ssm_heads",), jnp.float32, "zeros"),
        "dt_bias": spec((h,), ("ssm_heads",), jnp.float32, "zeros"),
        "d_skip": spec((h,), ("ssm_heads",), jnp.float32, "ones"),
        "norm": rms_norm_spec(d_inner),
        "out_proj": spec((d_inner, d), ("mlp", "embed"), cfg.dtype),
    }


def _causal_conv(x, w, b, cache=None):
    """x: (B, L, C) depthwise causal conv, kernel (K, C)."""
    k = w.shape[0]
    if cache is not None:
        x_pad = jnp.concatenate([cache, x], axis=1)
        new_cache = x_pad[:, -(k - 1):] if k > 1 else cache
    else:
        x_pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        new_cache = None
    out = sum(x_pad[:, i:i + x.shape[1]] * w[i] for i in range(k)) + b
    return out, new_cache


def mamba_mixer(p, x, cfg, *, cache: SSMCache | None = None):
    """Mamba-2 block body. x: (B, L, D) -> (B, L, D)."""
    b, l, d = x.shape
    d_inner, h, conv_dim = _mamba_dims(cfg)
    g, n, pdim = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_head_dim
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt_raw = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"],
                                 cache.conv if cache is not None else None)
    xbc = jax.nn.silu(xbc)
    xin, bmat, cmat = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    xh = xin.reshape(b, l, h, pdim)
    bh = bmat.reshape(b, l, g, n)
    ch = cmat.reshape(b, l, g, n)

    if cache is not None and l == 1:
        # single-token decode against the carried state
        from repro.kernels.ssd_scan import ops as ssd_ops
        y, new_state = ssd_ops.ssd_decode_step(
            xh[:, 0], dt[:, 0], a, bh[:, 0], ch[:, 0], p["d_skip"],
            cache.state)
        y = y[:, None]
        new_cache = SSMCache(new_conv, new_state)
    elif cache is not None:
        # prefill: chunked scan, build the cache for subsequent decoding
        from repro.kernels.ssd_scan import ref as ssd_ref
        y, new_state = ssd_ref.ssd_chunked_ref(
            xh, dt, a, bh, ch, p["d_skip"], chunk=cfg.ssm_chunk,
            return_state=True)
        # conv cache holds the last K-1 *pre-conv* channel inputs
        xbc_tail = zxbcdt[:, -(cfg.ssm_conv - 1):,
                          d_inner:d_inner + conv_dim]
        new_cache = SSMCache(xbc_tail.astype(cache.conv.dtype), new_state)
    else:
        if cfg.use_kernels:
            from repro.kernels.ssd_scan import ops as ssd_ops
            y = ssd_ops.ssd_scan(xh, dt, a, bh, ch, p["d_skip"],
                                 cfg.ssm_chunk, True)
        else:
            from repro.kernels.ssd_scan import ref as ssd_ref
            y = ssd_ref.ssd_chunked_ref(xh, dt, a, bh, ch, p["d_skip"],
                                        chunk=cfg.ssm_chunk)
        new_cache = None
    y = y.reshape(b, l, d_inner)
    y = rms_norm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    return y @ p["out_proj"], new_cache


def init_ssm_cache(cfg, batch, dtype):
    d_inner, h, conv_dim = _mamba_dims(cfg)
    return SSMCache(
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
        state=jnp.zeros((batch, h, cfg.ssm_state, cfg.ssm_head_dim),
                        jnp.float32))


# ---------------------------------------------------------------------------
# Hymba mixer: parallel attention + SSM heads (arXiv:2411.13676)
# ---------------------------------------------------------------------------


def hymba_specs(cfg):
    return {
        "attn": attention_specs(cfg),
        "mamba": mamba_specs(cfg),
        "norm_attn": rms_norm_spec(cfg.d_model),
        "norm_ssm": rms_norm_spec(cfg.d_model),
    }


def hymba_mixer(p, x, cfg, *, positions, is_local=None, cache=None,
                cache_pos=None):
    """Parallel attn+SSM heads, outputs mean-fused after per-branch
    normalization (the paper's beta-weighted mean, with beta = 1)."""
    kv, ssm = (cache if cache is not None else (None, None))
    attn_out, new_kv = attention(p["attn"], x, cfg, positions=positions,
                                 causal=True, is_local=is_local,
                                 cache=kv, cache_pos=cache_pos)
    # hymba's mamba branch keeps the block residual outside; out_proj of
    # the mamba sub-block maps back to d_model so the mean is welldefined
    ssm_out, new_ssm = mamba_mixer(p["mamba"], x, cfg, cache=ssm)
    out = 0.5 * (rms_norm(p["norm_attn"], attn_out, cfg.norm_eps)
                 + rms_norm(p["norm_ssm"], ssm_out, cfg.norm_eps))
    new_cache = (new_kv, new_ssm) if cache is not None else None
    return out, new_cache


# ---------------------------------------------------------------------------
# expert-parallel MoE (shard_map + the paper's routing engine)
# ---------------------------------------------------------------------------
#
# The auto-partitioned sort/scatter dispatch above is opaque to GSPMD
# (data-dependent scatters cannot be sharded), which the kimi-k2 dry-run
# baseline shows as ~10^14 bytes of all-reduce per step. The production
# path instead runs dispatch *manually* inside shard_map:
#
#   tokens --route(all_to_all over the data axis)--> expert shards
#   (E_loc, C, D) batched GEMMs (d_ff sharded over "model", psum)
#   results --route back--> source shards, gate-weighted combine.
#
# Token routing reuses repro.core.listrank.exchange.route — the paper's
# message-coalescing engine; on multi-pod meshes experts are placed
# within a pod (DP across pods), the topology-aware placement of §2.4.
# Capacity overflow = token drop, the standard MoE semantics; counted.


def moe_ffn_ep(p, x, cfg, ctx):
    """Expert-parallel MoE. x: (B, L, D) sharded over ctx.dp_axes."""
    import functools
    from jax.sharding import PartitionSpec as P
    from repro.core.listrank.config import IndirectionSpec
    from repro.core.listrank.exchange import MeshPlan, route

    mesh = ctx.mesh
    ep = ctx.ep_axis
    tp = ctx.tp_axis
    e_total = cfg.num_experts
    p_ep = mesh.shape[ep]
    assert e_total % p_ep == 0, (e_total, p_ep)
    e_loc = e_total // p_ep
    dp_spec = P(ctx.dp_axes, None, None)
    w_spec = P(ep, None, tp)      # (E, D, F)
    w_spec_t = P(ep, tp, None)    # (E, F, D)
    shared_specs = {k: P(None, tp) if k != "w_down" else P(tp, None)
                    for k in ("w_gate", "w_up", "w_down")}

    def body(xb, router, wg, wu, wd, shared):
        b_loc, l, d = xb.shape
        s = b_loc * l
        k = cfg.top_k
        xf = xb.reshape(s, d)
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, eidx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True),
                                         1e-9)
        # aux loss over the local shard (pmean'd below)
        me = probs.mean(axis=0)
        ce = jnp.zeros(e_total, jnp.float32).at[eidx.reshape(-1)].add(
            1.0) / (s * k)
        aux = e_total * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, ctx.dp_axes)

        q = s * k
        flat_e = eidx.reshape(-1).astype(jnp.int32)
        flat_gate = gate_vals.reshape(-1).astype(xb.dtype)
        flat_x = jnp.repeat(xf, k, axis=0)
        slot = jnp.arange(q, dtype=jnp.int32)

        plan = MeshPlan.from_mesh(mesh, (ep,), IndirectionSpec.direct((ep,)))
        me_id = plan.my_id().astype(jnp.int32)
        dest = flat_e // e_loc
        # per-dest-shard mailbox: shard-level loads pool e_loc experts,
        # so a binomial mean+5sigma bound suffices (1.03x padding at
        # kimi scale vs the 1.25x naive slack — §Perf P2); per-expert
        # capacity below keeps the capacity_factor drop semantics.
        m_dest = q / p_ep
        cap_send = min(q, int(m_dest + 5.0 * m_dest ** 0.5) + 8)
        payload = {"x": flat_x, "g": flat_gate, "slot": slot,
                   "src": jnp.full((q,), 0, jnp.int32) + me_id,
                   "e": flat_e}
        delivered, dval, leftovers, _ = route(
            plan, [cap_send], payload, dest, jnp.ones(q, bool))
        dropped_route = sum(jnp.sum(lv) for *_x, lv in leftovers)

        # group by local expert with per-expert capacity
        r = delivered["e"].shape[0]
        le = jnp.where(dval, delivered["e"] - me_id * e_loc, e_loc)
        order = jnp.argsort(jnp.where(dval, le, e_loc), stable=True)
        sle = jnp.where(dval, le, e_loc)[order]
        starts = jnp.searchsorted(sle, jnp.arange(e_loc + 1, dtype=sle.dtype))
        pos = jnp.arange(r, dtype=jnp.int32) - starts[
            jnp.minimum(sle, e_loc)].astype(jnp.int32)
        cap_e = max(8, int(cfg.capacity_factor * q / e_loc))
        fits = (sle < e_loc) & (pos < cap_e)
        row = jnp.where(fits, sle, e_loc).astype(jnp.int32)
        col = jnp.where(fits, pos, cap_e).astype(jnp.int32)
        xbuf = jnp.zeros((e_loc + 1, cap_e + 1, d), xb.dtype).at[
            row, col].set(delivered["x"][order], mode="drop")[:e_loc, :cap_e]

        h = jnp.einsum("ecd,edf->ecf", xbuf, wg)
        u = jnp.einsum("ecd,edf->ecf", xbuf, wu)
        yb = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, wd)
        # d_ff is sharded over `tp`, so yb holds partial sums. The psum
        # happens AFTER the gate-weighted combine back at the source
        # shard: all-reducing (tokens, d) instead of the padded
        # (E_loc, C, d) buffer cuts all-reduce bytes ~10x (§Perf P1).

        ydel = jnp.zeros((r, d), xb.dtype)
        gathered = yb[jnp.minimum(row, e_loc - 1),
                      jnp.minimum(col, cap_e - 1)]
        gathered = jnp.where(fits[:, None], gathered, 0)
        ydel = ydel.at[order].set(gathered)

        # route results back to the source shard
        back_payload = {"y": ydel, "slot": delivered["slot"],
                        "g": delivered["g"]}
        bdel, bval, bleft, _ = route(plan, [cap_send], back_payload,
                                     delivered["src"], dval)
        sidx = jnp.where(bval, bdel["slot"], q).astype(jnp.int32)
        contrib = jnp.where(bval[:, None],
                            bdel["y"] * bdel["g"][:, None], 0)
        y = jnp.zeros((q + 1, d), xb.dtype).at[sidx].add(
            contrib, mode="drop")[:q]
        y = y.reshape(s, k, d).sum(axis=1)
        if cfg.num_shared_experts:
            hs = jax.nn.silu(xf @ shared["w_gate"]) * (xf @ shared["w_up"])
            y = y + hs @ shared["w_down"]  # also partial over tp
        if tp is not None:
            y = jax.lax.psum(y, tp)  # one combined all-reduce (P1)
        return y.reshape(b_loc, l, d), aux

    in_specs = (dp_spec, P(None, None), w_spec, w_spec, w_spec_t,
                shared_specs if cfg.num_shared_experts else P())
    shared_p = p.get("shared", jnp.zeros((), x.dtype))
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=(dp_spec, P()),
        check_vma=False)(
        x, p["router"].astype(jnp.float32), p["w_gate"], p["w_up"],
        p["w_down"], shared_p)
    return out
