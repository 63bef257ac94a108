"""Optimizer: convergence, int8/bf16 state parity, ZeRO sharding specs,
compression roundtrips (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.launch.mesh import make_mesh
from repro.optim import adamw, schedule
from repro.runtime import compression


def _quadratic_params():
    return {"w": jnp.asarray(np.random.default_rng(0).normal(size=(32,)),
                             jnp.float32)}


def _run(state_dtype, steps=300):
    cfg = adamw.AdamWConfig(lr=0.05, weight_decay=0.0,
                            state_dtype=state_dtype)
    params = _quadratic_params()
    target = jnp.arange(32, dtype=jnp.float32) / 32
    opt = adamw.init(params, cfg)

    @jax.jit
    def step(params, opt):
        loss, grads = jax.value_and_grad(
            lambda p: jnp.sum((p["w"] - target) ** 2))(params)
        params, opt, _ = adamw.update(grads, opt, params, cfg)
        return params, opt, loss

    for _ in range(steps):
        params, opt, loss = step(params, opt)
    return float(loss)


def test_adamw_converges_fp32():
    assert _run("float32") < 1e-4


@pytest.mark.parametrize("state_dtype", ["bfloat16", "int8"])
def test_adamw_low_precision_states_converge(state_dtype):
    assert _run(state_dtype) < 1e-2


def test_master_weights_keep_bf16_params_training():
    cfg = adamw.AdamWConfig(lr=1e-4, weight_decay=0.0, master_weights=True)
    params = {"w": jnp.ones((16,), jnp.bfloat16)}
    opt = adamw.init(params, cfg)
    grads = {"w": jnp.full((16,), 1e-3, jnp.float32)}
    p = params
    for _ in range(10):
        p, opt, _ = adamw.update(grads, opt, p, cfg)
    # bf16-only updates of 1e-4*direction would be lost to rounding;
    # master weights accumulate them
    assert float(jnp.abs(opt["master"]["w"] - 1.0).max()) > 0


def test_zero1_state_shardings_add_data_axis():
    import os
    from repro.models.params import spec
    mesh = make_mesh((1, 1), ("data", "model"))
    tree = {"w": spec((64, 32), ("embed", "mlp"))}
    sh = adamw.state_shardings(tree, mesh, adamw.AdamWConfig(), zero1=True)
    # with axis sizes 1 everything divides; the first unsharded dim of
    # the moment gets the data axis
    pspec = sh["m"]["w"].spec
    assert "data" in str(pspec)


def test_schedules():
    s = jnp.arange(0, 1000, 50)
    w = schedule.cosine_warmup(s, warmup_steps=100, total_steps=1000)
    assert float(w[0]) == 0.0
    assert float(w.max()) <= 1.0
    assert float(w[-1]) >= 0.1 - 1e-6
    r = schedule.rsqrt(s, warmup_steps=100)
    assert float(r.max()) <= 1.0


# ------------------------------------------------------------- compression
@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 2000), scale=st.floats(1e-3, 1e3),
       seed=st.integers(0, 99))
def test_qint8_roundtrip_error_bound(n, scale, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n,)) * scale, jnp.float32)
    q = compression.QInt8.quantize(x)
    err = np.abs(np.asarray(q.dequantize()) - np.asarray(x))
    # blockwise absmax scaling: error <= scale_block / 2 per element
    blocks = np.asarray(q.scale)
    bound = np.repeat(blocks, compression.BLOCK)[:n] * 0.5 + 1e-7
    assert (err <= bound).all()


def test_qint8_shapes_and_zeros():
    q = compression.QInt8.zeros((3, 5, 7))
    assert q.dequantize().shape == (3, 5, 7)
    x = jnp.zeros((3, 5, 7))
    np.testing.assert_array_equal(np.asarray(q.dequantize()), np.asarray(x))


def test_error_feedback_unbiased_over_steps():
    """Error feedback: the accumulated applied signal converges to the
    true sum even with coarse quantization."""
    rng = np.random.default_rng(0)
    true = jnp.asarray(rng.normal(size=(512,)), jnp.float32) * 1e-4
    err = jnp.zeros_like(true)
    applied = jnp.zeros_like(true)
    for _ in range(200):
        xc = true + err
        q = compression.QInt8.quantize(xc)
        deq = q.dequantize()
        err = xc - deq
        applied = applied + deq
    np.testing.assert_allclose(np.asarray(applied / 200), np.asarray(true),
                               atol=1e-6)
