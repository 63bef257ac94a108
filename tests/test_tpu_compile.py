"""Compiles for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax; ``topologies.get_topology_desc``
describes a v5e 2x2 host without a chip, and ``.compile()`` then says
what the chip's compiler would say: Mosaic refusals in the Pallas
kernels, VMEM/SMEM limits, programs that do not fit HBM, collectives
that cannot be placed. Nothing runs, so these tests say nothing about
results or times.

The topology is described inside a module fixture, never at import
time: only one process may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.listrank import ListRankConfig, api
from repro.core.listrank.exchange import MeshPlan, route
from repro.kernels.local_chase import kernel as lc_kernel
from repro.kernels.local_chase import ops as lc_ops
from repro.kernels.mailbox_pack import kernel as mp_kernel
from repro.kernels.mailbox_pack import ops as mp_ops
from repro.launch.mesh import make_mesh, make_pe_mesh

V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # described-device executables cannot be read back from the
    # persistent cache; keep it out of the way
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ------------------------------------------------------------ local_chase
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_local_chase_compiles_at_cutoff(one_chip, dtype):
    m = lc_ops.MAX_M
    assert lc_ops.uses_kernel(m) and not lc_ops.uses_kernel(m + 1)
    succ = _shape((1, m), jnp.int32, one_chip)
    dist = _shape((1, m), dtype, one_chip)
    text = lc_kernel.local_chase_pallas.lower(
        succ, dist, steps=15, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


def test_local_chase_refused_above_cutoff(one_chip):
    """The cutoff is the compiler's: twice it overflows SMEM."""
    x = _shape((1, 2 * lc_ops.MAX_M), jnp.int32, one_chip)
    with pytest.raises(Exception, match="smem"):
        lc_kernel.local_chase_pallas.lower(
            x, x, steps=16, interpret=False).compile()


# ----------------------------------------------------------- mailbox_pack
def _pack_lowered(one_chip, n_cols, n_rows, q=1 << 16):
    cols = tuple(_shape((q,), jnp.int32, one_chip) for _ in range(n_cols))
    slots = _shape((q,), jnp.int32, one_chip)
    return mp_kernel.mailbox_pack_pallas.lower(cols, slots, n_rows=n_rows,
                                               interpret=False)


def test_mailbox_pack_compiles_at_cutoff(one_chip):
    w = api.chase_wire_words()
    n_rows = mp_ops.VMEM_BUDGET // (4 * w * mp_kernel.LANES) \
        * mp_kernel.LANES
    assert mp_ops.uses_kernel(w, n_rows)
    assert not mp_ops.uses_kernel(w, n_rows + mp_kernel.LANES)
    text = _pack_lowered(one_chip, w, n_rows).compile().as_text()
    assert "tpu_custom_call" in text


def test_mailbox_pack_refused_above_cutoff(one_chip):
    """The cutoff is the compiler's: twice the budget overflows VMEM."""
    w = api.chase_wire_words()
    n_rows = 2 * mp_ops.VMEM_BUDGET // (4 * w)
    with pytest.raises(Exception, match="vmem"):
        _pack_lowered(one_chip, w, n_rows).compile()


# ------------------------------------------------------------- the solver
def test_stage_program_fits_hbm(topo):
    """The prep stage of the default solve at p=1, m=2^20."""
    mesh = make_pe_mesh(topo.devices[:1])
    stages = dict(api.lower_stages(1 << 20, mesh, cfg=ListRankConfig()))
    ma = stages["prep"].compile().memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < need < V5E_HBM


def test_packed_hop_is_one_all_to_all_on_2x2(topo):
    """One packed exchange hop over all four chips of the 2x2 host."""
    axes = ("row", "col")
    mesh = make_mesh((2, 2), axes, devices=topo.devices)
    plan = MeshPlan.from_mesh(mesh, axes)
    q, cap = 4096, 2048

    def fn(target, weight, dest, valid):
        out, ok, _, _ = route(plan, (cap,), {"target": target,
                                             "weight": weight}, dest, valid)
        return out["target"], out["weight"], ok

    run = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P(axes),) * 4,
                                out_specs=(P(axes),) * 3, check_vma=False))
    sh = NamedSharding(mesh, P(axes))
    args = [_shape((4 * q,), dt, sh)
            for dt in (jnp.int32, jnp.float32, jnp.int32, jnp.bool_)]
    text = run.lower(*args).compile().as_text()
    a2a = [ln for ln in text.splitlines() if " all-to-all(" in ln]
    assert len(a2a) == 1, a2a
    assert "replica_groups={{0,1,2,3}}" in a2a[0]
