"""Sharding resolver: divisibility downgrades, axis reuse, rule order."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.runtime import sharding as shlib
from repro.launch.mesh import make_mesh


def mesh44():
    return make_mesh((1, 1), ("data", "model"))


def fake_mesh(shape, names):
    """Abstract mesh for resolution tests (no devices needed)."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(names))


def test_divisible_dims_shard():
    mesh = fake_mesh((16, 16), ("data", "model"))
    spec = shlib.resolve_spec((256, 4096), ("batch", "mlp"), mesh)
    assert spec == P("data", "model")


def test_non_divisible_downgrades_with_report():
    mesh = fake_mesh((16, 16), ("data", "model"))
    rep = shlib.ResolveReport()
    spec = shlib.resolve_spec((49155, 64), ("vocab", "embed"), mesh,
                              name="emb", report=rep)
    assert spec == P(None, None)
    assert any("49155" in d for d in rep.downgrades)


def test_axis_used_once():
    mesh = fake_mesh((16, 16), ("data", "model"))
    # both dims want "model": only the first gets it
    spec = shlib.resolve_spec((4096, 4096), ("mlp", "mlp"), mesh)
    assert spec == P("model", None)


def test_candidate_fallback_order():
    mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    # batch prefers (pod, data) jointly = 32
    spec = shlib.resolve_spec((256,), ("batch",), mesh)
    assert spec == P(("pod", "data"))
    # batch=8 not divisible by 32 -> falls to data(16)? 8%16!=0 -> repl
    spec = shlib.resolve_spec((8,), ("batch",), mesh)
    assert spec == P(None)


def test_multipod_expert_rule():
    mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    spec = shlib.resolve_spec((384, 7168, 2048),
                              ("experts", "embed", "expert_mlp"), mesh)
    assert spec == P("data", None, "model")
