"""Transport backends: one traced per-PE program, two ways to run it.

Every algorithm in this repo is written as a *per-PE body* — per-shard
arrays, collectives by mesh-axis name — and executed by a thin driver.
This module abstracts the driver into a :class:`Transport` plus
:func:`device_run`, with two interchangeable backends:

- **mesh** (:class:`MeshTransport`): the production path. The body runs
  under ``shard_map`` over a real device mesh; collectives are the raw
  ``lax`` primitives, exactly as before this abstraction existed.

- **simshard** (:class:`SimShardTransport`): virtual PEs. The *identical*
  body runs under nested ``vmap`` with the mesh-axis names bound to a
  leading virtual-PE axis on ONE device. JAX's batching rules rewrite
  the named collectives into static data movement at trace time —
  ``all_to_all`` becomes a transpose over the batch axis, ``axis_index``
  an iota, ``psum`` a sum — so any ``p`` (64, 256, 1024, ...) runs in a
  single process with **bit-identical** semantics to the mesh backend
  (verified by the golden pins in ``tests/test_simshard_golden.py``).

Because the vmap rewrite erases the collective eqns from the jaxpr, the
simshard backend wraps each collective in a *named jit marker*
(``simshard_all_to_all`` et al.): the pjit call keeps its name through
batching, and ``introspect.py`` counts markers exactly like real
collectives, keeping the jaxpr-level collective-count pins meaningful on
both backends.

A :class:`SimMesh` is the device-free stand-in for ``jax.Mesh`` (axis
names + sizes only); every front door accepts either. The backend is
chosen per :attr:`ListRankConfig.backend`: ``"auto"`` follows the mesh
object, ``"simshard"`` forces virtual PEs even for a real mesh (same
axis names/sizes, devices ignored), ``"mesh"`` rejects a SimMesh.

Known limits of the simshard backend: the Pallas kernels
(``use_pallas`` / ``use_pallas_pack``) are not supported under the
batched trace and are rejected up front; memory is the real bound on
virtual p — all p shards live on one device
(``benchmarks/simshard_bench.py`` measures how far that pushes).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


# --------------------------------------------------------------------------
# simulated-collective markers
# --------------------------------------------------------------------------
# Named jit wrappers around the raw collectives. Under vmap the enclosed
# primitive is rewritten into batch-axis data movement at trace time, but
# the pjit eqn keeps the function's name — introspect.count_primitives
# recognizes the ``simshard_`` prefix and counts the marker as the
# collective it stands for (and does not recurse into its body, which
# holds only the lowered transposes/reductions).

SIM_MARKER_PREFIX = "simshard_"


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def simshard_all_to_all(x, axes, split_axis, concat_axis, tiled):
    return lax.all_to_all(x, axes, split_axis, concat_axis, tiled=tiled)


@functools.partial(jax.jit, static_argnums=(1,))
def simshard_psum(x, axes):
    return lax.psum(x, axes)


@functools.partial(jax.jit, static_argnums=(1, 2))
def simshard_all_gather(x, axes, tiled):
    # jax's vmap batching rule rejects multi-axis all_gather; gathering
    # the minor axis first reproduces the row-major tuple-axis order of
    # the mesh collective exactly. One marker = one mesh collective, so
    # the counts pin identically.
    for a in reversed(axes):
        x = lax.all_gather(x, a, tiled=tiled)
    return x


# --------------------------------------------------------------------------
# transports
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MeshTransport:
    """Raw ``lax`` collectives by axis name (runs under ``shard_map``)."""

    kind = "mesh"

    def axis_index(self, axes: Sequence[str]) -> jax.Array:
        return lax.axis_index(tuple(axes))

    def all_to_all(self, x, axes, split_axis, concat_axis, tiled=True):
        return lax.all_to_all(x, tuple(axes), split_axis, concat_axis,
                              tiled=tiled)

    def psum(self, x, axes):
        return lax.psum(x, tuple(axes))

    def all_gather(self, x, axes, tiled=True):
        return lax.all_gather(x, tuple(axes), tiled=tiled)


@dataclasses.dataclass(frozen=True)
class SimShardTransport:
    """Marker-wrapped collectives (runs under nested ``vmap``)."""

    kind = "simshard"

    def axis_index(self, axes: Sequence[str]) -> jax.Array:
        # vmap's axis_index rule is already an iota; no marker needed
        # (axis_index is not a collective in the §2.6 model).
        return lax.axis_index(tuple(axes))

    def all_to_all(self, x, axes, split_axis, concat_axis, tiled=True):
        return simshard_all_to_all(x, tuple(axes), split_axis, concat_axis,
                                   tiled)

    def psum(self, x, axes):
        return simshard_psum(x, tuple(axes))

    def all_gather(self, x, axes, tiled=True):
        return simshard_all_gather(x, tuple(axes), tiled)


Transport = Any  # MeshTransport | SimShardTransport (duck-typed protocol)


# --------------------------------------------------------------------------
# virtual meshes
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SimMesh:
    """Device-free virtual mesh: axis names and sizes only.

    Drop-in for ``jax.Mesh`` wherever the front doors only read
    ``axis_names`` / ``shape`` — which, by construction, is everywhere
    (placement is the driver's job, and the simshard driver has no
    placement). Hashable, so the jit caches key on it like a real mesh.
    """

    axis_names: tuple[str, ...]
    axis_sizes: tuple[int, ...]

    def __post_init__(self):
        if len(self.axis_names) != len(self.axis_sizes):
            raise ValueError("axis_names and axis_sizes length mismatch")
        if any(s < 1 for s in self.axis_sizes):
            raise ValueError("axis sizes must be positive")

    @property
    def shape(self) -> dict[str, int]:
        return dict(zip(self.axis_names, self.axis_sizes))

    @property
    def size(self) -> int:
        out = 1
        for s in self.axis_sizes:
            out *= s
        return out


def sim_mesh(shape: int | Sequence[int],
             axis_names: Sequence[str] | None = None) -> SimMesh:
    """A virtual mesh of any shape — no devices required.

    ``sim_mesh(256)`` is a flat 256-PE mesh on axis ``"pe"``;
    ``sim_mesh((2, 128), ("row", "col"))`` a 2D grid for indirection.
    """
    if isinstance(shape, int):
        shape = (shape,)
    shape = tuple(int(s) for s in shape)
    if axis_names is None:
        axis_names = ("pe",) if len(shape) == 1 else tuple(
            f"pe{i}" for i in range(len(shape)))
    return SimMesh(axis_names=tuple(axis_names), axis_sizes=shape)


def is_sim(mesh) -> bool:
    return isinstance(mesh, SimMesh)


def backend_name(mesh) -> str:
    """Canonical backend label of a resolved mesh object — used by the
    observability layer (span annotations, trace metadata) and the
    bench harness; keep in sync with ``resolve_backend``."""
    return "simshard" if is_sim(mesh) else "mesh"


def resolve_backend(backend: str, mesh, pe_axes: Sequence[str]):
    """Resolve a ``ListRankConfig.backend`` against the mesh object.

    Returns ``(backend, mesh)`` — with a real mesh swapped for its
    SimMesh twin when simshard is forced.
    """
    pe_axes = tuple(pe_axes)
    if backend == "auto":
        backend = "simshard" if is_sim(mesh) else "mesh"
    if backend == "simshard" and not is_sim(mesh):
        mesh = SimMesh(axis_names=pe_axes,
                       axis_sizes=tuple(mesh.shape[a] for a in pe_axes))
    elif backend == "mesh" and is_sim(mesh):
        raise ValueError("backend='mesh' requires a real device mesh; "
                         "got a SimMesh (use backend='auto'/'simshard')")
    elif backend not in ("mesh", "simshard"):
        raise ValueError(f"unknown transport backend {backend!r}")
    return backend, mesh


def check_sim_config(cfg) -> None:
    """Reject config knobs the batched trace cannot honor."""
    if cfg.use_pallas or cfg.use_pallas_pack:
        raise ValueError(
            "simshard backend does not support the Pallas kernels "
            "(use_pallas/use_pallas_pack); they assume an unbatched "
            "per-PE trace")


def put_sharded(mesh, pe_axes: Sequence[str], x: jax.Array) -> jax.Array:
    """Host->device placement of a block-sharded input: a real
    ``device_put`` on a mesh, a plain array on a SimMesh (the simshard
    runner folds the PE axis itself)."""
    if is_sim(mesh):
        return jnp.asarray(x)
    return jax.device_put(x, NamedSharding(mesh, P(tuple(pe_axes))))


# --------------------------------------------------------------------------
# the one driver: shard_map on a mesh, nested vmap on a SimMesh
# --------------------------------------------------------------------------

def _spec_is_sharded(spec) -> bool:
    if not isinstance(spec, P):
        raise TypeError(f"expected a PartitionSpec, got {spec!r}")
    if len(spec) == 0:
        return False
    if len(spec) == 1 and spec[0] is not None:
        return True
    raise NotImplementedError(
        f"simshard supports P(pe_axes) on axis 0 or P() specs, got {spec}")


def _map_out(out, spec, n_axes: int, flat: int):
    """Apply an out_specs *prefix* to a sim output subtree: sharded
    leaves fold the virtual-PE axes back into axis 0, replicated leaves
    take the (identical) PE-0 copy."""
    if isinstance(spec, P):
        if _spec_is_sharded(spec):
            return jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[n_axes + 1:]), out)
        return jax.tree.map(lambda x: x[(0,) * n_axes], out)
    if isinstance(spec, dict):
        return {k: _map_out(out[k], spec[k], n_axes, flat) for k in out}
    if isinstance(spec, (list, tuple)):
        return tuple(_map_out(o, s, n_axes, flat)
                     for o, s in zip(out, spec))
    raise TypeError(f"unsupported out_specs node {spec!r}")


def device_run(mesh, pe_axes: Sequence[str], fn, in_specs, out_specs):
    """Jit the per-PE body ``fn`` for ``mesh``: ``jit(shard_map(fn))``
    on a real mesh, a nested-``vmap`` emulation on a :class:`SimMesh`.

    ``in_specs``/``out_specs`` follow the shard_map convention used
    throughout this repo: ``P(pe_axes)`` = block-sharded on axis 0,
    ``P()`` = replicated (out_specs entries may be pytree prefixes).
    """
    pe_axes = tuple(pe_axes)
    if not is_sim(mesh):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs,
                                     check_vma=False))

    sizes = tuple(mesh.shape[a] for a in pe_axes)
    flat = 1
    for s in sizes:
        flat *= s
    in_axes = tuple(0 if _spec_is_sharded(s) else None for s in in_specs)
    body = fn
    # innermost vmap binds the minor (fastest-varying) axis, matching
    # the row-major PE flattening of ``lax.axis_index(pe_axes)``.
    for name in reversed(pe_axes):
        body = jax.vmap(body, axis_name=name, in_axes=in_axes, out_axes=0)

    def fold_leaf(x):
        x = jnp.asarray(x)
        if x.shape[0] % flat != 0:
            raise ValueError(
                f"sharded input of size {x.shape[0]} not divisible "
                f"by virtual PE count {flat}")
        return x.reshape(sizes + (-1,) + x.shape[1:])

    def runner(*args):
        margs = []
        for spec, x in zip(in_specs, args):
            if _spec_is_sharded(spec):
                # the spec is a pytree *prefix* of the argument (shard_map
                # convention): fold the PE axis of every leaf, so whole
                # state pytrees (stores, stat dicts) ride as one arg.
                margs.append(jax.tree.map(fold_leaf, x))
            else:
                margs.append(x)
        out = body(*margs)
        return _map_out(out, out_specs, len(sizes), flat)

    return jax.jit(runner)
