"""Error-feedback int8 compressed gradient all-reduce under shard_map.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/dp_compression.py

Demonstrates the distributed-optimization trick from
repro.runtime.compression on a pure data-parallel loop: per-device
gradients are quantized to int8 blocks (+fp32 scales), summed across
the data axis, dequantized, with the quantization residual carried as
error feedback. Compares convergence against exact fp32 all-reduce —
the loss curves match to within noise while the gradient wire format
shrinks ~3.6x.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.launch.mesh import make_mesh  # noqa: E402
from repro.runtime import compression  # noqa: E402


def main():
    p = len(jax.devices())
    mesh = make_mesh((p,), ("data",))
    dim = 512
    rng = np.random.default_rng(0)
    w_true = jnp.asarray(rng.normal(size=(dim,)), jnp.float32)
    x_all = jnp.asarray(rng.normal(size=(p * 64, dim)), jnp.float32)
    y_all = x_all @ w_true

    def run(compressed: bool, steps=150, lr=0.05):
        w = jnp.zeros((dim,), jnp.float32)
        err0 = jnp.zeros((p, dim), jnp.float32)  # per-device residual

        @jax.jit
        @functools.partial(
            jax.shard_map, mesh=mesh, check_vma=False,
            in_specs=(P(), P("data"), P("data"), P("data")),
            out_specs=(P(), P("data")))
        def step(w, x, y, err):
            pred = x @ w
            g = 2 * x.T @ (pred - y) / x.shape[0]
            if compressed:
                g, err = compression.compressed_psum(g, "data", err[0])
                g = g / p
                err = err[None]
            else:
                g = jax.lax.pmean(g, "data")
            return w - lr * g, err

        losses = []
        err = err0
        for _ in range(steps):
            w, err = step(w, x_all, y_all, err)
            losses.append(float(jnp.mean((x_all @ w - y_all) ** 2)))
        return losses

    exact = run(False)
    comp = run(True)
    print(f"final loss exact fp32 : {exact[-1]:.3e}")
    print(f"final loss int8+EF    : {comp[-1]:.3e}")
    wire_fp32 = 4 * 512
    wire_int8 = 512 + 4 * (512 // compression.BLOCK)
    print(f"gradient wire bytes: {wire_fp32} -> {wire_int8} "
          f"({wire_fp32 / wire_int8:.1f}x smaller)")
    assert comp[-1] < 1e-2, "compressed training failed to converge"


if __name__ == "__main__":
    main()
