"""Public wrapper for local_chase: dispatch between the Pallas SMEM
kernel and the XLA fallback, with the interpret-mode switch for CPU."""
from __future__ import annotations

import jax

from repro.kernels.local_chase import kernel as _kernel
from repro.kernels.local_chase import ref as _ref

#: largest per-row length the kernel takes. Its six (m,) 32-bit SMEM
#: planes fill 768 KiB of v5e's 1 MiB SMEM at this m; the TPU compiler
#: refuses 2*MAX_M (tests/test_tpu_compile.py compiles both).
MAX_M = 1 << 15


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def uses_kernel(m: int) -> bool:
    """Whether :func:`local_chase` runs the Pallas kernel for rows of
    length ``m`` (else the XLA gather reference)."""
    return m <= MAX_M


def local_chase(succ: jax.Array, dist: jax.Array, steps: int):
    """Wyllie doubling with self-absorbing stops; returns (succ, dist).

    Uses the Pallas kernel when :func:`uses_kernel` admits the row
    length: compiled on a TPU, interpret mode elsewhere.
    """
    if uses_kernel(succ.shape[-1]):
        return _kernel.local_chase_pallas(succ, dist, steps,
                                          interpret=not _on_tpu())
    return _ref.local_chase_ref(succ, dist, steps)
