"""Public wrapper for mailbox_pack: dispatch between the fused Pallas
kernel and the XLA fallback, with the interpret-mode switch for CPU."""
from __future__ import annotations

import jax

from repro.kernels.mailbox_pack import kernel as _kernel
from repro.kernels.mailbox_pack import ref as _ref

#: bytes of the VMEM-resident send buffer the kernel takes: half of
#: v5e's 128 MiB VMEM. The TPU compiler accepts a buffer of this size
#: and refuses one of twice it (tests/test_tpu_compile.py compiles both).
VMEM_BUDGET = 64 * 1024 * 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def uses_kernel(n_cols: int, n_rows: int) -> bool:
    """Whether :func:`mailbox_pack` with ``use_pallas=True`` runs the
    Pallas kernel for a ``(n_cols, n_rows)`` send buffer (else the XLA
    scatter reference)."""
    rows = -(-n_rows // _kernel.LANES)
    return 4 * n_cols * rows * _kernel.LANES <= VMEM_BUDGET


def mailbox_pack(cols, slots: jax.Array, n_rows: int,
                 use_pallas: bool = False) -> jax.Array:
    """Build the packed (W, n_rows) int32 mailbox send buffer.

    out[w, slots[i]] = cols[w][i] for messages with slots[i] < n_rows;
    everything else is zero (invalid on the wire). ``use_pallas`` routes
    through the fused kernel when :func:`uses_kernel` admits the buffer.
    """
    if use_pallas and uses_kernel(len(cols), n_rows):
        return _kernel.mailbox_pack_pallas(tuple(cols), slots, n_rows,
                                           interpret=not _on_tpu())
    return _ref.mailbox_pack_ref(cols, slots, n_rows)
