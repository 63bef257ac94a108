"""Controls for the comparison that decides ``correct``: the plain
reference's pointer jumping, put in the program's place (the same
front-door interface as ``entries/rank_list.py``) and broken in one
way that a later change to the program might be tempted by. Each must
come out as not correct at the cell's own size; ``control.py`` runs
them on the chip, ``tests/test_controls.py`` on the CPU.

- ``Jumping("int32")``: the reference itself, on the device. Not a
  control: it shows that the machinery around the controls is sound.
- ``Jumping("int16")``: ranks accumulated in the next narrower integer
  type than the configuration's int32 weights. Wrong wherever a rank
  passes 2^15 - 1, so on lists longer than 32768.
- ``Jumping("int32", short=1)``: one doubling step fewer than the
  longest list needs, so elements more than half that length from
  their list's end report neither it nor their rank: the guarantee
  that every element reaches its list end is broken.
"""
from __future__ import annotations

import functools

import numpy as np


def steps_needed(n: int) -> int:
    """Doubling steps after which every element of a list of ``n``
    elements has reached its end: after k steps an element sees 2^k
    links ahead, and the farthest is n - 1 links from the end."""
    return int(n - 2).bit_length() if n > 2 else 0


@functools.lru_cache(maxsize=None)
def _jump(steps: int, dtype: str):
    import jax
    import jax.numpy as jnp

    def body(_, carry):
        s, w = carry
        return s[s], w + w[s]

    @jax.jit
    def run(succ, rank):
        s, w = jax.lax.fori_loop(0, steps, body,
                                 (succ, rank.astype(dtype)))
        return s, w.astype(jnp.int32)

    return run


class Jumping:
    """Pointer jumping on the cell's first device in ``dtype``, with
    ``short`` doubling steps left out."""

    def __init__(self, devices, dtype: str = "int32", short: int = 0):
        self.device = list(devices)[0]
        self.dtype = dtype
        self.short = short

    def _fn(self, n: int):
        return _jump(max(steps_needed(n) - self.short, 0), self.dtype)

    def compile(self, n: int) -> int:
        import jax
        import jax.numpy as jnp
        like = jax.ShapeDtypeStruct((n,), jnp.int32)
        self._fn(n).lower(like, like).compile()
        return 1

    def place(self, succ, rank):
        import jax
        return (jax.device_put(succ, self.device),
                jax.device_put(rank, self.device))

    def new_recorder(self):
        return None

    def call(self, placed, seed: int, recorder=None):
        import jax
        out = self._fn(placed[0].shape[0])(*placed)
        jax.block_until_ready(out)
        return out, {"attempts": 1}

    @staticmethod
    def to_host(outputs):
        return tuple(np.asarray(a) for a in outputs)

    @staticmethod
    def stage_spans(recorder):
        return []


#: the controls run against every cell, by name
CONTROLS = {
    "int16": dict(dtype="int16"),
    "short-step": dict(dtype="int32", short=1),
}
