"""Front-door adapter for ``repro.core.listrank.rank_list_with_stats``.

The harness hands it the cell's devices and configuration; it builds
the PE mesh, compiles the cell's stage programs, places instances and
makes the timed call. It is the only file of the benchmark that imports
the list-ranking program.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from repro.core.listrank import (IndirectionSpec, ListRankConfig, api,
                                 rank_list_with_stats)
from repro.core.listrank.transport import put_sharded
from repro.launch.mesh import make_pe_mesh
from repro.obs import Tracer

#: stage programs compiled at once: one host thread each, and a TPU
#: compile at 2^20-2^22 elements per chip peaks at 2.6-3.7 GB of host
#: memory, so each gets 10 GiB.
COMPILE_WORKERS = max(1, min(
    (os.cpu_count() or 2) // 2,
    os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE") // (10 << 30)))


class SpanRecorder(Tracer):
    """The program's tracer with its costly extras off: it records the
    solve's spans (the driver's ``stage-attempt`` intervals, each with
    its device-synced ``wall_s``) but leaves ``enabled`` false, which
    skips the per-stage jaxpr trace for the §2.6 prediction and the
    metrics registry. Those cost tens of milliseconds per stage on the
    host and would swamp a small solve."""

    enabled = False


class Entry:
    def __init__(self, devices, config: dict):
        self.mesh = make_pe_mesh(list(devices))
        if list(self.mesh.devices.shape) != config["mesh"]:
            raise ValueError(f"the configuration's mesh {config['mesh']} is "
                             f"not the PE mesh {self.mesh.devices.shape}")
        self.cfg = ListRankConfig(**config.get("listrank_config", {}))
        ind = config.get("indirection", "direct")
        self.indirection = (None if ind == "direct"
                            else IndirectionSpec.grid(tuple(
                                self.mesh.axis_names)))
        self.pe_axes = tuple(self.mesh.axis_names)

    def compile(self, n: int) -> int:
        """Compile every stage program of a solve of ``n`` elements,
        COMPILE_WORKERS at a time; the solves then find them compiled.
        Returns the number of programs."""
        lowered = api.lower_stages(n, self.mesh, cfg=self.cfg,
                                   indirection=self.indirection)
        with ThreadPoolExecutor(COMPILE_WORKERS) as pool:
            return len(list(pool.map(lambda lw: lw[1].compile(), lowered)))

    def place(self, succ: np.ndarray, rank: np.ndarray):
        """The instance block-sharded over the PE mesh."""
        return (put_sharded(self.mesh, self.pe_axes, succ),
                put_sharded(self.mesh, self.pe_axes, rank))

    def new_recorder(self) -> SpanRecorder:
        return SpanRecorder()

    def call(self, placed, seed: int, recorder=None):
        """One timed solve: the front door until both outputs are ready
        on the device. Returns (outputs, counters)."""
        succ, rank = placed
        s_out, r_out, stats = rank_list_with_stats(
            succ, rank, self.mesh, cfg=self.cfg,
            indirection=self.indirection, seed=seed, tracer=recorder)
        jax.block_until_ready((s_out, r_out))
        return (s_out, r_out), {"attempts": stats["attempts"],
                                "rounds": stats["rounds"]}

    @staticmethod
    def to_host(outputs):
        return tuple(np.asarray(a) for a in outputs)

    @staticmethod
    def stage_spans(recorder) -> list[tuple[str, float, float, float]]:
        """[(stage label, t0, t1, device-synced wall)] of each stage
        attempt, in ``time.perf_counter`` seconds."""
        return [(sp.args["stage"], recorder.epoch + sp.t0,
                 recorder.epoch + sp.t1, sp.args["wall_s"])
                for sp in recorder.spans
                if sp.cat == "stage-attempt" and "wall_s" in sp.args]
