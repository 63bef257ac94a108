"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers: busy time as the union of op intervals, idle gaps, op
time per name, and the window the benchmark's own host annotations
span. ``TraceData`` holds only plain tuples, so every reduction can be
checked on a recorded trace or on made-up intervals.
"""
from __future__ import annotations

import bisect
import dataclasses
from pathlib import Path

#: the device planes' line with one event per executed HLO op (a
#: ``while`` op's event encloses the events of its body's ops) and the
#: line with one event per executed program
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"


def short_name(event_name: str) -> str:
    """An op's instruction name from its event name, which is the HLO
    text (``%fusion.17 = s32[...] fusion(...)`` -> ``fusion.17``), or a
    program's name without its fingerprint (``jit__post_body(1234)`` ->
    ``jit__post_body``)."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return head.split("(", 1)[0] if head.endswith(")") else head


@dataclasses.dataclass
class TraceData:
    #: device plane name -> [(op name, start s, end s)], by start
    devices: dict
    #: host annotations named with the benchmark's prefix:
    #: [(name, start s, end s)], by start
    annotations: list
    #: device plane name -> [(program name, start s, end s)], by start
    modules: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def from_dir(cls, path, prefix: str) -> "TraceData":
        files = sorted(Path(path).glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        return cls.from_file(files[-1], prefix)

    @classmethod
    def from_file(cls, path, prefix: str) -> "TraceData":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(str(path)), prefix)

    @classmethod
    def from_profile(cls, pd, prefix: str) -> "TraceData":
        devices, modules, annotations = {}, {}, []

        def events(plane, line_name):
            return sorted(((short_name(ev.name), ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
                           for line in plane.lines if line.name == line_name
                           for ev in line.events), key=lambda e: e[1])

        for plane in pd.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                ops = events(plane, OPS_LINE)
                if ops:
                    devices[plane.name] = ops
                    modules[plane.name] = events(plane, MODULES_LINE)
            elif plane.name == HOST_PLANE:
                annotations += [(ev.name, ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9)
                                for line in plane.lines
                                for ev in line.events
                                if ev.name.startswith(prefix)]
        annotations.sort(key=lambda e: e[1])
        return cls(devices=devices, annotations=annotations,
                   modules=modules)

    def busy_per_device(self) -> list[float]:
        """Seconds of the window in which each device ran some op."""
        lo, hi = self.window()
        return [busy_seconds(ops, lo, hi) for ops in self.devices.values()]

    def window(self) -> tuple[float, float]:
        """From the start of the first annotation to the end of the last."""
        if not self.annotations:
            raise ValueError("the trace holds no benchmark annotation")
        return (self.annotations[0][1],
                max(end for _, _, end in self.annotations))


def merged(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals clipped to [lo, hi], as
    disjoint intervals in order."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(ops, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which some op of ``ops`` ran."""
    return sum(e - s for s, e in merged(((s, e) for _, s, e in ops), lo, hi))


def idle_gaps(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """The intervals of [lo, hi] in which no op of ``ops`` ran."""
    gaps, t = [], lo
    for s, e in merged(((s, e) for _, s, e in ops), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    return gaps


def op_seconds(ops, lo: float, hi: float) -> dict[str, float]:
    """Seconds per op name, each event clipped to [lo, hi]."""
    out: dict[str, float] = {}
    for name, s, e in ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            out[name] = out.get(name, 0.0) + d
    return out


def self_seconds(ops, lo: float, hi: float) -> dict[str, float]:
    """Seconds per op name in which the op ran and none of the ops it
    encloses did (a ``while`` op's own time is its loop control), each
    event clipped to [lo, hi]. Events nest, as on one device line."""
    out: dict[str, float] = {}
    stack: list[tuple[str, float, float]] = []
    for name, s, e in sorted(((n, max(s, lo), min(e, hi))
                              for n, s, e in ops),
                             key=lambda ev: (ev[1], -ev[2])):
        if e <= s:
            continue
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            out[parent] = out.get(parent, 0.0) - (min(e, stack[-1][2]) - s)
        out[name] = out.get(name, 0.0) + (e - s)
        stack.append((name, s, e))
    return out


def in_programs(ops, programs) -> list[tuple[str, float, float]]:
    """``ops`` renamed ``<program>/<op>`` after the program event that
    holds each op's start (``?`` where none does)."""
    starts = [s for _, s, _ in programs]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = programs[i][0] if i >= 0 and s < programs[i][2] else "?"
        out.append((f"{prog}/{name}", s, e))
    return out


def op_family(name: str) -> str:
    """An HLO op's name without its instance number: ``fusion.12`` ->
    ``fusion``, ``all-to-all.3`` -> ``all-to-all``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name
