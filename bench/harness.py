"""The benchmark's machinery, driven by ``BENCHMARK.json`` and the files
it names.

A cell (``<config>.<traffic>``) is read from ``configs/<config>.json``
and ``traffic/<traffic>.json``; the configuration names its front-door
adapter (``entries/<entry>.py``), its instance family
(``instances/<family>.py``) and its plain reference
(``references/<entry>.py``). Each metric is read by
``metrics/<name>.py``. Nothing here knows one cell from another, so a
cell, a traffic mix or a metric is added by adding files and entries.

``run_cell`` builds the cell (set-up), drives its closed loop for the
measured window and returns what it saw; ``check`` compares every
answer of the window with the reference; ``read_metrics`` reduces a run
to the metrics. The callers: ``run.py`` (the measurement, which needs
the chip), ``rehearse.py`` (CPU, tiny sizes, no device metric) and
``control.py`` (the controls in the program's place).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache: a fixed directory inside the
#: checkout (the path is part of the cache key), listed in .gitignore.
CACHE_DIR = ROOT / ".jax_cache"
#: its size bound. The chip machines default to 192 MiB, under what
#: one cell's stage programs take, so LRU eviction kept nothing.
CACHE_MAX_BYTES = 4 << 30
#: host annotation around each timed call in a traced run
CALL_ANNOTATION = "bench_call#"
#: all seeds handed to the program are non-negative int32
SEED_MASK = 0x7FFFFFFF

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark, imported by path."""
    path = BENCH / kind / f"{name}.py"
    mod_name = f"bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def elements_per_pe(self) -> int:
        return self.traffic["elements_per_pe"]


def load_cell(workload: str) -> Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    cfg_file = {c["name"]: c["file"] for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_file).read_text())
    traffic = load_json("traffic", w["traffic"])
    if traffic["loop"] != "closed" or traffic["clients"] != 1:
        raise ValueError(f"{workload}: the harness drives a closed loop "
                         f"with one caller")
    if traffic["elements_per_pe"] > config["elements_per_pe"]:
        raise ValueError(f"{workload}: traffic asks for more elements per "
                         f"PE than the configuration holds")
    return Cell(name=workload, chips=w["chips"], config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if applies(m, workload)],
                per_layer=[m for m in spec["per_layer"]
                           if applies(m, workload)])


def derive_seed(seed: int, *path: int) -> int:
    """A non-negative int32 drawn from ``seed`` (any whole number) and
    a path of small integers: one per instance, solve and purpose."""
    ss = np.random.SeedSequence([seed % (1 << 64), *path])
    return int(ss.generate_state(1, np.uint32)[0]) & SEED_MASK


class CompileCounter:
    """Counts JAX's compile and persistent-cache events in this process
    (one listener, registered on first use)."""

    _instance = None

    def __init__(self):
        from jax import monitoring
        self.counts: dict[str, int] = {}
        monitoring.register_event_duration_secs_listener(
            lambda name, _secs, **_kw: self._add(name))
        monitoring.register_event_listener(
            lambda name, **_kw: self._add(name))

    def _add(self, name: str) -> None:
        self.counts[name] = self.counts.get(name, 0) + 1

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def snapshot(self) -> dict:
        """Programs asked of the compiler (``programs``), how many of
        them the persistent cache held (``from_cache``), the rest
        (``compiled``), and jaxpr traces (``traced``)."""
        c = self.counts
        programs = c.get("/jax/core/compile/backend_compile_duration", 0)
        hits = c.get("/jax/compilation_cache/cache_hits", 0)
        return {"programs": programs, "from_cache": hits,
                "compiled": programs - hits,
                "traced": c.get("/jax/core/compile/jaxpr_trace_duration", 0)}

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: after[k] - before[k] for k in after}


def configure_compile_cache() -> None:
    """Keep JAX's persistent cache at CACHE_DIR with CACHE_MAX_BYTES,
    whatever the environment says, and write every program to it."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class GcClock:
    """Time spent in Python's garbage collector, per generation, while
    installed (a diagnostic of host stalls in the window)."""

    def __init__(self):
        self.seconds = [0.0, 0.0, 0.0]
        self.count = [0, 0, 0]
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            g = info["generation"]
            self.seconds[g] += time.perf_counter() - self._t0
            self.count[g] += 1

    def __enter__(self) -> "GcClock":
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)


@dataclasses.dataclass
class Call:
    index: int
    instance: int
    seed: int
    t_call: float                  #: perf_counter at the front-door call
    t_ret: float                   #: after both outputs are ready
    answer: tuple | None = None    #: host copy of the outputs
    error: str | None = None       #: the exception a failed solve raised
    counters: dict | None = None
    spans: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Run:
    cell: Cell
    n: int
    calls: list
    setup_s: float
    setup_compiles: dict
    window_compiles: dict
    t_first: float
    t_last: float
    peak_bytes: int | None = None
    trace: object | None = None    #: trace_reduce.TraceData of the window
    instances: list = dataclasses.field(default_factory=list)
    gc: GcClock | None = None

    @property
    def done(self) -> list:
        return [c for c in self.calls if c.error is None]


def timed_call(entry, placed, call: Call, trace: bool) -> None:
    """Make ``call`` through ``entry`` on the placed instance, timed from
    the front-door call until both outputs are ready; then copy the
    answer to the host. A traced call records the program's spans and
    sits in a host annotation of the profiler's trace."""
    import jax

    recorder = entry.new_recorder() if trace else None
    ann = (jax.profiler.TraceAnnotation(f"{CALL_ANNOTATION}{call.index}")
           if trace else contextlib.nullcontext())
    call.t_call = time.perf_counter()
    try:
        with ann:
            out, call.counters = entry.call(placed, call.seed, recorder)
    except Exception as e:  # a failed solve counts; the loop goes on
        call.t_ret = time.perf_counter()
        call.error = f"{type(e).__name__}: {e}"[:500]
    else:
        call.t_ret = time.perf_counter()
        call.answer = entry.to_host(out)
    if recorder is not None:
        call.spans = entry.stage_spans(recorder)


def run_cell(cell: Cell, *, seed: int, seconds: float, devices, t_start,
             trace: bool = False, elements_per_pe: int | None = None,
             pool: int | None = None, entry=None) -> Run:
    """Set up the cell on ``devices``, then drive its closed loop for
    ``seconds``. ``elements_per_pe`` and ``pool`` override the traffic's
    (rehearsals at tiny sizes); ``entry`` replaces the configuration's
    front door (the controls)."""
    import jax

    counter = CompileCounter.get()
    before = counter.snapshot()
    m = elements_per_pe or cell.elements_per_pe
    n = m * cell.chips
    pool = pool or cell.traffic["pool"]
    family = load_module("instances", cell.config["instances"])
    if entry is None:
        entry = load_module("entries", cell.config["entry"]).Entry(
            devices, cell.config)

    instances = [family.make(n, cell.config, derive_seed(seed, 0, k))
                 for k in range(pool)]
    placed = [entry.place(*inst) for inst in instances]
    entry.compile(n)
    recorder = entry.new_recorder() if trace else None
    # warm-up: one solve on the first instance, with a seed of its own;
    # a failure here shows again, and counts, in the window
    try:
        entry.call(placed[0], derive_seed(seed, 2, 0), recorder)
    except Exception as e:
        print(f"warm-up solve failed: {type(e).__name__}: {e}"[:500],
              file=sys.stderr, flush=True)
    jax.block_until_ready(placed)
    mid = counter.snapshot()
    # set-up's objects (traced and compiled programs, the pool) leave
    # the collector's view, as a long-running caller's start-up objects
    # would: collections in the window then walk only the window's own
    gc.collect()
    gc.freeze()

    trace_dir = None
    if trace:
        trace_dir = tempfile.TemporaryDirectory(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    calls: list[Call] = []
    with GcClock() as gc_clock:
        t_first = time.perf_counter()
        while not calls or time.perf_counter() - t_first < seconds:
            k = len(calls)
            call = Call(index=k, instance=k % pool,
                        seed=derive_seed(seed, 1, k), t_call=0.0, t_ret=0.0)
            timed_call(entry, placed[call.instance], call, trace)
            calls.append(call)
    t_last = calls[-1].t_ret
    run = Run(cell=cell, n=n, calls=calls, setup_s=setup_s,
              setup_compiles=CompileCounter.delta(mid, before),
              window_compiles=CompileCounter.delta(counter.snapshot(), mid),
              t_first=t_first, t_last=t_last, gc=gc_clock)
    if trace:
        jax.profiler.stop_trace()
    stats = [d.memory_stats() for d in devices]
    if all(s and "peak_bytes_in_use" in s for s in stats):
        run.peak_bytes = max(s["peak_bytes_in_use"] for s in stats)
    del placed, entry
    if trace:
        from trace_reduce import TraceData
        run.trace = TraceData.from_dir(trace_dir.name, CALL_ANNOTATION)
        trace_dir.cleanup()
    run.instances = instances
    return run


def check(cell: Cell, run: Run) -> tuple[bool, dict]:
    """Compare every answer of the window with the plain reference.
    Returns (correct, {number: {"value", "limit"}}): each number is a
    count of wrong or missing answers, and each limit is 0."""
    ref = load_module("references", cell.config["entry"])
    totals = dict.fromkeys(ref.NUMBERS, 0)
    answers: dict[int, tuple] = {}
    for c in run.done:
        if c.instance not in answers:
            answers[c.instance] = ref.reference(*run.instances[c.instance])
        for k, v in ref.compare(answers[c.instance], c.answer).items():
            totals[k] = totals.get(k, 0) + v
    totals["failed_solves"] = len(run.calls) - len(run.done)
    compared = {k: {"value": v, "limit": 0} for k, v in totals.items()}
    return all(v == 0 for v in totals.values()), compared


def metric_reader(name: str):
    """The reader of metric ``name``: ``metrics/<name>.py``, or for a
    name ``<base>.<group>`` without a file of its own (one quantity
    split by the cells that report it), ``metrics/<base>.py``."""
    if not (BENCH / "metrics" / f"{name}.py").exists():
        name = name.split(".", 1)[0]
    return load_module("metrics", name)


def read_metrics(specs: list, run: Run) -> dict:
    """{name: {"value", "unit"}} of each metric in ``specs`` whose
    reader finds something to read in ``run``."""
    out = {}
    for spec in specs:
        value = metric_reader(spec["name"]).read(run)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def mean_per_call(run: Run, fn):
    """The mean of ``fn(call)`` over the completed calls that recorded
    the program's stage spans (traced runs), or None."""
    calls = [c for c in run.done if c.spans]
    return sum(fn(c) for c in calls) / len(calls) if calls else None


def stage_wall(run: Run, pred):
    """Mean per solve of the device-synced walls of the stage attempts
    whose label satisfies ``pred``; None where no attempt does."""
    if not any(pred(s[0]) for c in run.done for s in c.spans):
        return None
    return mean_per_call(run, lambda c: sum(
        wall for label, _, _, wall in c.spans if pred(label)))


def host_activity(call: Call | None, t: float) -> str:
    """What the host was doing at perf_counter time ``t``."""
    if call is None:
        return "harness, between calls"
    spans = call.spans
    if not spans or t < spans[0][1]:
        return "front door, before the first stage"
    for (label, t0, t1, _), nxt in zip(spans, spans[1:] + [None]):
        if t < t1:
            return f"stage {label}"
        if nxt is not None and t < nxt[1]:
            return f"driver, after {label}"
    return "front door, after the last stage"


def breakdown(run: Run, top: int = 10) -> dict:
    """The traced run's ``breakdown``: the device ops with the most self
    time (``<program>/<op>``, mean over the devices), and the first
    device's longest idle gaps, each named by what the host was doing in
    its middle."""
    import statistics

    from trace_reduce import idle_gaps, in_programs, self_seconds
    tr = run.trace
    if not tr.devices:
        return {"device_ops": [], "idle_gaps": []}
    lo, hi = tr.window()
    ops: dict[str, float] = {}
    for dev, dev_ops in tr.devices.items():
        named = in_programs(dev_ops, tr.modules.get(dev, []))
        for name, sec in self_seconds(named, lo, hi).items():
            ops[name] = ops.get(name, 0.0) + sec / len(tr.devices)
    device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    # profiler clock = perf_counter + offset, from the call annotations
    offset = statistics.median(
        t0 - run.calls[int(name[len(CALL_ANNOTATION):])].t_call
        for name, t0, _ in tr.annotations)
    first = next(iter(tr.devices.values()))
    gaps = sorted(idle_gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:top]
    named_gaps = []
    for s, e in gaps:
        t = (s + e) / 2 - offset
        call = next((c for c in run.calls if c.t_call <= t <= c.t_ret), None)
        named_gaps.append([host_activity(call, t), e - s])
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": named_gaps}
