"""What every cell shares: discovery by name, the chip checks, spans, the
compile watch, the traced window and the result line.

Discovery: ``BENCHMARK.json`` names each cell's configuration and traffic
mix. A configuration is the file its entry names; a mix is
``bench/traffic/<traffic>.json``; a per-layer metric ``<name>`` is the
function ``read(record)`` in ``bench/layer_metrics/<name>.py``; the driver
of a configuration is ``bench/drivers/<kind>.py``, chosen by the
configuration's ``kind``. Adding a cell, configuration, mix or metric is
adding files and entries; nothing here changes.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]

# Environment overrides of the program that hide the device: one makes the
# routing see another device kind, the other forces the Pallas interpreter.
FAKE_DEVICE_ENVS = ("REPRO_FAKE_DEVICE_KIND", "REPRO_FORCE_INTERPRET")

# jax.monitoring events that mean a program was traced or compiled (or
# fetched from the persistent cache) -- none may fire inside a window.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


class Refused(RuntimeError):
    """The run cannot measure what the cell asks for on this machine."""


# -- discovery ---------------------------------------------------------------


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cell_named(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r}; known: "
                   f"{[c['name'] for c in bench['workloads']]}")


def config_for(bench: dict, cell: dict, root: Path = ROOT) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == cell["config"]:
            return json.loads((Path(root) / cfg["file"]).read_text())
    raise KeyError(f"no config {cell['config']!r}")


def traffic_for(cell: dict, root: Path = ROOT) -> dict:
    path = Path(root) / "bench" / "traffic" / f"{cell['traffic']}.json"
    return json.loads(path.read_text())


def limits_for(cell: dict, root: Path = ROOT) -> Dict[str, float]:
    """The limit of each number a cell's correctness check compares."""
    path = Path(root) / "bench" / "limits" / f"{cell['name']}.json"
    return json.loads(path.read_text())["limits"]


def metrics_of(bench: dict, cell: dict, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that apply to a cell:
    those listing it, and those with no ``workloads`` key."""
    return [m for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def jax_seed(seed: int, stream: int = 0) -> int:
    """A 31-bit seed for ``jax.random`` derived from a run seed of any
    size (the driver's seeds may pass 2**31) and a stream tag."""
    import numpy as np

    rng = np.random.default_rng([int(seed) % 2**64, 7, stream])
    return int(rng.integers(2**31 - 1))


def _load_file(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    path = Path(root) / "bench" / "layer_metrics" / f"{name}.py"
    return _load_file(path, f"bench_metric_{name}").read


def driver_for(config: dict, root: Path = ROOT):
    kind = config["kind"]
    return _load_file(Path(root) / "bench" / "drivers" / f"{kind}.py",
                      f"bench_driver_{kind}")


# -- the chip ----------------------------------------------------------------


def refuse_fake_devices(environ=os.environ) -> None:
    for env in FAKE_DEVICE_ENVS:
        if environ.get(env):
            raise Refused(f"{env} is set: it hides the device")


def require_tpu(chips: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")


def require_resolved(factor) -> None:
    """Refuse unless the factor's next mutation runs the compiled fused
    kernel: ``auto`` resolving to ``fused``/``mosaic``, not interpreted."""
    from repro.core import backends
    from repro.core.factor import resolve_backend_for

    name = resolve_backend_for(factor)
    lowering = backends.resolve_lowering(factor.lowering)
    interpret = (factor.interpret if factor.interpret is not None
                 else backends.default_interpret(lowering=lowering))
    if (name, lowering, interpret) != ("fused", "mosaic", False):
        raise Refused(f"auto resolved backend={name} lowering={lowering} "
                      f"interpret={interpret}; the benchmark measures "
                      "fused/mosaic compiled")


def enable_compile_cache() -> str:
    """The program's persistent compile cache, caching every program: a
    warm run then compiles nothing, however short its compiles are."""
    import jax
    from repro.runtime.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# -- spans and compiles --------------------------------------------------------


class Spans:
    """Host spans the benchmark puts around its calls into each layer:
    durations by name, and a ``TraceAnnotation`` in the profiler's trace."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds: Dict[str, List[float]] = collections.defaultdict(list)
        self.recording = False

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        if self.recording:
            dt = time.perf_counter() - t0
            with self._lock:
                self.seconds[name].append(dt)


_COMPILE_COUNT = collections.Counter()
_LISTENING: List[bool] = []


def _on_event(name, *args, **kw) -> None:
    if name in COMPILE_EVENTS:
        _COMPILE_COUNT[name] += 1


class CompileWatch:
    """Counts traces and compiles in the process from its creation on."""

    def __init__(self):
        import jax

        if not _LISTENING:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            _LISTENING.append(True)

    @staticmethod
    def count() -> int:
        return sum(_COMPILE_COUNT.values())


# -- the run -----------------------------------------------------------------


@dataclasses.dataclass
class Ctx:
    """One run of one cell. ``on_chip`` False is for tests on the CPU: the
    chip checks are skipped and ``interpret`` is passed to the program."""

    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool = False
    on_chip: bool = True
    interpret: Optional[bool] = None
    variant: Optional[str] = None    # "control": the lower-precision path
    keep_trace: Optional[str] = None
    limits: Dict[str, float] = dataclasses.field(default_factory=dict)
    peaks: Optional[dict] = None
    spans: Spans = dataclasses.field(default_factory=Spans)
    t_window: Optional[float] = None
    window_s: Optional[float] = None
    compiles_in_window: int = 0
    reduced_trace: Optional[dict] = None
    memory_peak_bytes: int = 0

    @contextlib.contextmanager
    def window(self, chips: int = 1):
        """The measured window: spans record, compiles are counted, and
        with ``trace`` the profiler records it. The peak memory is read at
        its close, before any reference check runs."""
        import jax

        watch = CompileWatch()
        before = watch.count()
        tmp = tempfile.mkdtemp(prefix="bench-trace-") if self.trace else None
        self.spans.recording = True
        self.t_window = time.perf_counter()
        try:
            with contextlib.ExitStack() as stack:
                if tmp is not None:
                    stack.enter_context(jax.profiler.trace(tmp))
                stack.enter_context(jax.profiler.TraceAnnotation(
                    "bench.window"))
                yield self
        finally:
            self.window_s = time.perf_counter() - self.t_window
            self.spans.recording = False
            self.compiles_in_window = watch.count() - before
        if self.on_chip:
            self.memory_peak_bytes = device_info(chips)["memory_peak_bytes"]
        if tmp is not None:
            self._reduce(tmp)

    def _reduce(self, tmp: str) -> None:
        from bench import tracereduce

        try:
            path = tracereduce.find_xplane(tmp)
            if path is not None:
                self.reduced_trace = tracereduce.reduce_trace(
                    tracereduce.load_trace(path))
                if self.keep_trace:
                    os.makedirs(self.keep_trace, exist_ok=True)
                    shutil.copy(path, Path(self.keep_trace) / path.name)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


@dataclasses.dataclass
class Outcome:
    """What a driver hands back: end-to-end values (without ``setup_s``),
    the record the per-layer readers read, and the correctness checks as
    ``name -> (value, limit)``; a check passes when value <= limit."""

    end_to_end: Dict[str, float]
    record: dict
    checks: Dict[str, tuple]
    attempted: int
    failed: int
    notes: Dict[str, object] = dataclasses.field(default_factory=dict)


def result_line(*, bench: dict, cell: dict, ctx: Ctx, outcome: Outcome,
                setup_s: float, device: dict, root: Path = ROOT) -> dict:
    """The last line of standard output. With ``--trace 1`` the metrics are
    the cell's per-layer metrics; a reader that finds nothing is left out."""
    correct = all(v <= lim for v, lim in outcome.checks.values())
    values = dict(outcome.end_to_end, setup_s=setup_s)
    metrics = {}
    if not ctx.trace:
        for m in metrics_of(bench, cell, "end_to_end"):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in metrics_of(bench, cell, "per_layer"):
            v = metric_reader(m["name"], root)(outcome.record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": int(outcome.attempted),
            "failed": int(outcome.failed), "metrics": metrics,
            "device": dict(device)}
    red = ctx.reduced_trace
    if ctx.trace and red is not None:
        line["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in outcome.checks.items()}
    return line


def run_cell(name: str, *, seed: int, seconds: float, trace: bool = False,
             keep_trace: Optional[str] = None, variant: Optional[str] = None,
             on_chip: bool = True, interpret: Optional[bool] = None,
             shrink: Optional[dict] = None, t_start: Optional[float] = None,
             root: Path = ROOT):
    """Run one cell end to end; returns ``(result line, outcome, ctx)``.

    ``bench/run.py`` calls this with the defaults. Tests on the CPU pass
    ``on_chip=False`` (no chip checks, no peaks), ``interpret=True`` and
    ``shrink``: ``{"config": {...}, "traffic": {...}}`` entries that
    replace sizes for a tiny run."""
    from bench import peaks

    t_start = time.perf_counter() if t_start is None else t_start
    bench = load_benchmark(root)
    cell = cell_named(bench, name)
    config = config_for(bench, cell, root)
    traffic = traffic_for(cell, root)
    if shrink:
        config.update(shrink.get("config", {}))
        traffic.update(shrink.get("traffic", {}))
    ctx = Ctx(config=config, traffic=traffic, limits=limits_for(cell, root),
              seed=seed, seconds=seconds, trace=trace, on_chip=on_chip,
              interpret=interpret, variant=variant, keep_trace=keep_trace)
    if on_chip:
        refuse_fake_devices()
        if not (Path(root) / "src" / "repro").is_dir():
            raise Refused("the program is not in this checkout")
        import jax

        require_tpu(cell["chips"])
        enable_compile_cache()
        ctx.peaks = peaks.peaks_for(jax.devices()[0].device_kind)
    outcome = driver_for(config, root).run(ctx)
    if on_chip:
        device = device_info(cell["chips"])
        device["memory_peak_bytes"] = ctx.memory_peak_bytes
    else:
        device = {"platform": "cpu", "kind": "cpu", "count": 1,
                  "memory_peak_bytes": 0}
    line = result_line(bench=bench, cell=cell, ctx=ctx, outcome=outcome,
                       setup_s=ctx.t_window - t_start, device=device,
                       root=root)
    return line, outcome, ctx


def print_checks(outcome: Outcome, stream=sys.stderr) -> None:
    """Notes first, then each number compared beside its limit: the last
    lines of standard error."""
    for name, value in outcome.notes.items():
        print(f"note {name} = {value!r}", file=stream, flush=True)
    for name, (value, limit) in outcome.checks.items():
        verdict = "ok" if value <= limit else "FAIL"
        print(f"check {name} = {value!r} limit {limit!r} {verdict}",
              file=stream, flush=True)
