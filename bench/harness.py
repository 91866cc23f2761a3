"""One run of one cell: find its files by name, check the chip, set up,
measure a window, check the window's outputs against the plain
reference, and print the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

    bench/configs/<config>.json     one deployment (sizes, source, cuts)
    bench/traffic/<traffic>.json    one traffic mix; its ``driver`` names
                                    the loop in bench/drivers/ that reads it
    bench/limits/<workload>.json    the limit of each number compared
    bench/metrics/<metric>.py       one reader per per-layer metric:
                                    ``read(layer) -> float | None``

A driver's ``run(ctx)`` builds the cell from the seed, calls
``ctx.begin_window()`` / ``ctx.end_window()`` around the measured work,
``ctx.read_memory()`` before it frees the program's state, then runs the
reference and returns an ``Outcome``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import shutil
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """The run cannot measure: no TPU, or fewer chips than the cell asks."""


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load_cell(workload: str) -> dict:
    """The cell's BENCHMARK.json entries and files, found by name."""
    spec = load_json(ROOT / "BENCHMARK.json")
    wl = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    return {
        "spec": spec,
        "workload": wl,
        "config": load_json(ROOT / cfg_entry["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{wl['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{workload}.json"),
    }


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chips(n: int) -> dict:
    """The device description, or ``NoChip``: a run never falls back to
    the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's default device is {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips; JAX sees {len(devs)}")
    return describe_devices()


def describe_devices() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events.  JAX times every executable it builds under one event,
    loading it from the persistent cache included, so the compiles are
    those events less the cache hits."""

    def __init__(self):
        import jax
        self.built = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @property
    def compiles(self) -> int:
        return self.built - self.hits

    def _duration(self, event: str, duration_s: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def setup_jax_cache() -> str:
    """The persistent compilation cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), holding every program however
    fast it compiled, so that only a checkout's first run compiles."""
    import jax
    from repro.launch.compile_cache import setup_compile_cache
    where = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


@dataclasses.dataclass
class Outcome:
    metrics: dict             # end-to-end metric -> value
    attempted: int
    failed: int
    checks: dict              # number compared -> value
    layer: dict               # raw quantities the per-layer readers use


class Ctx:
    """What a driver gets: the cell, the run's arguments, and the
    window's bookkeeping."""

    def __init__(self, cell: dict, *, seed: int, seconds: float,
                 trace: bool, t_start: float, counter: CompileCounter,
                 scratch: pathlib.Path, control: bool = False):
        self.cell = cell
        self.cfg = cell["config"]
        self.mix = cell["traffic"]
        self.workload = cell["workload"]
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.control = control
        self.counter = counter
        self.scratch = scratch
        self.t_start = t_start
        self.setup_s = None
        self.t0 = self.t1 = None
        self.memory_peak = None
        self.window_compiles = self.window_hits = None
        self._trace_dir = None
        self._window_span = None

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def begin_window(self) -> None:
        import jax
        self.t0 = time.perf_counter()
        self.setup_s = self.t0 - self.t_start
        self._c0, self._h0 = self.counter.compiles, self.counter.hits
        if self.trace:
            self._trace_dir = self.scratch / "trace"
            jax.profiler.start_trace(str(self._trace_dir))
            self._window_span = jax.profiler.TraceAnnotation("bench.window")
            self._window_span.__enter__()
            self.t0 = time.perf_counter()

    def end_window(self) -> float:
        """Close the window; returns its wall seconds."""
        import jax
        self.t1 = time.perf_counter()
        if self.trace:
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.window_compiles = self.counter.compiles - self._c0
        self.window_hits = self.counter.hits - self._h0
        return self.t1 - self.t0

    def read_memory(self) -> None:
        import jax
        peaks = []
        for d in jax.devices()[:self.chips]:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak = max(peaks)

    def trace_file(self):
        if self._trace_dir is None:
            return None
        from bench import xtrace
        return xtrace.find_xplane(str(self._trace_dir))


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def judge(checks: dict, limits: dict) -> tuple[bool, dict]:
    """Each number against its limit (a number at or under its limit
    passes); a number missing or not finite fails."""
    import math
    out, ok = {}, True
    for name, limit in limits.items():
        value = checks.get(name)
        good = (value is not None and math.isfinite(value)
                and value <= limit)
        ok = ok and good
        out[name] = {"value": value, "limit": limit}
    return ok, out


@dataclasses.dataclass
class Layer:
    """What a per-layer reader sees."""
    cfg: dict
    mix: dict
    workload: dict
    peak: dict
    chips: int
    quantities: dict
    trace: object = None       # xtrace.Trace, or None
    lo: float = 0.0            # the window on the trace's clock
    hi: float = 0.0


def per_layer_metrics(cell: dict, layer: Layer) -> dict:
    """Run the reader of every per-layer metric this cell lists; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    name = cell["workload"]["name"]
    reports = {m["name"] for m in cell["spec"]["end_to_end"]
               if name in m.get("workloads", [name])}
    for m in cell["spec"]["per_layer"]:
        listed = m.get("workloads")
        if listed is not None and name not in listed:
            continue
        if listed is None and m["moves"] not in reports:
            continue
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(layer)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, need_chip: bool = True,
        cell: dict | None = None) -> tuple[dict, list]:
    """One run; returns (the result line as a dict, stderr check lines).
    ``need_chip=False`` skips the look for a chip and ``cell`` replaces
    the cell's files (tests only)."""
    cell = cell or load_cell(workload)
    device = (require_chips(int(cell["workload"]["chips"])) if need_chip
              else describe_devices())
    setup_jax_cache()
    counter = CompileCounter()
    scratch = pathlib.Path(tempfile.mkdtemp(prefix="bench-"))
    try:
        ctx = Ctx(cell, seed=seed, seconds=seconds, trace=trace,
                  t_start=t_start, counter=counter, scratch=scratch)
        driver = load_module(BENCH / "drivers" /
                             f"{cell['traffic']['driver']}.py")
        out = driver.run(ctx)
        correct, checks = judge(out.checks, cell["limits"])
        notes = [f"compiles in window: {ctx.window_compiles}; "
                 f"persistent-cache hits in window: {ctx.window_hits}"]
        device = dict(device, memory_peak_bytes=ctx.memory_peak)
        result = {"correct": correct, "attempted": out.attempted,
                  "failed": out.failed}
        if trace:
            result["metrics"], extra = _traced(ctx, cell, out, device)
            device.update(extra.pop("device"))
            result["device"] = device
            result.update(extra)
        else:
            metrics = dict(out.metrics, setup_s=ctx.setup_s)
            units = {m["name"]: m["unit"] for m in cell["spec"]["end_to_end"]}
            result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                                 for k, v in metrics.items()}
            result["device"] = device
        result["checks"] = checks
        return result, notes + [
            f"check {k}: {v['value']!r} (limit {v['limit']!r})"
            for k, v in checks.items()]
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


def _traced(ctx: Ctx, cell: dict, out: Outcome, device: dict):
    from bench import xtrace
    tr = xtrace.load(ctx.trace_file())
    lo, hi = xtrace.window(tr)
    trace = xtrace.Trace(tr.devices[:ctx.chips], tr.spans)
    if not trace.devices:
        raise RuntimeError("the trace holds no TPU device plane")
    busy = xtrace.mean_busy_s(trace, lo, hi)
    layer = Layer(cfg=ctx.cfg, mix=ctx.mix, workload=ctx.workload,
                  peak=peak_for(device["kind"]), chips=ctx.chips,
                  quantities=out.layer,
                  trace=trace, lo=lo, hi=hi)
    metrics = per_layer_metrics(cell, layer)
    extra = {"device": {"busy_s": busy, "window_s": hi - lo},
             "breakdown": {
                 "device_ops": xtrace.top_ops(layer.trace, lo, hi),
                 "idle_gaps": xtrace.idle_gaps(layer.trace, lo, hi)}}
    return metrics, extra
