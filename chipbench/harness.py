"""The harness: run one cell of ``BENCHMARK.json`` once.

``run_cell`` finds everything by name.  The cell's entry names its
configuration (``configs/<config>.json``) and traffic mix
(``traffic/<traffic>.json``); the traffic names its window driver
(``drivers/<driver>.py``) and its work count (``counts/<count>.py``);
``limits/<cell>.json`` holds the limit of every number the check
compares; each metric is read by ``e2e/<metric>.py`` or
``layers/<metric>.py``.  A later cell, configuration or metric is new
files and new entries, never an edit here.

A driver module has four functions:

  setup(ctx)          make the data from the seed, build the program's
                      objects, run every shape the window will use once;
  window(ctx)         drive the program while ``ctx.window_open()``,
                      calling ``ctx.unit(t0, **counts)`` after each unit
                      of work (a unit that started ends inside the window);
  release(ctx)        drop the program's state once the window has closed;
  check(ctx, control) compare what the window produced with the plain
                      reference; ``control=True`` puts the reference
                      computed in bfloat16 in the program's place.
                      Returns {name: number}.

The run measures with the profiler off (``trace=False``) or traces the
whole window (``trace=True``); the metrics are the cell's end-to-end
metrics or its per-layer metrics accordingly.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = ROOT / "chipbench"


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def read_json(path: pathlib.Path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_module(path: pathlib.Path):
    """Import a file by path (metric files carry dots in their names)."""
    name = "chipbench_" + "_".join(path.relative_to(HERE).with_suffix("").parts)
    name = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _merge(base: dict, over: Optional[dict]) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def cell_spec(workload: str, bench: Optional[dict] = None) -> dict:
    """The cell's entry with its configuration, traffic and limits."""
    bench = bench or read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {"bench": bench, "cell": cell,
            "config": read_json(ROOT / conf["file"]),
            "traffic": read_json(HERE / "traffic" / f"{cell['traffic']}.json"),
            "limits": read_json(HERE / "limits" / f"{workload}.json")}


def metrics_for(bench: dict, workload: str, trace: bool) -> list:
    """The cell's metrics: end-to-end untraced, per-layer traced."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def configure_jax():
    """Persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR`` or
    at a fixed path in the checkout, holding every program, however fast
    it compiled, so that only a checkout's first run compiles."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


_CLOCK = None


def compile_clock(jax):
    """One listener per process, however many runs it makes."""
    global _CLOCK
    if _CLOCK is None:
        from chipbench.clock import CompileClock
        _CLOCK = CompileClock(jax)
    return _CLOCK


def _counters() -> dict:
    from repro.obs.metrics import default_registry
    return dict(default_registry().snapshot()["counters"])


class Ctx:
    """What a driver sees of the run, and what the readers read."""

    def __init__(self, spec, seed, seconds, trace, device):
        from chipbench.refs.dgp import seed_key
        self.cell, self.config = spec["cell"], spec["config"]
        self.traffic, self.limits = spec["traffic"], spec["limits"]
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.device = device
        self.key = seed_key(seed)
        self.state: dict = {}
        self.out: dict = {}
        self.spans: list = []
        self.units: list = []
        self.attempted = 0
        self.failed = 0
        self.window_start = self.window_end = 0.0
        self.setup_s = 0.0
        self.device_trace: Optional[dict] = None
        self.work: Optional[dict] = None
        self.peaks: Optional[dict] = None

    @contextlib.contextmanager
    def span(self, name: str):
        """A harness span around a call into one layer; in a traced run
        also a ``bench.<name>`` mark on the profiler's clock."""
        mark = contextlib.nullcontext()
        if self.trace:
            import jax
            mark = jax.profiler.TraceAnnotation(f"bench.{name}")
        with mark:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def window_open(self) -> bool:
        return time.perf_counter() < self.window_start + self.seconds

    def unit(self, t0: float, **counts):
        """One unit of work that started at ``t0`` has completed."""
        self.units.append({"t0": t0, "t1": time.perf_counter(), **counts})

    def span_seconds(self, name: str) -> list:
        return [t1 - t0 for n, t0, t1 in self.spans if n == name]


def _memory_peak(dev) -> Optional[int]:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else int(peak)


def _trace_window(ctx, driver, jax):
    """Run the window under the profiler and reduce its trace."""
    from chipbench import trace_reduce
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tmp = tempfile.mkdtemp(prefix="chipbench_trace_")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                ctx.window_start = time.perf_counter()
                driver.window(ctx)
                ctx.window_end = time.perf_counter()
        finally:
            jax.profiler.stop_trace()
        files = sorted(pathlib.Path(tmp).rglob("*.xplane.pb"))
        if files:
            ctx.device_trace = trace_reduce.reduce(trace_reduce.load(str(files[-1])))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _health(c0: dict, c1: dict) -> dict:
    """Fallbacks, retries, downgrades and failed probes over the run:
    each must be 0."""
    def delta(prefix):
        return sum(v - c0.get(k, 0) for k, v in c1.items() if k.startswith(prefix))
    return {"seg_gram_fallbacks": delta("seg_gram.fallback"),
            "runtime_retries": delta("runtime.events.retry"),
            "runtime_downgrades": delta("runtime.events.downgrade"),
            "probe_failures": delta("runtime.probe_failed")}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, overrides: Optional[dict] = None,
             control: bool = False, t_start: Optional[float] = None,
             log=sys.stderr) -> dict:
    """Run one cell once; returns the result line as a dict (plus
    ``"control"`` readings when ``control``).  Raises ``NoChip`` when
    ``require_chip`` and JAX finds no TPU or too few chips."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = cell_spec(workload)
    spec["config"] = _merge(spec["config"], (overrides or {}).get("config"))
    spec["traffic"] = _merge(spec["traffic"], (overrides or {}).get("traffic"))
    jax = configure_jax()
    devs = jax.devices()
    chips = int(spec["cell"]["chips"])
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell {workload!r} needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from chipbench.peaks import peaks_for

    dev = devs[0]
    ctx = Ctx(spec, seed, seconds, trace, dev)
    traffic = ctx.traffic
    driver = load_module(HERE / "drivers" / f"{traffic['driver']}.py")
    ctx.work = load_module(HERE / "counts" / f"{traffic['count']}.py").work(
        ctx.config, traffic) if traffic.get("count") else None
    ctx.peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    clock = compile_clock(jax)
    c0 = _counters()

    driver.setup(ctx)
    gc.collect()
    gc.freeze()  # set-up's objects stay out of the window's collections
    ctx.setup_s = time.perf_counter() - t_start
    mark, cw0 = clock.mark(), _counters()
    if trace:
        _trace_window(ctx, driver, jax)
    else:
        ctx.window_start = time.perf_counter()
        driver.window(ctx)
        ctx.window_end = time.perf_counter()
    compiled = clock.since(mark)
    cw1 = _counters()
    peak = _memory_peak(dev)

    metrics = {}
    for m in metrics_for(spec["bench"], workload, trace):
        if m["source"] == "device_trace" and dev.platform != "tpu":
            continue  # a device number comes from the chip or not at all
        sub = "layers" if trace else "e2e"
        value = load_module(HERE / sub / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    lowering = {k: v - cw0.get(k, 0) for k, v in cw1.items()
                if k.startswith("seg_gram.lowering") and v - cw0.get(k, 0)}
    print(f"chipbench {workload}: seed={seed} setup_s={ctx.setup_s:.3f} "
          f"window_s={ctx.window_end - ctx.window_start:.3f} "
          f"units={len(ctx.units)} attempted={ctx.attempted} "
          f"failed={ctx.failed}", file=log)
    print(f"chipbench {workload}: inside the window: "
          f"backend_compiles={compiled['compiles']} "
          f"cache_hits={compiled['cache_hits']} "
          f"cache_misses={compiled['cache_misses']} "
          f"compile_s={compiled['compile_s']:.3f} lowerings_traced={lowering}",
          file=log)

    driver.release(ctx)
    compared = dict(driver.check(ctx, control=False))
    compared.update(_health(c0, _counters()))
    limits = ctx.limits
    missing = sorted(set(compared) - set(limits))
    if missing:
        raise KeyError(f"limits/{workload}.json has no limit for {missing}")
    correct = all(compared[k] <= limits[k] for k in compared)
    result = {"correct": bool(correct), "attempted": int(ctx.attempted),
              "failed": int(ctx.failed), "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs), "memory_peak_bytes": peak}}
    if trace and ctx.device_trace:
        result["device"]["busy_s"] = ctx.device_trace["busy_s"]
        result["device"]["window_s"] = ctx.device_trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.device_trace["top_ops"],
                               "idle_gaps": ctx.device_trace["idle_gaps"]}
    result["compared"] = {k: {"value": float(v), "limit": float(limits[k])}
                          for k, v in compared.items()}
    if control:
        result["control"] = {k: float(v) for k, v in
                             driver.check(ctx, control=True).items()}
    for k, v in result["compared"].items():
        verdict = "ok" if v["value"] <= v["limit"] else "OVER"
        print(f"compared {k} = {v['value']!r} limit {v['limit']!r} {verdict}",
              file=log)
    return result
