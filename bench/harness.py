"""Runs one cell: set-up, the measured window, the comparison, the
metrics. ``run.py`` is its command line.

A cell is found by name: its ``workloads`` entry in ``BENCHMARK.json``
names a configuration (``configs/<file>``) and a traffic mix
(``traffic/<name>.json``); those name the system, reference and driver
modules; every metric is read by ``metrics/<metric>.py``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
SETUP = "setup_s"


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def keep_logs_in_tmpdir() -> None:
    """Point the TPU runtime's logs (``/tmp/tpu_logs`` by default) into
    ``TMPDIR``, unless ``TPU_LOG_DIR`` says otherwise. Call before JAX
    loads the runtime."""
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold '.' or
    '-', so it is loaded by path)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _reports(metric: dict, cell: str, e2e_in_cell: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # a per-layer metric with no list is reported wherever its
    # end-to-end metric is
    return "moves" not in metric or metric["moves"] in e2e_in_cell


def resolve(bench: dict, workload: str, root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``bench`` with its files loaded."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = _load_json(os.path.join(root, cfgs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload, [])]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, names)]
    return Cell(workload, w["chips"], cfg, traffic, e2e, per_layer)


def check_device(chips: int):
    """The devices the cell runs on; :class:`NoDevice` where JAX finds no
    accelerator or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoDevice("JAX found no accelerator (platform cpu)")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path: where
    ``JAX_COMPILATION_CACHE_DIR`` points, else ``<checkout>/.jax_cache``.
    Every program is kept, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CacheEvents:
    """Counts the persistent cache's hits and misses in this process."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@dataclass
class Context:
    """What a metric reader reads."""
    cell: Cell
    window: Any                    # bench.window.Window
    trace: Any                     # bench.tracing.Reduced, traced runs
    device_kind: str
    chips: int


def _peak_memory(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _traced(fn: Callable, log_dir: str):
    """``fn(span)`` under the profiler: the device only, no host or
    Python tracer (see ``bench.tracing``). Returns fn's result and the
    host spans, clock markers and ``bench.window`` among them."""
    import jax
    import jax.numpy as jnp

    from bench.tracing import CLOCK_SPAN, WINDOW_SPAN, bench_clock
    from bench.window import SpanLog

    log = SpanLog()
    mark = jax.jit(bench_clock)
    x = jnp.ones((8, 128), jnp.float32)
    mark(x).block_until_ready()            # compiled outside the trace

    def markers():
        for _ in range(2):
            with log(CLOCK_SPAN):
                mark(x).block_until_ready()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        markers()
        with log(WINDOW_SPAN):
            out = fn(log)
        markers()
    finally:
        jax.profiler.stop_trace()
    return out, log.spans


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, *, devices=None,
        wrap_system: Optional[Callable] = None,
        control: bool = False) -> Dict[str, Any]:
    """Run ``cell`` once and return its result line (a dict).

    ``devices`` skips the look for a chip (a test passes the CPU's);
    ``wrap_system`` puts a wrapper around the system under test (a test
    plants faults with it). ``control`` also reads the control: the
    reference one precision step down, in the program's place, on the
    same images (``bench/readings.py``; the benchmark's runs skip it).
    Set-up runs from ``t_start`` (the process's start) until the window
    opens.
    """
    import jax

    from bench import correct, tracing
    from bench.weights import make_weights

    devs = devices if devices is not None else check_device(cell.chips)
    devs = devs[:cell.chips]
    say(f"cache dir {enable_compile_cache()}")
    cache = CacheEvents()
    cfg, traffic = cell.cfg, cell.traffic
    system_mod = load_module("systems", cfg["system"])
    reference = load_module("references", cfg["reference"])
    driver_mod = load_module("drivers", traffic["driver"])

    t0 = time.perf_counter()
    weights = make_weights(cfg, seed, system_mod.weight_sharding(cfg))
    jax.block_until_ready(weights)
    t1 = time.perf_counter()
    system = system_mod.System(cfg, traffic["batch_per_chip"], weights)
    if wrap_system is not None:
        system = wrap_system(system)
    t2 = time.perf_counter()
    driver = driver_mod.Driver(system, cfg, traffic, seed)
    t3 = time.perf_counter()
    driver.warm()
    t4 = time.perf_counter()
    setup_s = t4 - t_start
    say(f"setup {setup_s!r} s: weights {t1 - t0!r}, compile {t2 - t1!r}, "
        f"inputs {t3 - t2!r}, warm-up {t4 - t3!r}; persistent cache "
        f"{cache.hits} hits, {cache.misses} misses")
    misses_before = cache.misses

    reduced = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            window, spans = _traced(lambda span: driver.run(seconds, span),
                                    log_dir)
            t5 = time.perf_counter()
            reduced = tracing.load(tracing.find_xplane(log_dir), spans)
            say(f"trace read in {time.perf_counter() - t5!r} s; host spans "
                f"placed to +-{reduced.clock_err_ns * 1e-6!r} ms")
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    else:
        window = driver.run(seconds)
    if cache.misses != misses_before:
        say(f"WARNING: {cache.misses - misses_before} compile(s) inside "
            f"the window")
    memory_peak = _peak_memory(devs)
    say(f"window {window.seconds!r} s, {window.images} images, "
        f"{window.batches} batches, attempted {window.attempted}, failed "
        f"{window.failed}; " + ", ".join(f"{k} {v!r}"
                                         for k, v in window.notes.items()))

    # the program's state goes before the reference runs
    system.close()
    del system
    t6 = time.perf_counter()
    images = driver.images(window.check_index)
    want = reference.logits(cfg, weights, images)
    numbers = {"logit_rel_err": correct.logit_rel_err(window.check_logits,
                                                      want)}
    ok, compared = correct.judge(numbers, cfg["limits"])
    say(f"reference over {len(images)} images in "
        f"{time.perf_counter() - t6!r} s")
    control_numbers = None
    if control:
        control_numbers = {
            passes: correct.logit_rel_err(
                reference.logits(cfg, weights, images, passes=passes), want)
            for passes in reference.CONTROLS}

    kind = devs[0].device_kind
    ctx = Context(cell, window, reduced, kind, len(devs))
    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        value = (setup_s if m["name"] == SETUP
                 else load_module("metrics", m["name"]).read(ctx))
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result: Dict[str, Any] = {
        "correct": ok, "attempted": window.attempted,
        "failed": window.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = tracing.busy_s(reduced)
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": tracing.top_ops(reduced),
                               "idle_gaps": tracing.idle_by_host(reduced)}
    if control_numbers is not None:
        result["control"] = control_numbers
    result["compared"] = compared
    for line in correct.lines(compared):
        print(line, file=sys.stderr, flush=True)
    return result
