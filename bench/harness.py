"""One run of one cell: set-up, a measured window, metrics, the check.

Everything that belongs to one configuration, traffic mix or metric is
found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: sizes, source, limits, and the name
  of the generator module ``bench/generators/<generator>.py``;
* ``bench/traffic/<traffic>.json``: the name of its loop and the
  loop's parameters.  ``refill`` and ``new`` are built into
  :mod:`bench.loops`; any other loop is the ``LOOP`` class of
  ``bench/traffic/<loop>.py`` (:func:`load_loop`);
* ``bench/metrics/<metric>.py``: a reader ``read(ctx)`` that returns
  the metric's value, or ``None`` where it finds nothing to read.  A
  run of a cell that declares the metric and reads ``None`` fails with
  the names the reader matches, and prints no result.

A run with ``--trace 0`` reports the cell's end-to-end metrics; one
with ``--trace 1`` traces the same window and reports its per-layer
metrics, the device's busy and window seconds and a breakdown.  Both
read every chip the cell asks for: the peak memory of each, and in the
trace the operations of each (``Trace.ops_by_device``).
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
from pathlib import Path

from . import check, peaks, tracereduce
from .loops import LOOPS, Loop

BENCH = Path(__file__).resolve().parent


# ---------------------------------------------------------------------------
# Discovery by name
# ---------------------------------------------------------------------------
def load_spec(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def find_cell(spec: dict, workload: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def load_config(root: Path, spec: dict, name: str) -> dict:
    (entry,) = [c for c in spec["configs"] if c["name"] == name]
    return json.loads((Path(root) / entry["file"]).read_text())


def load_traffic(bench: Path, name: str) -> dict:
    return json.loads((Path(bench) / "traffic" / f"{name}.json").read_text())


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_generator(bench: Path, name: str):
    return load_module(Path(bench) / "generators" / f"{name}.py",
                       f"bench_generator_{name}")


class LoopNotFound(LookupError):
    """A traffic mix names a loop that is neither built in nor a file
    that defines ``LOOP``."""


def load_loop(bench: Path, name: str) -> type:
    """The loop class a traffic mix names: ``refill`` and ``new`` are
    built into :mod:`bench.loops`; any other name is the ``LOOP`` of
    ``bench/traffic/<name>.py``, a subclass of :class:`bench.loops.Loop`."""
    if name in LOOPS:
        return LOOPS[name]
    path = Path(bench) / "traffic" / f"{name}.py"
    if not path.is_file():
        raise LoopNotFound(f"no loop {name!r}: {path} does not exist")
    loop = getattr(load_module(path, "bench_loop_" + name.replace(".", "_")),
                   "LOOP", None)
    if not (isinstance(loop, type) and issubclass(loop, Loop)):
        raise LoopNotFound(f"no loop {name!r}: {path} defines no LOOP, "
                           f"a subclass of bench.loops.Loop")
    return loop


def load_reader(bench: Path, metric: str):
    path = Path(bench) / "metrics" / f"{metric}.py"
    return load_module(path, "bench_metric_" + metric.replace(".", "_"))


def _patterns(reader) -> dict:
    """The tuples of names a metric reader matches trace events by."""
    return {k: v for k, v in vars(reader).items()
            if k.isupper() and isinstance(v, tuple)}


def cell_metrics(spec: dict, workload: str, traced: bool) -> list:
    """The metric entries a run of ``workload`` reports: its end-to-end
    metrics, or with a trace its per-layer ones."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in names]


# ---------------------------------------------------------------------------
# What a metric reader sees
# ---------------------------------------------------------------------------
class Context:
    """Everything a run measured, for the metric readers."""

    def __init__(self, *, cfg, setup_s, latencies, triplets, window_s,
                 trace, peak):
        self.cfg = cfg
        self.setup_s = setup_s
        self.latencies = latencies      # s per request, host clock
        self.triplets = triplets        # triplets per request
        self.window_s = window_s        # host clock
        self.trace = trace              # tracereduce.Trace or None
        self.peak = peak                # bench.peaks entry

    def window(self) -> tuple:
        return self.trace.window()

    def requests(self) -> list:
        lo, hi = self.window()
        return [s for s in self.trace.spans("bench.request")
                if lo <= s.start and s.end <= hi]

    def op_time(self, **sel) -> float:
        lo, hi = self.window()
        return tracereduce.op_time(self.trace.ops, lo, hi, **sel)

    def busy(self, lo=None, hi=None) -> float:
        wlo, whi = self.window()
        return tracereduce.busy(self.trace.ops, wlo if lo is None else lo,
                                whi if hi is None else hi)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def find_devices(chips: int, require_tpu: bool):
    """``(devices, count, peak)``: the cell's devices (the first
    ``chips`` that JAX sees), how many JAX sees, and the chip's peaks."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise NoChip(f"needs a TPU, JAX found {dev.platform} "
                         f"({dev.device_kind})")
        if len(devices) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
        peak = peaks.peaks(dev.device_kind)
    else:
        peak = peaks.DEVICE_PEAKS["TPU v5 lite"]
    return devices[:chips], len(devices), peak


def compilation_cache(root: Path) -> str:
    """JAX's persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else the fixed ``.jax_cache`` in the checkout."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def measure(loop, seconds: float, annotate) -> tuple:
    """The closed loop for ``seconds``, ending on a request boundary:
    ``(latencies, window_s, failed)``."""
    latencies, failed = [], 0
    t0 = time.perf_counter()
    with annotate("bench.window"):
        while True:
            s = time.perf_counter()
            with annotate("bench.request"):
                try:
                    loop.request(len(latencies) + failed)
                except Exception as exc:  # noqa: BLE001 - counted, reported
                    failed += 1
                    print(f"request failed: {type(exc).__name__}: {exc}",
                          file=sys.stderr)
                    if failed > 3:
                        raise
                    continue
            end = time.perf_counter()
            latencies.append(end - s)
            if end - t0 >= seconds:
                break
    return latencies, end - t0, failed


def run(root, workload: str, seed: int, seconds: float, trace: bool, *,
        bench=BENCH, require_tpu: bool = True, control: bool = False,
        out=None, err=None) -> int:
    """One run; prints the result line and returns the exit code."""
    out = out or sys.stdout
    err = err or sys.stderr
    root, bench = Path(root), Path(bench)
    seed = int(seed) & ((1 << 63) - 1)
    spec = load_spec(root)
    cell = find_cell(spec, workload)
    cfg = load_config(root, spec, cell["config"])
    traffic = load_traffic(bench, cell["traffic"])
    try:
        loop_class = load_loop(bench, traffic["loop"])
    except LoopNotFound as e:
        print(f"bench: {workload}: {e}", file=err)
        return 3
    metrics = cell_metrics(spec, workload, trace)
    readers = {m["name"]: load_reader(bench, m["name"]) for m in metrics}
    generator = load_generator(bench, cfg["generator"])

    try:
        devices, count, peak = find_devices(int(cell["chips"]), require_tpu)
    except (NoChip, KeyError) as e:
        print(f"bench: {e}", file=err)
        return 2
    import jax

    dev = devices[0]
    cache = compilation_cache(root)
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    print(f"bench: {workload} seed={seed} loop {loop_class.__name__} on "
          f"{len(devices)} of {count} x {dev.device_kind}; compile cache "
          f"{cache}", file=err)

    t0 = time.perf_counter()
    loop = loop_class(cfg, traffic, seed, generator)
    try:
        loop.setup()
        setup_s = time.perf_counter() - t0

        logdir = root / ".bench_trace" / workload
        if trace:
            shutil.rmtree(logdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(logdir), profiler_options=opts)
        try:
            latencies, window_s, failed = measure(
                loop, seconds, jax.profiler.TraceAnnotation)
        finally:
            if trace:
                jax.profiler.stop_trace()
        memory_peaks = [int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devices]
        loop.finish()
    finally:
        loop.close()
    wait_s = loop.wait_s

    tr = None
    if trace:
        tr = tracereduce.load(logdir, [d.id for d in devices])
        shutil.rmtree(logdir, ignore_errors=True)
    ctx = Context(cfg=cfg, setup_s=setup_s,
                  latencies=latencies, triplets=loop.triplets(),
                  window_s=window_s, trace=tr, peak=peak)
    values = {}
    for m in metrics:
        v = readers[m["name"]].read(ctx)
        if v is None:
            print(f"bench: {workload} declares {m['name']}, but its reader "
                  f"found nothing to read (names it matches: "
                  f"{_patterns(readers[m['name']])})", file=err)
            return 3
        values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    numbers = check.worst(loop.check(control))
    ok, checks = check.verdict(numbers, cfg["limits"])
    correct = ok and failed == 0
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": count, "memory_peak_bytes": max(memory_peaks),
              "memory_peak_bytes_by_device": memory_peaks}
    result = {"correct": correct, "attempted": len(latencies) + failed,
              "failed": failed, "metrics": values, "device": device}
    if trace:
        lo, hi = tr.window()
        device["busy_s"] = statistics.mean(
            tracereduce.busy(ops, lo, hi)
            for ops in tr.ops_by_device.values()) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": tracereduce.top_ops(tr.ops, lo, hi),
            "idle_gaps": tracereduce.idle_by_host(tr.ops, tr.host, lo, hi),
        }
    result["checks"] = checks
    print(f"bench: {len(latencies)} requests in {window_s:.3f} s, "
          f"median {statistics.median(latencies) * 1e3:.3f} ms, setup "
          f"{setup_s:.3f} s, generator_wait_s {wait_s:.6f}", file=err)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err)
    print(f"check correct={correct} failed={failed}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0
