"""Run one cell of ``BENCHMARK.json``: set up, warm up, measure, check.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name:

* ``bench/configs/<config>.json`` — the deployment; its ``family`` names the
  module ``bench/families/<family>.py`` that builds the resident operands,
  makes one timed call, and compares the result with its plain reference;
* ``bench/traffic/<traffic>.json`` — read by :mod:`bench.generator`;
* ``bench/metrics/<metric>.py`` — ``read(ctx)`` returns the metric's value,
  or None where the run has nothing it can read.  A metric named
  ``<quantity>.<part>`` that has no file of its own is read by the file of
  ``<quantity>``: one reader serves the same quantity in cells that report
  different end-to-end metrics (``idle_share.product`` and
  ``idle_share.product.4chip`` are both read by ``idle_share.py``).
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

from . import generator

BENCH = os.path.dirname(os.path.abspath(__file__))


class NoDevice(RuntimeError):
    """The accelerator the cell needs is not there: no result is printed."""


class CompileMeter:
    """Counts XLA compiles (persistent-cache loads included), and the
    persistent cache's hits and misses."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.jax = jax
        self.count = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == self.EVENT:
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self) -> None:
        self.jax.monitoring.unregister_event_duration_listener(self._duration)
        self.jax.monitoring.unregister_event_listener(self._event)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_path(name: str) -> str:
    """The reader of metric ``name``: its own file, or that of the longest
    leading part of its dotted name that has one."""
    parts = name.split(".")
    for k in range(len(parts), 0, -1):
        path = os.path.join(BENCH, "metrics", ".".join(parts[:k]) + ".py")
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no reader for metric {name!r} in bench/metrics")


def cell_spec(bench: dict, workload: str) -> dict:
    """The cell's entry, its configuration and traffic, and its metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if applies(m) and m["moves"] in names]
    return dict(cell=cell, config_entry=conf, end_to_end=e2e, per_layer=layer)


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")["kinds"]
    if kind not in table:
        raise NoDevice(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def devices_for(jax, chips: int, require_tpu: bool):
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoDevice(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


@contextlib.contextmanager
def profiled(jax, enabled: bool):
    """Profiler trace of the block, in a temporary directory; yields a dict
    that receives the path of the ``.xplane.pb`` once the block ends."""
    out: dict = {}
    if not enabled:
        yield out
        return
    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
    for dirpath, _, files in os.walk(logdir):
        for f in files:
            if f.endswith(".xplane.pb"):
                out["path"] = os.path.join(dirpath, f)
    out["dir"] = logdir


def run_cell(spec: dict, cfg: dict, traffic: dict, *, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             impl: str = "fused") -> dict:
    """One run of one cell; returns the result line as a dict.

    ``spec`` is :func:`cell_spec`'s output, ``cfg`` and ``traffic`` the
    loaded files.  ``require_tpu=False`` and ``impl`` exist for the CPU
    rehearsal tests; the command line always requires the chip.
    """
    import jax

    from . import trace_reduce

    chips = spec["cell"]["chips"]
    devices = devices_for(jax, chips, require_tpu)
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if require_tpu else None
    meter = CompileMeter(jax)
    generator.validate(traffic)

    from repro.core.distributed import make_worker_mesh

    mesh = make_worker_mesh(chips)
    family = importlib.import_module(f"bench.families.{cfg['family']}")
    cell = family.build(cfg, mesh, seed, impl=impl)
    generator.warm_up(cell.call, traffic)
    cell.start_window()

    with profiled(jax, trace) as prof:
        c0 = meter.count
        setup_s = time.perf_counter() - t_start
        annotate = (lambda k: jax.profiler.TraceAnnotation(trace_reduce.SPAN)) if trace else None
        win = generator.closed_loop(cell.call, seconds, traffic, on_call=annotate)
        compiles = meter.count - c0
    meter.close()
    print(f"bench: set-up {setup_s:.3f} s, {c0} compiles ({meter.hits} persistent-cache "
          f"hits, {meter.misses} misses); window {win['window_s']:.3f} s, "
          f"{win['calls']} calls, {compiles} compiles; slowest sample "
          f"{max(win['samples']):.6f} s (number {win['samples'].index(max(win['samples']))} "
          f"of {len(win['samples'])}), median {sorted(win['samples'])[len(win['samples']) // 2]:.6f} s",
          file=sys.stderr)
    memory_peak = peak_bytes(devices)
    counters = cell.counters()
    failed = cell.failed()
    work = cell.work()

    reduced = None
    if trace:
        if "path" in prof:
            reduced = trace_reduce.reduce_file(prof["path"])
        shutil.rmtree(prof.get("dir", ""), ignore_errors=True)

    got = cell.collect()
    cell.release()
    checks = cell.check(got)

    ctx = dict(window_s=win["window_s"], calls=win["calls"], samples=win["samples"],
               setup_s=setup_s, memory_peak_bytes=memory_peak, chips=chips,
               peaks=peaks, work=work, compiles_in_window=compiles,
               counters=counters, trace=reduced)
    metrics = {}
    for m in spec["per_layer"] if trace else spec["end_to_end"]:
        reader = load_module(reader_path(m["name"]),
                             "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"))
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": all(v <= lim for _, v, lim in checks),
              "attempted": win["calls"], "failed": failed, "metrics": metrics,
              "device": device}
    if reduced is not None:
        device["busy_s"] = sum(c["busy_s"] for c in reduced["chips"].values()) / len(reduced["chips"])
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {name: {"value": _finite(v), "limit": lim} for name, v, lim in checks}
    return result


def _finite(v: float):
    return v if math.isfinite(v) else str(v)


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} <= {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
