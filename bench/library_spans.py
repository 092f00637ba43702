"""The library's own host spans in a profiler trace, beside the device.

A ``repro.obs.Tracer`` installed on a cell's ``PlanCache`` writes each of
its spans into the profiler trace as a host event named ``repro.<span>``
(``repro.dist_multiply`` with its children ``repro.plan_key`` and
``repro.dispatch``, ``repro.plan_build`` and ``repro.plan_verify`` on a plan
miss).  :func:`reduce_planes` reads them from the window of ``bench.call``
spans that :mod:`bench.trace_reduce` reads:

* ``library``: for each span name, its total and self seconds and its count
  in the window; self time is the duration less what its child ``repro.*``
  spans cover (children nest on their thread's line);
* ``idle_gaps``: the busiest chip's longest idle gaps, each named by the
  ``repro.*`` span that overlaps it most with its self time (the innermost
  span open there), or where none does, by the runtime event that overlaps
  it most (as :mod:`bench.trace_reduce` names every gap);
* ``device``: what :func:`bench.trace_reduce.reduce_planes` gives;
* ``host_ms`` and ``enqueue_ms``: per ``bench.call``, the milliseconds of
  ``repro.dist_multiply`` less its ``repro.dispatch`` children (plan key,
  cache lookup, wrapping C), and of ``repro.dispatch`` (the executable's
  enqueue).

Run as a command, it measures what the library tracer costs: one process
builds a cell as :mod:`bench.run` does, installs a tracer on its plan cache
right after the build (so set-up planning and compiles are counted), warms
up, and then runs profiled windows without and with the tracer on the same
operands, in alternating order, printing one JSON line per window::

    python3 -m bench.library_spans --workload <name> --seed <n> --seconds <s> --rounds <k>
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

T_START = time.perf_counter()

from . import generator, trace_reduce  # noqa: E402

PREFIX = "repro."
DIST_MULTIPLY = PREFIX + "dist_multiply"
DISPATCH = PREFIX + "dispatch"


def nest(events: list[tuple[str, float, float]]) -> list[dict]:
    """The events of one thread's line with their self intervals and the
    seconds of their direct children by name (events on one line nest)."""
    out: list[dict] = []
    stack: list[dict] = []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1]["end"] <= s:
            stack.pop()
        node = dict(name=name, start=s, end=e, children=[])
        if stack:
            stack[-1]["children"].append(node)
        stack.append(node)
        out.append(node)
    for node in out:
        own, t = [], node["start"]
        for c in node["children"]:
            if c["start"] > t:
                own.append((t, c["start"]))
            t = max(t, c["end"])
        if node["end"] > t:
            own.append((t, node["end"]))
        node["own"] = own
    return out


def reduce_planes(planes, span: str = trace_reduce.SPAN, top: int = 10) -> dict | None:
    """The library's spans in the window (module docstring); None where the
    trace holds no ``bench.call`` span or no device operation."""
    # a profile's planes and lines may be read only once: keep them
    planes = [SimpleNamespace(name=p.name, lines=[
        SimpleNamespace(name=line.name, events=list(line.events)) for line in p.lines])
        for p in planes]
    base = trace_reduce.reduce_planes(planes, span=span, top=top)
    if base is None:
        return None
    calls, runtime, nodes = [], [], []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            lib = []
            for ev in trace_reduce._events(line):
                if ev[0] == span:
                    calls.append(ev)
                elif ev[0].startswith(PREFIX):
                    lib.append(ev)
                else:
                    runtime.append(ev)
            nodes += nest(lib)
    w0 = min(s for _, s, _ in calls)
    w1 = max(e for _, _, e in calls)
    nodes = [n for n in nodes if w0 <= n["start"] < w1]

    library: dict[str, dict] = {}
    for n in nodes:
        d = library.setdefault(n["name"], dict(total_s=0.0, self_s=0.0, count=0,
                                               children_s={}))
        d["total_s"] += (n["end"] - n["start"]) * 1e-9
        d["self_s"] += sum(e - s for s, e in n["own"]) * 1e-9
        d["count"] += 1
        for c in n["children"]:
            d["children_s"][c["name"]] = (d["children_s"].get(c["name"], 0.0)
                                          + (c["end"] - c["start"]) * 1e-9)
    pieces = [(n["name"], s, e) for n in nodes for s, e in n["own"]]
    busy = base["chips"][base["busiest"]]["busy"]
    gaps = sorted(idle_intervals(busy, (w0, w1)), key=lambda g: g[0] - g[1])[:top]
    named = [[name_gap(g, pieces) or name_gap(g, runtime) or "idle", (g[1] - g[0]) * 1e-9]
             for g in gaps]

    out = dict(device=base, calls=len(calls), library=library, idle_gaps=named)
    dm = library.get(DIST_MULTIPLY)
    if dm is not None:
        out["host_ms"] = 1e3 * (dm["total_s"] - dm["children_s"].get(DISPATCH, 0.0)) / len(calls)
    if DISPATCH in library:
        out["enqueue_ms"] = 1e3 * library[DISPATCH]["total_s"] / len(calls)
    return out


def idle_intervals(busy: list[tuple[float, float]], window: tuple[float, float]):
    """The gaps of a sorted disjoint busy list inside ``window``."""
    gaps, t = [], window[0]
    for s, e in busy + [(window[1], window[1])]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    return gaps


def name_gap(gap: tuple[float, float], events) -> str | None:
    """The name of the event that overlaps ``gap`` most, ties to the smaller
    name, as :mod:`bench.trace_reduce` names a gap; None where none does."""
    best, best_ov = None, 0.0
    for name, s, e in events:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_ov or (ov == best_ov and ov > 0 and name < best):
            best, best_ov = name, ov
    return best


def reduce_file(path: str, span: str = trace_reduce.SPAN, top: int = 10) -> dict | None:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, span=span, top=top)


# -- the command -------------------------------------------------------------

def _window(jax, cell, traffic: dict, seconds: float, harness) -> dict:
    annotate = lambda k: jax.profiler.TraceAnnotation(trace_reduce.SPAN)  # noqa: E731
    with harness.profiled(jax, True) as prof:
        win = generator.closed_loop(cell.call, seconds, traffic, on_call=annotate)
    lib = reduce_file(prof["path"])
    shutil.rmtree(prof["dir"], ignore_errors=True)
    base = lib["device"]
    idle = harness.load_module(harness.reader_path("idle_share"), "bench_metric_idle_share")
    share = idle.read(dict(trace=base))
    out = dict(product_s=win["window_s"] / win["calls"], calls=win["calls"],
               idle_share=share,
               idle_per_product_ms=10.0 * share * base["window_s"] / base["spans"])
    out.update({k: lib[k] for k in ("host_ms", "enqueue_ms") if k in lib})
    out["library"] = {n: {k: d[k] for k in ("total_s", "self_s", "count")}
                      for n, d in lib["library"].items()}
    out["idle_gaps"] = lib["idle_gaps"]
    return out


def measure(spec: dict, cfg: dict, traffic: dict, *, seed: int, seconds: float,
            rounds: int, require_tpu: bool = True, impl: str = "fused"):
    """Yield the set-up line, then one line per profiled window (module
    docstring).  ``require_tpu=False`` and ``impl`` exist for the CPU tests."""
    import importlib

    import jax

    from repro.core.distributed import make_worker_mesh
    from repro.obs import Tracer

    from . import harness

    devices = harness.devices_for(jax, spec["cell"]["chips"], require_tpu)
    meter = harness.CompileMeter(jax)
    tracer = Tracer()
    compile_secs: list[float] = []  # every compile, the benchmark's own included

    def on_compile(event, secs, **_):
        if event == harness.CompileMeter.EVENT:
            compile_secs.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    listening = True
    try:
        family = importlib.import_module(f"bench.families.{cfg['family']}")
        t0 = time.perf_counter()
        cell = family.build(cfg, make_worker_mesh(len(devices)), seed, impl=impl)
        t1 = time.perf_counter()
        cell.cache.tracer = tracer
        generator.warm_up(cell.call, traffic)
        t2 = time.perf_counter()
        jax.monitoring.unregister_event_duration_listener(on_compile)
        listening = False
        st = cell.cache.stats()
        yield dict(setup_s=t2 - T_START, before_build_s=t0 - T_START, build_cell_s=t1 - t0,
                   warm_up_s=t2 - t1, compile_s_all=sum(compile_secs),
                   planning_s=st["build_s"] + st["verify_s"],
                   build_s=st["build_s"], verify_s=st["verify_s"],
                   compile_s=tracer.counter("compile_s").value,
                   compiles=tracer.counter("compiles").value,
                   compiles_all=meter.count, cache_hits=meter.hits,
                   cache_misses=meter.misses,
                   compiled=[[e["span"], e["kind"], e["fun"], e["secs"]]
                             for e in tracer.instants_of("compile")])
        for r in range(rounds):
            for on in ((False, True) if r % 2 == 0 else (True, False)):
                cell.cache.tracer = tracer if on else None
                c0 = meter.count
                out = _window(jax, cell, traffic, seconds, harness)
                out.update(round=r, tracer=on, compiles_in_window=meter.count - c0)
                yield out
    finally:
        if listening:
            jax.monitoring.unregister_event_duration_listener(on_compile)
        meter.close()
        tracer.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Library spans and the tracer's cost in one cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rounds", type=int, default=2)
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.compile_cache import enable_compilation_cache

    from . import harness

    enable_compilation_cache(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = harness.cell_spec(json.load(f), args.workload)
    cfg = harness.load_json(root, spec["config_entry"]["file"])
    traffic = harness.load_json(harness.BENCH, "traffic", spec["cell"]["traffic"] + ".json")
    try:
        for line in measure(spec, cfg, traffic, seed=args.seed, seconds=args.seconds,
                            rounds=args.rounds):
            print(json.dumps(dict(line, workload=args.workload, seed=args.seed)), flush=True)
    except harness.NoDevice as e:
        print(f"library_spans: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
