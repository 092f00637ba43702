"""Observability subsystem tests (repro.obs) + driver telemetry invariants.

Unit tests run in-process (tracer nesting, null-tracer no-ops, the shared
timing idioms, Chrome-trace export/validation, report math, wall-clock
policy calibration).  SPMD invariants — tracing-off bit-identity, counter
conservation on a zero-miss replay, per-iteration row schema stability
across both iterative drivers, one trace track per worker — run in a
subprocess with 4 fake CPU devices, same harness as test_dist.py.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.dist.balance import RebalancePolicy, WorkerLoad, calibrate_policy
from repro.obs import (
    NULL_TRACER,
    SHARED_ITER_KEYS,
    IterationScope,
    Tracer,
    chrome_trace_events,
    run_metrics,
    timed_into,
    tracer_of,
    utilization_from_file,
    validate_chrome_trace,
    worker_utilization,
    write_chrome_trace,
)
from repro.core.cache import SymbolicCache


class Tick:
    """Deterministic clock: advances 1.0 s per call."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


# -- tracer core --------------------------------------------------------------

def test_span_nesting_and_durations():
    tr = Tracer(clock=Tick())
    with tr.span("phase", cat="phase"):
        with tr.span("inner") as sp:
            sp.args.update(k=1)
        tr.instant("marker", cat="m", x=2)
    assert [s.name for s in tr.spans] == ["phase", "inner"]
    assert tr.spans[0].parent == -1
    assert tr.spans[1].parent == 0
    assert tr.spans[1].args == {"k": 1}
    assert all(s.dur > 0 for s in tr.spans)
    # inner closed before outer, nested inside it
    assert tr.spans[0].t0 < tr.spans[1].t0 <= tr.spans[1].t1 < tr.spans[0].t1
    (name, cat, t, parent, args) = tr.instants[0]
    assert (name, cat, parent, args) == ("marker", "m", 0, {"x": 2})
    assert tr._stack == []


def test_counters_and_gauges_register_once():
    tr = Tracer()
    c = tr.counter("bytes")
    c.add(3)
    tr.counter("bytes").add(4)  # same object
    assert tr.counter("bytes") is c and c.value == 7
    tr.gauge("imb").set(1.5)
    m = tr.metrics_flat()
    assert m["bytes"] == 7 and m["imb"] == 1.5 and m["spans_recorded"] == 0
    assert len(tr._counter_events) == 3  # two adds + one set


def test_null_tracer_is_inert():
    assert not NULL_TRACER and not NULL_TRACER.enabled
    with NULL_TRACER.span("x", cat="c", a=1) as sp:
        sp.worker_costs = [1, 2]  # annotations vanish
        sp.args.update(k=1)
    assert sp.worker_costs is None and sp.args == {}
    NULL_TRACER.counter("c").add(5)
    NULL_TRACER.gauge("g").set(5)
    NULL_TRACER.instant("i")
    assert NULL_TRACER.metrics_flat() == {}


def test_tracer_of_rides_on_the_cache():
    assert tracer_of(None) is NULL_TRACER
    c = SymbolicCache()
    assert tracer_of(c) is NULL_TRACER
    tr = Tracer()
    c.tracer = tr
    assert tracer_of(c) is tr
    c.tracer = None  # assigning None disables tracing (normalized)
    assert tracer_of(c) is NULL_TRACER and c.tracer is NULL_TRACER
    assert SymbolicCache(tracer=tr).tracer is tr


def test_compiles_count_only_under_spans_until_close():
    import jax

    tr = Tracer()
    jax.jit(lambda x: x * 3.0 + 1.0)(np.ones(3))  # no span open
    assert tr.metrics_flat().get("compiles", 0) == 0
    with tr.span("step", kind="k"):
        jax.jit(lambda x: x * 5.0 - 2.0)(np.ones(5))
    (ev,) = tr.instants_of("compile")
    assert ev["span"] == "step" and ev["kind"] == "k" and ev["secs"] > 0
    assert tr.counter("compiles").value == 1
    assert tr.counter("compile_s").value == ev["secs"]
    tr.close()
    tr.close()  # idempotent
    with tr.span("step"):
        jax.jit(lambda x: x * 7.0 - 4.0)(np.ones(7))
    assert tr.counter("compiles").value == 1


# -- library spans in the profiler trace, compiles, host fetches --------------

@pytest.fixture(scope="module")
def traced_multiply(tmp_path_factory):
    """Two ``dist_multiply`` calls on one structure with a tracer, under a
    CPU profiler capture; the tracer, the compile instants after the first
    call, and the capture's ``repro.*`` host events."""
    import jax
    from jax.profiler import ProfileData

    from repro.core import BSMatrix
    from repro.core.distributed import make_worker_mesh
    from repro.dist import PlanCache, dist_multiply, scatter

    rng = np.random.default_rng(11)
    n, bs = 44, 4  # shapes no other test here compiles
    band = np.abs(np.subtract.outer(np.arange(n), np.arange(n))) < 9
    a = scatter(BSMatrix.from_dense(
        (rng.standard_normal((n, n)) * band).astype(np.float32), bs),
        make_worker_mesh(1))
    tr = Tracer()
    cache = PlanCache(tracer=tr)
    logdir = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(logdir):
        dist_multiply(a, a, cache, impl="ref").store.block_until_ready()
        first = list(tr.instants_of("compile"))
        dist_multiply(a, a, cache, impl="ref").store.block_until_ready()
    tr.close()
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(logdir)
               for f in fs if f.endswith(".xplane.pb")]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("repro.")]
    return tr, first, events


def test_spans_reach_the_profiler_trace_nested(traced_multiply):
    tr, _, events = traced_multiply
    by_name: dict = {}
    for name, s, e in events:
        by_name.setdefault(name, []).append((s, e))
    assert len(by_name["repro.dist_multiply"]) == 2
    assert len(by_name["repro.plan_key"]) == 2
    assert len(by_name["repro.dispatch"]) == 2
    for child in ("repro.plan_key", "repro.dispatch"):
        for s, e in by_name[child]:
            assert any(ps <= s and e <= pe
                       for ps, pe in by_name["repro.dist_multiply"])
    # the miss's planning lands inside the first call only
    assert len(by_name["repro.plan_build"]) == 1
    # the tracer keeps the same spans in memory
    assert [s.name for s in tr.spans].count("dispatch") == 2


def test_first_dispatch_records_one_compile(traced_multiply):
    tr, first, _ = traced_multiply
    under_dispatch = [ev for ev in first if ev["span"] == "dispatch"]
    assert len(under_dispatch) == 1
    assert under_dispatch[0]["kind"] == "spgemm"
    # the repeat call hits the plan cache and compiles nothing
    assert tr.instants_of("compile") == first
    assert tr.counter("compiles").value == len(first)


def test_sp2_iteration_counts_three_host_fetches():
    from repro.core import BSMatrix
    from repro.core.distributed import make_worker_mesh
    from repro.dist import PlanCache, dist_sp2_purify, scatter

    rng = np.random.default_rng(5)
    n, bs = 40, 8
    h = rng.standard_normal((n, n)) * (
        np.abs(np.subtract.outer(np.arange(n), np.arange(n))) < 12)
    f = ((h + h.T) / 2).astype(np.float32)
    w = np.linalg.eigvalsh(f.astype(np.float64))
    tr = Tracer()
    _, st = dist_sp2_purify(
        scatter(BSMatrix.from_dense(f, bs), make_worker_mesh(1)), n // 2,
        float(w.min()) - 0.1, float(w.max()) + 0.1, cache=PlanCache(),
        tracer=tr, max_iter=1, trunc_tau=1e-3, impl="ref")
    tr.close()
    assert len(st.per_iter) == 1
    it = next(s for s in tr.spans if s.name == "sp2_iteration")
    fetches = [v for t, name, v in tr._counter_events
               if name == "host_fetches" and it.t0 <= t <= it.t1]
    # ||X² - X||_F, trace(X), and the norm table for truncation
    assert fetches == [1.0, 2.0, 3.0]


# -- shared timing idioms -----------------------------------------------------

def test_timed_into_accumulates_and_emits_span():
    cache = SymbolicCache()
    tr = Tracer(clock=Tick())
    with timed_into(cache, "symbolic_s", tr, "descent", cat="symbolic", n=3):
        pass
    assert cache.symbolic_s > 0
    assert [s.name for s in tr.spans] == ["descent"]
    assert tr.spans[0].args == {"n": 3}
    # disabled tracer: still accumulates, no span
    before = cache.symbolic_s
    with timed_into(cache, "symbolic_s", NULL_TRACER, "descent") as t:
        pass
    assert cache.symbolic_s > before and t.elapsed >= 0
    # no accumulator object at all
    with timed_into(None, "x", tr, None):
        pass
    assert len(tr.spans) == 1


def test_iteration_scope_row_schema():
    cache = SymbolicCache()
    tr = Tracer(clock=Tick())
    with IterationScope(cache, 2, tr, name="sp2_iteration") as scope:
        cache.get_or_build(("k",), lambda: 1)
        row = scope.row(nnzb=7, idem=0.5)
    assert set(SHARED_ITER_KEYS) <= row.keys()
    assert row["iteration"] == 2 and row["nnzb"] == 7 and row["idem"] == 0.5
    assert row["cache_misses"] == 1 and row["wall_s"] > 0
    assert tr.spans[0].name == "sp2_iteration" and tr.spans[0].args["i"] == 2
    # cache-less stage scope still yields the full schema with zero counters
    with IterationScope(None, None, tr, name="stage", cat="phase") as st:
        d = st.delta()
    assert d["cache_hits"] == 0 and d["plan_build_s"] == 0.0


def test_cache_plan_counters_flow_to_tracer():
    tr = Tracer()
    cache = SymbolicCache(tracer=tr)
    cache.get_or_build(("spgemm", 1), lambda: "v")
    cache.get_or_build(("spgemm", 1), lambda: "v")
    m = run_metrics(cache)
    assert m["plan_misses"] == 1 and m["plan_hits"] == 1
    assert m["hits"] == 1 and m["misses"] == 1  # cache.stats() merged in
    assert any(s.name == "plan_build" for s in tr.spans)
    # tracing off: run_metrics is exactly cache.stats()
    cache2 = SymbolicCache()
    cache2.get_or_build(("add", 1), lambda: "v")
    assert run_metrics(cache2) == cache2.stats()


# -- export + report ----------------------------------------------------------

def _synthetic_tracer():
    tr = Tracer(clock=Tick())
    with tr.span("phase", cat="phase"):
        with tr.span("dispatch", cat="dispatch") as sp:
            sp.worker_costs = np.array([2.0, 1.0, 0.0, 1.0])
            tr.counter("tasks_executed").add(4)
        with tr.span("dispatch", cat="dispatch") as sp:
            sp.worker_costs = np.array([1.0, 1.0, 1.0, 1.0])
            tr.instant("exchange_round", cat="exchange", bytes=256)
    return tr


def test_chrome_trace_export_and_validation(tmp_path):
    tr = _synthetic_tracer()
    summary = write_chrome_trace(tr, str(tmp_path / "t.json"))
    assert summary["host_spans"] == 3
    assert summary["workers"] == 4  # one track per worker
    assert "tasks_executed" in summary["counters"]
    with open(tmp_path / "t.json") as fh:
        trace = json.load(fh)
    assert validate_chrome_trace(trace) == summary
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "M"}
    assert "thread_name" in names and trace["displayTimeUnit"] == "ms"


def test_validate_rejects_misnested_pairs():
    bad = [
        {"ph": "M", "pid": 0, "tid": 0, "name": "thread_name",
         "args": {"name": "host"}},
        {"ph": "B", "pid": 0, "tid": 0, "ts": 0.0, "name": "a", "cat": "c"},
        {"ph": "B", "pid": 0, "tid": 0, "ts": 1.0, "name": "b", "cat": "c"},
        {"ph": "E", "pid": 0, "tid": 0, "ts": 2.0, "name": "a"},
    ]
    with pytest.raises(AssertionError):
        validate_chrome_trace(bad)


def test_worker_utilization_math(tmp_path):
    tr = _synthetic_tracer()
    util = worker_utilization(tr)
    # each dispatch span lasts exactly 2 ticks (the counter/instant inside
    # consumes one); step 1 costs [2,1,0,1] -> busy [2,1,0,1]; step 2 is
    # balanced -> +2 each; window = 4
    assert util["nparts"] == 4 and util["window_s"] == pytest.approx(4.0)
    assert util["busy_s"] == pytest.approx([4.0, 3.0, 2.0, 3.0])
    assert util["busy_frac"] == pytest.approx([1.0, 0.75, 0.5, 0.75])
    assert util["timeline_imbalance"] == pytest.approx(4.0 / 3.0)
    # the written trace file carries the same picture on its own
    write_chrome_trace(tr, str(tmp_path / "t.json"))
    util2 = utilization_from_file(str(tmp_path / "t.json"))
    assert util2["busy_s"] == pytest.approx(util["busy_s"], abs=1e-6)
    assert util2["timeline_imbalance"] == pytest.approx(
        util["timeline_imbalance"], abs=1e-6)


def test_attributed_busy_never_nests():
    tr = Tracer(clock=Tick())
    with tr.span("outer", cat="collective") as outer:
        outer.worker_costs = np.array([1.0, 1.0])
        with tr.span("inner", cat="dispatch") as inner:
            inner.worker_costs = np.array([2.0, 1.0])
    ev = chrome_trace_events(tr)
    busy = [e for e in ev if e.get("pid") == 1 and e["ph"] == "B"]
    # only the outermost attributed span feeds the worker tracks
    assert len(busy) == 2 and all(e["name"] == "outer" for e in busy)


# -- wall-clock policy calibration --------------------------------------------

def _load(tasks, recv, send, blocks, wall, bs=8):
    z = lambda v: np.asarray(v, dtype=np.float64)
    return WorkerLoad(nparts=len(tasks), bs=bs, tasks=z(tasks),
                      recv_bytes=z(recv), send_bytes=z(send),
                      blocks=z(blocks), wall_s=wall)


def test_calibrate_policy_recovers_coefficients():
    rng = np.random.default_rng(3)
    k_t, k_r, k_s, k_b = 1e-4, 5e-5, 2.5e-5, 1e-5
    blk = 8 * 8 * 4
    loads = []
    for _ in range(8):
        t = rng.uniform(50, 500, size=4)
        r = rng.uniform(0, 40, size=4) * blk
        s = rng.uniform(0, 40, size=4) * blk
        b = rng.uniform(5, 50, size=4)
        wall = (k_t * t.max() + k_r * r.max() / blk
                + k_s * s.max() / blk + k_b * b.max())
        loads.append(_load(t, r, s, b, wall))
    policy, rep = calibrate_policy(loads, RebalancePolicy())
    assert rep["fitted"] and rep["samples"] == 8
    assert rep["task_s"] == pytest.approx(k_t, rel=1e-6)
    assert policy.recv_cost == pytest.approx(k_r / k_t, rel=1e-5)
    assert policy.send_cost == pytest.approx(k_s / k_t, rel=1e-5)
    assert policy.block_cost == pytest.approx(k_b / k_t, rel=1e-5)
    assert rep["rms_resid_s"] == pytest.approx(0.0, abs=1e-9)
    # threshold is preserved — only the cost ratios are measured
    assert policy.threshold == RebalancePolicy().threshold


def test_calibrate_policy_needs_enough_samples():
    base = RebalancePolicy()
    ld = _load([10, 20], [0, 0], [0, 0], [1, 2], 0.5)
    policy, rep = calibrate_policy([ld] * 3, base)
    assert policy is base and not rep["fitted"]
    # unwalled loads don't count as samples
    nowall = _load([10, 20], [0, 0], [0, 0], [1, 2], None)
    _, rep2 = calibrate_policy([nowall] * 10, base)
    assert rep2["samples"] == 0 and not rep2["fitted"]


def test_workerload_add_accumulates_wall():
    a = _load([1, 2], [0, 0], [0, 0], [1, 1], 0.25)
    b = _load([2, 1], [0, 0], [0, 0], [1, 1], 0.5)
    assert (a + b).wall_s == pytest.approx(0.75)
    c = _load([1, 1], [0, 0], [0, 0], [1, 1], None)
    assert (c + c).wall_s is None
    assert (a + c).wall_s == pytest.approx(0.25)


# -- SPMD invariants (subprocess, 4 fake devices) -----------------------------

_SCRIPT = r"""
import json, os, tempfile
import numpy as np, jax
from repro.core import BSMatrix
from repro.core.distributed import make_worker_mesh
from repro.dist import (PlanCache, RebalancePolicy, dist_sp2_purify,
                        dist_localized_inverse_factorization, scatter)
from repro.obs import (SHARED_ITER_KEYS, Tracer, run_metrics,
                       utilization_from_file, validate_chrome_trace,
                       worker_utilization, write_chrome_trace)

assert jax.device_count() == 4, jax.device_count()
mesh = make_worker_mesh(4)
out = {}

rng = np.random.default_rng(0)
n, bs = 64, 8
b = np.zeros((n, n), dtype=np.float32)
for i in range(n):
    lo, hi = max(0, i - 5), min(n, i + 6)
    b[i, lo:hi] = rng.standard_normal(hi - lo)
S = BSMatrix.from_dense(b @ b.T / n + np.eye(n, dtype=np.float32), bs)
hm = 0.2 * rng.standard_normal((n, n)).astype(np.float32)
F = BSMatrix.from_dense(
    (hm + hm.T) / 2 + np.diag(np.linspace(-1, 1, n)).astype(np.float32), bs)
w = np.linalg.eigvalsh(np.asarray(F.to_dense(), np.float64))
lmin, lmax = float(w.min()) - 0.05, float(w.max()) + 0.05
nocc = 20
kw = dict(idem_tol=1e-5, trunc_tau=1e-6, spamm_tau=1e-7, max_iter=40)

# -- tracing off vs on: bit-identical results, identical row schema ----------
dF = scatter(F, mesh)
d0, st0 = dist_sp2_purify(dF, nocc, lmin, lmax, cache=PlanCache(), **kw)
tr = Tracer()
d1, st1 = dist_sp2_purify(dF, nocc, lmin, lmax, cache=PlanCache(),
                          tracer=tr, **kw)
out["sp2_bit_identical"] = bool(np.array_equal(
    np.asarray(d0.to_dense()), np.asarray(d1.to_dense())))
out["sp2_rows_same_schema"] = [sorted(st0.per_iter[0]), sorted(st1.per_iter[0])]
out["sp2_rows_shared_keys"] = bool(all(
    set(SHARED_ITER_KEYS) <= set(pi) for st in (st0, st1) for pi in st.per_iter))
out["sp2_spans"] = len(tr.spans)
out["sp2_span_names"] = sorted({s.name for s in tr.spans})[:20]

# -- counter conservation on a zero-miss replay ------------------------------
tr2 = Tracer()
cache = PlanCache(tracer=tr2)
dS = scatter(S, mesh)
z1, i1 = dist_localized_inverse_factorization(
    dS, cache, tol=1e-7, max_iter=40, trunc_tau=1e-6, spamm_tau=1e-7)
h1, m1 = cache.hits, cache.misses
p1 = dict(hits=tr2.counter("plan_hits").value,
          misses=tr2.counter("plan_misses").value)
z2, i2 = dist_localized_inverse_factorization(
    dS, cache, tol=1e-7, max_iter=40, trunc_tau=1e-6, spamm_tau=1e-7)
out["replay_misses"] = [int(cache.misses - m1),
                        int(tr2.counter("plan_misses").value - p1["misses"])]
out["replay_hits_equal"] = bool(
    (cache.hits - h1) == (tr2.counter("plan_hits").value - p1["hits"]))
out["counters_conserved"] = bool(
    tr2.counter("plan_hits").value == cache.hits
    and tr2.counter("plan_misses").value == cache.misses)
out["inv_rows_shared_keys"] = bool(all(
    set(SHARED_ITER_KEYS) <= set(pi) for st in (i1, i2) for pi in st.per_iter))
out["run_metrics_merged"] = bool(
    set(cache.stats()) <= set(run_metrics(cache))
    and run_metrics(cache)["plan_hits"] == cache.hits)

# -- rebalanced run feeds wall-clock calibration -----------------------------
skew = np.zeros(F.nnzb, dtype=np.int32)
dFs = scatter(F, mesh, owner=skew)
d2, st2 = dist_sp2_purify(dFs, nocc, lmin, lmax, cache=PlanCache(),
                          rebalance=RebalancePolicy(), **kw)
out["rebalanced_bit_identical"] = bool(np.array_equal(
    np.asarray(d0.to_dense()), np.asarray(d2.to_dense())))
out["calibration"] = st2.calibration
out["calibration_untracked"] = st0.calibration is None

# -- exported trace: valid, one track per worker, utilization sane -----------
path = os.path.join(tempfile.mkdtemp(), "trace.json")
summary = write_chrome_trace(tr2, path)
out["trace_summary"] = summary
util = worker_utilization(tr2)
out["util_nparts"] = util["nparts"]
out["util_fracs_sane"] = bool(all(0.0 <= f <= 1.0 + 1e-9
                                  for f in util["busy_frac"]))
futil = utilization_from_file(path)
out["util_file_close"] = bool(abs(
    futil["timeline_imbalance"] - util["timeline_imbalance"]) < 1e-6)

# -- tasks laid out ahead of the exchange: counted with a tracer on ----------
from repro.core.distributed import FusedSpgemmExecutable
from repro.core.families import band_block_coords
from repro.core.schedule import make_spgemm_plan
from repro.dist import dist_multiply
coords = band_block_coords(24, 3)
blocks = rng.standard_normal((len(coords), bs, bs)).astype(np.float32)
dA = scatter(BSMatrix(shape=(24 * bs, 24 * bs), bs=bs, coords=coords,
                      data=blocks), mesh)
plan = make_spgemm_plan(coords, coords, 4, bs, exchange="p2p",
                        a_owner=dA.owner, b_owner=dA.owner)
exe = FusedSpgemmExecutable(plan, mesh)
n1 = sum(exe.phases[0]) if len(exe.phases) == 2 else 0
tr3 = Tracer()
dist_multiply(dA, dA, PlanCache(tracer=tr3))
cache_off = PlanCache()
dist_multiply(dA, dA, cache_off)
out["before_exchange"] = dict(
    counted=tr3.counter("tasks_before_exchange").value,
    phase1_real=int(exe._real[:, :n1].sum()),
    executed=tr3.counter("tasks_executed").value,
    off_keys=sorted(k for k in run_metrics(cache_off) if "exchange" in k))

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def obs_results():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        env=env,
        capture_output=True,
        text=True,
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_tracing_off_is_bit_identical(obs_results):
    assert obs_results["sp2_bit_identical"]
    assert obs_results["rebalanced_bit_identical"]


def test_driver_rows_share_one_schema(obs_results):
    a, b = obs_results["sp2_rows_same_schema"]
    assert a == b  # traced and untraced rows carry identical keys
    assert obs_results["sp2_rows_shared_keys"]
    assert obs_results["inv_rows_shared_keys"]


def test_traced_sp2_records_expected_spans(obs_results):
    assert obs_results["sp2_spans"] > 0
    names = set(obs_results["sp2_span_names"])
    assert {"sp2_purify", "sp2_iteration", "dist_spamm",
            "plan_build"} <= names


def test_zero_miss_replay_conserves_counters(obs_results):
    # second identical run: no plan-cache misses, and the tracer's
    # plan_hits/plan_misses counters agree with the cache's own counters
    assert obs_results["replay_misses"] == [0, 0]
    assert obs_results["replay_hits_equal"]
    assert obs_results["counters_conserved"]
    assert obs_results["run_metrics_merged"]


def test_rebalanced_run_reports_calibration(obs_results):
    cal = obs_results["calibration"]
    assert cal is not None and "samples" in cal and "fitted" in cal
    assert obs_results["calibration_untracked"]


def test_exported_trace_one_track_per_worker(obs_results):
    s = obs_results["trace_summary"]
    assert s["workers"] == 4
    assert s["host_spans"] > 0 and s["events"] > s["host_spans"]
    assert obs_results["util_nparts"] == 4
    assert obs_results["util_fracs_sane"]
    assert obs_results["util_file_close"]


def test_tasks_before_exchange_counter(obs_results):
    r = obs_results["before_exchange"]
    assert r["phase1_real"] > 0  # the banded plan splits over four devices
    assert r["counted"] == r["phase1_real"] < r["executed"]
    assert r["off_keys"] == []  # nothing is counted with the tracer off
