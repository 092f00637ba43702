"""Runtime health observatory: event log, flight recorder, memory accounting,
health monitors, regression gate, device-transfer lint.

Schema stability is golden-keyed like the shared iteration rows
(``SHARED_ITER_KEYS``): ``EVENT_KEYS`` pins the event-log envelope and
``POSTMORTEM_KEYS`` the flight-recorder dump.  The SPMD half (observability
off/on bit-identity, Lanczos fallback, refine-divergence postmortem) runs in
a subprocess with 4 fake CPU devices, same harness as test_obs.py.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from helpers import random_block_matrix

from repro.analysis import PlanError
from repro.analysis.lint import lint_paths
from repro.analysis.mutate import CORRUPTIONS
from repro.core.cache import SymbolicCache
from repro.core.inverse import RefineMonitor
from repro.core.purify import Sp2Monitor
from repro.core.schedule import make_spgemm_plan
from repro.obs import Tracer
from repro.obs.log import (
    EVENT_KEYS,
    NULL_LOG,
    POSTMORTEM_KEYS,
    EventLog,
    FlightRecorder,
    load_events,
    log_of,
)
from repro.obs.memory import MemoryMeter, plan_memory_bytes
from repro.obs.regress import (
    ENTRY_KEYS,
    append_history,
    check_history,
    load_history,
)
from repro.obs.regress import main as regress_main

BS = 16


def _plan(exchange="p2p"):
    m = random_block_matrix(256, BS, 0.25, seed=3)
    return make_spgemm_plan(m.coords, m.coords, 4, BS, exchange=exchange)


# ---------------------------------------------------------------------------
# event log: golden envelope, level filter, JSONL round-trip, ring buffer
# ---------------------------------------------------------------------------


def test_event_record_golden_keys():
    lg = EventLog()
    rec = lg.info("run_start", driver="sp2", n=64)
    # the envelope keys come first, in pinned order; payload follows
    assert tuple(rec)[: len(EVENT_KEYS)] == EVENT_KEYS
    assert EVENT_KEYS == ("ts", "seq", "level", "event")
    assert rec["event"] == "run_start" and rec["level"] == "info"
    assert rec["driver"] == "sp2" and rec["n"] == 64


def test_level_filter_and_sequencing():
    lg = EventLog(level="warn")
    assert lg.info("quiet") is None and lg.debug("quiet") is None
    a, b = lg.warn("first"), lg.error("second")
    assert [r["event"] for r in lg.recent] == ["first", "second"]
    assert b["seq"] == a["seq"] + 1


def test_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    lg = EventLog(path, level="debug")
    lg.debug("plan_build", kind="spgemm", build_s=0.25)
    lg.warn("health_alert", kind="straggler", worker=2)
    lg.close()
    back = load_events(path)
    assert [r["event"] for r in back] == ["plan_build", "health_alert"]
    assert back[0]["kind"] == "spgemm" and back[1]["worker"] == 2
    assert all(tuple(r)[: len(EVENT_KEYS)] == EVENT_KEYS for r in back)


def test_ring_buffer_capacity():
    lg = EventLog(capacity=4)
    for i in range(10):
        lg.info("tick", i=i)
    assert [r["i"] for r in lg.recent] == [6, 7, 8, 9]


def test_null_log_is_inert():
    assert not NULL_LOG and not NULL_LOG.enabled
    assert NULL_LOG.info("anything", x=1) is None
    assert NULL_LOG.events_of("anything") == []
    assert log_of(None) is NULL_LOG
    cache = SymbolicCache()
    assert log_of(cache) is NULL_LOG  # default off
    lg = EventLog()
    cache.event_log = lg
    assert log_of(cache) is lg
    cache.event_log = None
    assert log_of(cache) is NULL_LOG


def test_events_filter_by_name_and_level():
    lg = EventLog(level="debug")
    lg.debug("iteration", i=0)
    lg.warn("health_alert", kind="stall")
    lg.debug("iteration", i=1)
    assert [r["i"] for r in lg.events_of("iteration")] == [0, 1]
    assert len(lg.events_of("health_alert", level="warn")) == 1
    assert lg.events_of("iteration", level="warn") == []


# ---------------------------------------------------------------------------
# flight recorder: golden postmortem schema, counter deltas, PlanError hook
# ---------------------------------------------------------------------------


def test_postmortem_golden_keys(tmp_path):
    tr = Tracer()
    cache = SymbolicCache(tracer=tr, event_log=EventLog())
    rec = FlightRecorder(str(tmp_path / "pm.json")).install(cache)
    assert cache.flight_recorder is rec
    with tr.span("step", cat="phase"):
        tr.counter("tasks_executed").add(7.0)
    pm = rec.snapshot("unit_test", cache, extra="detail")
    assert tuple(pm) == POSTMORTEM_KEYS
    assert pm["reason"] == "unit_test" and pm["detail"]["extra"] == "detail"
    assert [sp["name"] for sp in pm["spans"]] == ["step"]


def test_postmortem_counter_deltas_vs_mark(tmp_path):
    tr = Tracer()
    cache = SymbolicCache(tracer=tr)
    rec = FlightRecorder(str(tmp_path / "pm.json")).install(cache)
    tr.counter("tasks_executed").add(10.0)
    rec.mark(cache)
    tr.counter("tasks_executed").add(3.0)
    pm = rec.snapshot("delta_test", cache)
    assert pm["counters"]["tasks_executed"] == pytest.approx(13.0)
    assert pm["counter_deltas"]["tasks_executed"] == pytest.approx(3.0)


def test_plan_error_dumps_postmortem(tmp_path):
    """An injected plan corruption rejected at admission leaves a complete
    postmortem behind — the debugging workflow the flight recorder exists
    for."""
    plan = _plan()
    bad, _ = CORRUPTIONS["send_conflict"][0](plan)
    tr = Tracer()
    lg = EventLog(level="debug")
    cache = SymbolicCache(tracer=tr, event_log=lg)
    pm_path = str(tmp_path / "postmortem.json")
    rec = FlightRecorder(pm_path).install(cache)
    with pytest.raises(PlanError):
        cache.get_or_build(("spgemm", "k1"), lambda: (bad, None))
    assert rec.dumps == 1 and os.path.exists(pm_path)
    with open(pm_path) as fh:
        pm = json.load(fh)
    assert tuple(pm) == POSTMORTEM_KEYS
    assert pm["reason"] == "plan_error"
    assert pm["detail"]["violations"]
    assert pm["detail"]["violations"][0]["check"] == "send-conflict"
    assert pm["cache"]["entries"] == 0  # the bad plan was never admitted
    # the error also landed in the event log and the tracer's instants
    assert lg.events_of("plan_error", level="error")
    assert tr.instants_of("postmortem", "health")


# ---------------------------------------------------------------------------
# memory accounting
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("exchange", ["p2p", "allgather"])
def test_plan_memory_bytes_math(exchange):
    plan = _plan(exchange)
    mem = plan_memory_bytes(plan)
    blk = BS * BS * 4
    assert mem["own_bytes"] == (plan.a_cap + plan.b_cap) * blk
    assert mem["out_bytes"] == plan.c_cap * blk
    if exchange == "allgather":
        expected = (plan.nparts - 1) * (plan.a_cap + plan.b_cap) * blk
    else:
        expected = sum(
            send[d].shape[1] * blk
            for offs, send in ((plan.a_offsets, plan.a_send),
                               (plan.b_offsets, plan.b_send))
            for d in offs
        )
    assert mem["recv_buffer_bytes"] == expected
    assert mem["total_bytes"] == pytest.approx(
        mem["own_bytes"] + mem["recv_buffer_bytes"] + mem["out_bytes"]
        + mem["index_bytes"]
    )
    assert np.allclose(mem["per_worker"], mem["total_bytes"])
    # memoized on the plan: the second call is the same dict
    assert plan_memory_bytes(plan) is mem


def test_plan_memory_bf16_wire():
    plan = _plan()

    class Bf16:
        mode = "bf16"

    full = plan_memory_bytes(plan)
    half = plan_memory_bytes(plan, Bf16())
    assert half["recv_buffer_bytes"] == pytest.approx(
        full["recv_buffer_bytes"] / 2)
    assert half["own_bytes"] == full["own_bytes"]  # stores stay fp32


def test_memory_meter_peaks_and_flush():
    mm = MemoryMeter()
    mm.note_bytes("norm_table", np.array([100.0, 300.0, 200.0, 100.0]))
    mm.note_bytes("norm_table", np.array([50.0, 50.0, 50.0, 50.0]))
    # the peak watermark keeps the high tide, not the last note
    assert np.array_equal(mm.peak["norm_table"], [100.0, 300.0, 200.0, 100.0])
    mm.note_bytes("recv", np.full(4, 10.0))
    assert np.array_equal(mm.worker_peak(), [110.0, 310.0, 210.0, 110.0])
    tr = Tracer()
    mm.flush(tr)
    assert tr.gauge("mem_peak_w1_bytes").value == pytest.approx(310.0)
    summary = mm.summary()
    assert summary["nparts"] == 4
    assert summary["peak_bytes_max"] == pytest.approx(310.0)
    assert set(summary["per_kind"]) == {"norm_table", "recv"}


# ---------------------------------------------------------------------------
# health monitor detectors (synthetic rows/loads, no mesh needed)
# ---------------------------------------------------------------------------


def _row(it, misses=0, recv=1000.0, residual=None):
    row = dict(iteration=it, cache_misses=misses, recv_bytes_mean=recv)
    if residual is not None:
        row["residual"] = residual
    return row


def _load(tasks):
    from repro.dist.balance import WorkerLoad

    tasks = np.asarray(tasks, dtype=np.float64)
    z = np.zeros_like(tasks)
    return WorkerLoad(nparts=tasks.shape[0], bs=BS, tasks=tasks,
                      recv_bytes=z, send_bytes=z, blocks=z)


def test_straggler_detector_needs_patience():
    from repro.obs.health import HealthMonitor, HealthPolicy

    hm = HealthMonitor(HealthPolicy(straggler_factor=1.5,
                                    straggler_patience=3))
    slow = _load([100.0, 100.0, 100.0, 400.0])
    assert hm.observe(_row(0), slow) == []  # streak 1
    assert hm.observe(_row(1), slow) == []  # streak 2
    alerts = hm.observe(_row(2), slow)      # streak 3: trips
    assert [a.kind for a in alerts] == ["straggler"]
    assert alerts[0].data["worker"] == 3
    # re-armed after the alert: no immediate repeat
    assert hm.observe(_row(3), slow) == []
    # a one-iteration blip never trips
    hm2 = HealthMonitor(HealthPolicy())
    assert hm2.observe(_row(0), slow) == []
    assert hm2.observe(_row(1), _load([100.0] * 4)) == []
    assert hm2.observe(_row(2), slow) == []


def test_miss_storm_detector_past_warmup():
    from repro.obs.health import HealthMonitor, HealthPolicy

    hm = HealthMonitor(HealthPolicy(miss_warmup=2, miss_storm_window=3))
    alerts = []
    for it in range(8):
        alerts += hm.observe(_row(it, misses=2))
    assert [a.kind for a in alerts] == ["miss_storm"]
    # warmup misses alone never trip
    hm2 = HealthMonitor(HealthPolicy(miss_warmup=4, miss_storm_window=3))
    for it in range(4):
        assert hm2.observe(_row(it, misses=5)) == []


def test_exchange_blowup_detector():
    from repro.obs.health import HealthMonitor, HealthPolicy

    hm = HealthMonitor(HealthPolicy(exchange_blowup=4.0))
    for it in range(4):
        assert hm.observe(_row(it, recv=1000.0)) == []
    alerts = hm.observe(_row(4, recv=8000.0))
    assert [a.kind for a in alerts] == ["exchange_blowup"]
    assert alerts[0].data["recv_bytes_mean"] == pytest.approx(8000.0)


def test_convergence_stall_detector():
    from repro.obs.health import HealthMonitor, HealthPolicy

    hm = HealthMonitor(HealthPolicy(stall_window=3))
    assert hm.observe(_row(0, residual=1.0)) == []
    alerts = []
    for it in range(1, 6):
        alerts += hm.observe(_row(it, residual=1.0))  # flat forever
    assert [a.kind for a in alerts] == ["convergence_stall"]
    # improvement resets the stall counter
    hm2 = HealthMonitor(HealthPolicy(stall_window=3))
    r = 1.0
    for it in range(8):
        r *= 0.5
        assert hm2.observe(_row(it, residual=r)) == []


def test_alerts_land_in_log_and_trace():
    from repro.obs.health import HealthMonitor, HealthPolicy

    tr = Tracer()
    cache = SymbolicCache(tracer=tr, event_log=EventLog())
    hm = HealthMonitor(HealthPolicy(stall_window=2), cache=cache)
    for it in range(5):
        hm.observe(_row(it, residual=1.0))
    assert hm.alerts
    assert cache.event_log.events_of("health_alert", level="warn")
    assert tr.instants_of("health_alert", "health")
    summary = hm.summary()
    assert summary["alerts_by_kind"] == {"convergence_stall": 1}


def test_maybe_refit_applies_fitted_policy():
    from repro.dist.balance import RebalancePolicy
    from repro.obs.health import HealthMonitor, HealthPolicy

    fitted = RebalancePolicy(recv_cost=0.9, send_cost=0.1, block_cost=0.4)

    class FakeLB:
        policy = RebalancePolicy()

        def calibration(self):
            return fitted, dict(fitted=True, rms_resid_s=0.01)

    lb = FakeLB()
    hm = HealthMonitor(HealthPolicy(refit_every=4))
    for it in range(3):
        hm.observe(_row(it))
        assert hm.maybe_refit(lb) is None
    hm.observe(_row(3))
    assert hm.maybe_refit(lb) == fitted  # iteration 4: refit applied live
    assert lb.policy == fitted and hm.refits == 1
    # same fit again: no-op, not another refit
    for it in range(4, 8):
        hm.observe(_row(it))
    assert hm.maybe_refit(lb) is None and hm.refits == 1

    class NotFitted:
        policy = RebalancePolicy()

        def calibration(self):
            return fitted, dict(fitted=False)

    hm2 = HealthMonitor(HealthPolicy(refit_every=1))
    hm2.observe(_row(0))
    nf = NotFitted()
    assert hm2.maybe_refit(nf) is None and nf.policy == RebalancePolicy()
    # live_policy=False is a hard off switch
    hm3 = HealthMonitor(HealthPolicy(refit_every=1, live_policy=False))
    hm3.observe(_row(0))
    assert hm3.maybe_refit(lb) is None


# ---------------------------------------------------------------------------
# regression gate
# ---------------------------------------------------------------------------


def _entry(bench="trace", config="smoke", commit="abc1234", **metrics):
    return dict(ts=1e9, commit=commit, bench=bench, config=config,
                metrics=metrics, meta={})


def test_history_round_trip_and_envelope(tmp_path):
    path = str(tmp_path / "hist.jsonl")
    assert load_history(path) == []  # missing file is an empty history
    append_history(path, _entry(overhead_pct=1.0))
    append_history(path, _entry(overhead_pct=1.2))
    back = load_history(path)
    assert len(back) == 2 and set(ENTRY_KEYS) <= back[0].keys()
    with pytest.raises(ValueError):
        append_history(path, dict(ts=1.0, commit="x"))  # missing keys
    with pytest.raises(ValueError):
        append_history(path, _entry(bit_identical=True))  # bool metric


def test_check_history_pass_and_fail():
    base = [_entry(overhead_pct=1.0), _entry(overhead_pct=1.1)]
    assert check_history(base) == []  # within abs_tol=2.0
    # seeded regression: overhead jumps past baseline + 2% absolute slack
    bad = base + [_entry(overhead_pct=4.0, commit="bad9999")]
    violations = check_history(bad)
    assert len(violations) == 1
    v = violations[0]
    assert (v["bench"], v["metric"], v["commit"]) == (
        "trace", "overhead_pct", "bad9999")
    # higher-is-better direction: a dropped bit_identical gate fails exactly
    flip = [_entry(bit_identical=1.0), _entry(bit_identical=1.0),
            _entry(bit_identical=0.0)]
    assert [v["metric"] for v in check_history(flip)] == ["bit_identical"]
    # single-entry groups are their own baseline
    assert check_history([_entry(overhead_pct=99.0)]) == []
    # baseline is the median of priors: one noisy run doesn't poison it
    noisy = [_entry(overhead_pct=1.0), _entry(overhead_pct=50.0),
             _entry(overhead_pct=1.0), _entry(overhead_pct=1.2)]
    assert check_history(noisy) == []


def test_regress_cli_exit_codes(tmp_path, capsys):
    path = str(tmp_path / "hist.jsonl")
    append_history(path, _entry(overhead_pct=1.0, bit_identical=1.0))
    append_history(path, _entry(overhead_pct=1.1, bit_identical=1.0))
    assert regress_main(["--history", path, "--check"]) == 0
    assert "clean" in capsys.readouterr().out
    append_history(path, _entry(overhead_pct=9.9, commit="bad9999",
                                bit_identical=1.0))
    assert regress_main(["--history", path, "--check"]) == 1
    out = capsys.readouterr().out
    assert "overhead_pct" in out and "bad9999" in out
    assert regress_main(["--history", path, "--list"]) == 0


def test_history_extractor_from_bench_files(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    try:
        from history import entries_from_bench_json
    finally:
        sys.path.pop(0)
    trace = dict(
        meta=dict(smoke=True, n=128, workers=8, observatory=True),
        overhead=dict(overhead_pct=0.5, overhead_sync_pct=2.0,
                      min_untraced_s=1.0, min_traced_s=1.005,
                      bit_identical=True),
    )
    path = str(tmp_path / "BENCH_trace.json")
    with open(path, "w") as fh:
        json.dump(trace, fh)
    entries = entries_from_bench_json(path, ts=1e9, commit="abc1234")
    assert len(entries) == 1
    e = entries[0]
    assert (e["bench"], e["config"]) == ("trace", "smoke")
    assert e["metrics"]["bit_identical"] == 1.0  # bool became 0/1
    assert e["meta"]["observatory"] is True
    # the extracted entry passes the envelope validation on append
    hist = str(tmp_path / "hist.jsonl")
    append_history(hist, e)
    assert check_history(load_history(hist)) == []
    with open(str(tmp_path / "junk.json"), "w") as fh:
        json.dump(dict(nonsense=1), fh)
    with pytest.raises(ValueError):
        entries_from_bench_json(str(tmp_path / "junk.json"))


# ---------------------------------------------------------------------------
# monitors expose why they stopped
# ---------------------------------------------------------------------------


def test_monitor_stop_reasons():
    m = RefineMonitor(1e-8)
    assert not m.update(0, 1.0) and m.stop_reason is None
    assert m.update(1, 1e-9) and m.stop_reason == "converged"
    d = RefineMonitor(1e-12)
    d.update(0, 1.0)
    assert d.update(1, 5.0) and d.stop_reason == "diverged"
    s = RefineMonitor(1e-12, max_stall=2)
    s.update(0, 1.0)
    assert not s.update(1, 1.5) and s.stop_reason is None
    assert s.update(2, 1.5) and s.stop_reason == "stalled"
    p = Sp2Monitor(1e-8)
    assert not p.update(0, 1.0) and p.stop_reason is None
    assert p.update(1, 1e-9) and p.stop_reason == "converged"
    pd = Sp2Monitor(1e-12)
    pd.update(0, 1.0)
    assert pd.update(1, 5.0) and pd.stop_reason == "diverged"


# ---------------------------------------------------------------------------
# device-transfer lint rule
# ---------------------------------------------------------------------------


def test_device_transfer_lint_fires(tmp_path):
    offender = tmp_path / "offender.py"
    offender.write_text(
        "import jax\n"
        "def dist_bad_collective(x, sh):\n"
        "    y = jax.device_put(x, sh)\n"
        "    return jax.device_get(y)\n"
        "def innocent_helper(x, sh):\n"
        "    return jax.device_put(x, sh)\n"
    )
    findings, _ = lint_paths([offender], baseline=set())
    hits = [f for f in findings if f.rule == "device-transfer"]
    assert len(hits) == 2  # put + get inside dist_*; the helper is clean
    assert all("dist_bad_collective" in f.message for f in hits)
    assert {f.line for f in hits} == {3, 4}
    # the waiver key works like every other rule's
    waived_findings, waived = lint_paths(
        [offender], baseline={"offender.py::device-transfer"})
    assert [f for f in waived_findings if f.rule == "device-transfer"] == []
    assert len([f for f in waived if f.rule == "device-transfer"]) == 2


def test_repo_is_device_transfer_clean():
    findings, _ = lint_paths()
    assert [str(f) for f in findings if f.rule == "device-transfer"] == []


# ---------------------------------------------------------------------------
# SPMD half: bit-identity with observability on, Lanczos fallback,
# refine-divergence postmortem (subprocess, 4 fake devices)
# ---------------------------------------------------------------------------

_SCRIPT = r"""
import json, os, tempfile
import numpy as np, jax
from repro.core import BSMatrix
from repro.core.distributed import make_worker_mesh
from repro.dist import (PlanCache, RebalancePolicy, dist_sp2_purify,
                        dist_localized_inverse_factorization, scatter)
import repro.dist.purify as pur
import repro.dist.inverse as inv
from repro.obs import (EventLog, FlightRecorder, HealthPolicy, MemoryMeter,
                       POSTMORTEM_KEYS, Tracer)

assert jax.device_count() == 4, jax.device_count()
mesh = make_worker_mesh(4)
tmp = tempfile.mkdtemp()
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

# -- full observatory on vs everything off: bit-identical results ------------
skew = np.zeros(F.nnzb, dtype=np.int32)
dFs = scatter(F, mesh, owner=skew)
d0, st0 = dist_sp2_purify(dFs, nocc, lmin, lmax, cache=PlanCache(),
                          rebalance=RebalancePolicy(), **kw)
cache = PlanCache(tracer=Tracer(),
                  event_log=EventLog(os.path.join(tmp, "ev.jsonl"),
                                     level="debug"))
mm = MemoryMeter().install(cache)
rec = FlightRecorder(os.path.join(tmp, "pm.json")).install(cache)
d1, st1 = dist_sp2_purify(dFs, nocc, lmin, lmax, cache=cache,
                          rebalance=RebalancePolicy(),
                          health=HealthPolicy(), **kw)
out["obs_bit_identical"] = bool(np.array_equal(
    np.asarray(d0.to_dense()), np.asarray(d1.to_dense())))
out["health_summary_present"] = st1.health is not None
out["health_off_is_none"] = st0.health is None
evs = [r["event"] for r in cache.event_log.recent]
out["driver_events"] = sorted({e for e in evs
                               if e in ("run_start", "run_end", "iteration",
                                        "plan_build", "rebalance")})
out["memory_accounted"] = bool(mm.notes > 0
                               and float(mm.worker_peak().max()) > 0)
out["no_spurious_postmortem"] = rec.dumps == 0
cache.event_log.close()

# -- Lanczos divergence falls back to block Gershgorin -----------------------
dS = scatter(S, mesh)
cache2 = PlanCache(event_log=EventLog(level="debug"))
lo_ref, hi_ref = pur._spectral_bounds_from_norms(
    dS.coords, pur.resident_block_norms(dS, cache2))
real_ritz = pur._lanczos_ritz
def broken_ritz(f, cache, steps, seed):
    raise pur.LanczosDivergence("injected non-finite beta")
pur._lanczos_ritz = broken_ritz
lo, hi = pur.dist_lanczos_bounds(dS, cache2, steps=8)
pur._lanczos_ritz = real_ritz
out["lanczos_fallback_matches_gershgorin"] = bool(
    abs(lo - lo_ref) < 1e-12 and abs(hi - hi_ref) < 1e-12)
fb = cache2.event_log.events_of("lanczos_fallback", level="warn")
out["lanczos_fallback_logged"] = bool(
    fb and "injected" in fb[0]["reason"])
lo2, hi2 = pur.dist_lanczos_bounds(dS, cache2, steps=8)
out["lanczos_healthy_sane"] = bool(
    np.isfinite(lo2) and np.isfinite(hi2) and lo2 < hi2)

# -- refine divergence trips the flight recorder -----------------------------
class DivergeNow(inv.RefineMonitor):
    def update(self, it, r):
        super().update(it, r)
        if it >= 1:
            self.stop_reason = "diverged"
            return True
        return False
real_mon = inv.RefineMonitor
inv.RefineMonitor = DivergeNow
cache3 = PlanCache(tracer=Tracer(),
                   event_log=EventLog(level="debug"))
pm_path = os.path.join(tmp, "pm_refine.json")
rec3 = FlightRecorder(pm_path, last_spans=32).install(cache3)
z, ist = dist_localized_inverse_factorization(
    dS, cache3, tol=1e-9, max_iter=10, trunc_tau=1e-6, spamm_tau=1e-7)
inv.RefineMonitor = real_mon
out["refine_dump_count"] = rec3.dumps
with open(pm_path) as fh:
    pm = json.load(fh)
out["refine_pm_keys_golden"] = list(pm) == list(POSTMORTEM_KEYS)
out["refine_pm_reason"] = pm["reason"]
out["refine_pm_iteration"] = pm["detail"].get("iteration")
out["refine_pm_has_spans"] = bool(pm["spans"])
out["refine_pm_cache_state"] = bool(pm["cache"].get("hits", 0) > 0
                                    or pm["cache"].get("misses", 0) > 0)
out["refine_warned"] = bool(
    cache3.event_log.events_of("refine_divergence", level="warn"))

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def spmd_results():
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


def test_observatory_on_is_bit_identical(spmd_results):
    assert spmd_results["obs_bit_identical"]
    assert spmd_results["health_summary_present"]
    assert spmd_results["health_off_is_none"]
    assert spmd_results["no_spurious_postmortem"]


def test_driver_threads_event_log(spmd_results):
    assert set(spmd_results["driver_events"]) >= {
        "run_start", "run_end", "iteration", "plan_build"}


def test_memory_meter_rides_the_drivers(spmd_results):
    assert spmd_results["memory_accounted"]


def test_lanczos_divergence_falls_back_to_gershgorin(spmd_results):
    assert spmd_results["lanczos_fallback_matches_gershgorin"]
    assert spmd_results["lanczos_fallback_logged"]
    assert spmd_results["lanczos_healthy_sane"]


def test_refine_divergence_dumps_postmortem(spmd_results):
    assert spmd_results["refine_dump_count"] == 1
    assert spmd_results["refine_pm_keys_golden"]
    assert spmd_results["refine_pm_reason"] == "refine_divergence"
    assert spmd_results["refine_pm_iteration"] == 1
    assert spmd_results["refine_pm_has_spans"]
    assert spmd_results["refine_pm_cache_state"]
    assert spmd_results["refine_warned"]
