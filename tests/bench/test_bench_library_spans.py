"""The library's ``repro.*`` spans read from a profiler trace."""

import os
from types import SimpleNamespace as NS

import pytest

from bench import library_spans as ls
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=evs) for n, evs in lines.items()])


def _two_products():
    """Two products in the window, one warm-up product before it.  Each
    product: dist_multiply holds plan_key then dispatch; the chip runs the
    kernel after the enqueue."""
    host = _plane("/host:CPU", {
        "python": [
            # warm-up, outside the window: its plan miss is not counted
            _ev("repro.dist_multiply", 0, 50), _ev("repro.plan_build", 5, 30),
            _ev(tr.SPAN, 100, 400),
            _ev("repro.dist_multiply", 100, 100),
            _ev("repro.plan_key", 110, 20), _ev("repro.dispatch", 140, 50),
            _ev(tr.SPAN, 500, 400),
            _ev("repro.dist_multiply", 500, 120),
            _ev("repro.plan_key", 505, 25), _ev("repro.dispatch", 540, 70),
        ],
        "runtime": [_ev("ReadSyncFlag", 420, 90), _ev("ReadSyncFlag", 850, 60)],
    })
    chip = _plane("/device:TPU:0", {
        "XLA Ops": [_ev("fusion.1", 195, 230), _ev("fusion.1", 615, 240)],
    })
    return [host, chip]


def test_library_totals_and_self_times():
    r = ls.reduce_planes(_two_products())
    assert r["calls"] == 2
    lib = r["library"]
    assert set(lib) == {"repro.dist_multiply", "repro.plan_key", "repro.dispatch"}
    dm = lib["repro.dist_multiply"]
    assert dm["count"] == 2
    assert dm["total_s"] == pytest.approx(220e-9)
    # less plan_key (20 + 25) and dispatch (50 + 70)
    assert dm["self_s"] == pytest.approx(55e-9)
    assert dm["children_s"] == {"repro.plan_key": pytest.approx(45e-9),
                                "repro.dispatch": pytest.approx(120e-9)}
    assert lib["repro.dispatch"]["self_s"] == pytest.approx(120e-9)
    # per product: dist_multiply less dispatch, and dispatch
    assert r["host_ms"] == pytest.approx(1e3 * (220 - 120) * 1e-9 / 2)
    assert r["enqueue_ms"] == pytest.approx(1e3 * 120e-9 / 2)
    # the device side is the existing reduction's, untouched
    assert r["device"]["spans"] == 2 and r["device"]["busiest"] == 0


def test_gap_takes_the_library_span_that_overlaps_it_most():
    r = ls.reduce_planes(_two_products())
    gaps = {round(g * 1e9): name for name, g in r["idle_gaps"]}
    # 100-195: plan_key 20, dispatch 50, dist_multiply's own 25 (ReadSyncFlag
    # is elsewhere): the innermost span with the most time in it is dispatch
    assert gaps[95] == "repro.dispatch"
    # 425-615: the runtime wait overlaps 85 and dispatch 70, but a library
    # span names a gap before any runtime event does
    assert gaps[190] == "repro.dispatch"
    # 855-900: no library span, so the runtime event names it
    assert gaps[45] == "ReadSyncFlag"


def test_nest_self_intervals():
    nodes = ls.nest([("p", 0, 10), ("c1", 1, 3), ("g", 2, 3), ("c2", 5, 9)])
    by = {n["name"]: n for n in nodes}
    assert by["p"]["own"] == [(0, 1), (3, 5), (9, 10)]
    assert [c["name"] for c in by["p"]["children"]] == ["c1", "c2"]
    assert by["c1"]["own"] == [(1, 2)] and by["g"]["own"] == [(2, 3)]


def test_nothing_to_read():
    host = _plane("/host:CPU", {"python": [_ev("other", 0, 10)]})
    chip = _plane("/device:TPU:0", {"XLA Ops": [_ev("fusion", 0, 5)]})
    assert ls.reduce_planes([host, chip]) is None
    # spans and ops but no library span: no per-product numbers
    host = _plane("/host:CPU", {"python": [_ev(tr.SPAN, 0, 10)]})
    r = ls.reduce_planes([host, chip])
    assert r["library"] == {} and "host_ms" not in r and "enqueue_ms" not in r


def test_recorded_trace_without_library_spans():
    """The committed one-chip trace predates the library's spans: nothing to
    read for the library, and every gap keeps the runtime event's name that
    the existing reduction gives it."""
    path = os.path.join(DATA, "banded_8192_one_chip.xplane.pb")
    r = ls.reduce_file(path)
    base = tr.reduce_file(path)
    assert r["library"] == {} and "host_ms" not in r
    assert r["calls"] == base["spans"] == 19
    assert [n for n, _ in r["idle_gaps"]] == [n for n, _ in base["idle_gaps"]]
    assert [g for _, g in r["idle_gaps"]] == pytest.approx([g for _, g in base["idle_gaps"]])
    assert r["device"]["window_s"] == base["window_s"]


def test_setup_reads_planning_and_compiles_from_the_program():
    """The command's set-up line at a toy size on the CPU: planning seconds
    from the plan cache, and the compile of the product's executable under
    its ``dispatch`` span, counted by the tracer installed after the build."""
    from bench_toy import toy_cell

    spec, cfg, traffic = toy_cell("spgemm.banded-100k")
    (setup,) = ls.measure(spec, cfg, traffic, seed=2**33 + 11, seconds=0.0, rounds=0,
                          require_tpu=False, impl="fused-interpret")
    assert setup["planning_s"] > 0
    assert setup["planning_s"] == pytest.approx(setup["build_s"] + setup["verify_s"])
    assert setup["compiles"] >= 1 and setup["compile_s"] > 0
    assert ["dispatch", "spgemm"] in [c[:2] for c in setup["compiled"]]
    # the tracer counts only what compiled under the library's spans
    assert setup["compiles"] <= setup["compiles_all"]
    assert setup["compile_s"] <= setup["compile_s_all"]
    assert setup["setup_s"] == pytest.approx(
        setup["before_build_s"] + setup["build_cell_s"] + setup["warm_up_s"])
