"""The reduction from a profiler trace to busy, idle and collective time."""

import os
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=evs) for n, evs in lines.items()])


def test_merge_and_overlap():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert tr.overlap([(0, 3), (5, 8)], [(2, 6)]) == 2
    assert tr.overlap([], [(0, 1)]) == 0


def test_synthetic_two_chip_trace():
    host = _plane("/host:CPU", {
        "python": [_ev(tr.SPAN, 100, 400), _ev(tr.SPAN, 600, 400),
                   _ev("PjitFunction(step)", 480, 150)],
    })
    chip0 = _plane("/device:TPU:0", {
        "XLA Ops": [_ev("fusion.1", 150, 300), _ev("collective-permute.2", 650, 100),
                    _ev("fusion.1", 750, 200), _ev("fusion.1", 1100, 50)],
        "XLA Modules": [_ev("jit_step", 0, 2000)],
    })
    chip1 = _plane("/device:TPU:1", {"XLA Ops": [_ev("fusion.1", 200, 100)]})
    other = _plane("/device:TPU:0 SparseCore", {"XLA Ops": [_ev("x", 0, 5000)]})
    r = tr.reduce_planes([host, chip0, chip1, other])
    assert r["spans"] == 2
    assert r["window_s"] == pytest.approx(900e-9)  # 100 .. 1000
    c0 = r["chips"][0]
    # 150-450 and 650-950 inside the window; the op at 1100 lies outside it
    assert c0["busy_s"] == pytest.approx(600e-9)
    assert c0["permute_s"] == pytest.approx(100e-9)
    assert r["chips"][1]["busy_s"] == pytest.approx(100e-9)
    assert r["busiest"] == 0
    assert r["device_ops"][0][0] == "fusion.1"
    assert tr.op_name("%fusion.2 = f32[1,5114,256,256]{3,2,1,0:T(8,128)} fusion(f32[5114]") \
        == "%fusion.2 f32[1,5114,256,256]"
    # gaps on chip 0: 100-150, 450-650 (the host was in PjitFunction), 950-1000
    assert r["idle_gaps"][0] == ["PjitFunction(step)", pytest.approx(200e-9)]
    assert len(r["idle_gaps"]) == 3


def test_nothing_to_read_gives_none():
    host = _plane("/host:CPU", {"python": [_ev("other", 0, 10)]})
    chip = _plane("/device:TPU:0", {"XLA Ops": [_ev("fusion", 0, 5)]})
    assert tr.reduce_planes([host, chip]) is None
    assert tr.reduce_planes([_plane("/host:CPU", {"python": [_ev(tr.SPAN, 0, 10)]})]) is None


def test_recorded_one_chip_trace():
    """A profiler trace of 19 products of the banded family at n = 8,192
    (bs 512, 244 C blocks) recorded on one TPU v5e by the harness."""
    r = tr.reduce_file(os.path.join(DATA, "banded_8192_one_chip.xplane.pb"))
    assert r["spans"] == 19
    assert list(r["chips"]) == [0]
    c = r["chips"][0]
    assert 0.0 < c["busy_s"] <= r["window_s"]
    assert c["busy_s"] == pytest.approx(0.28696, rel=1e-4)
    assert r["window_s"] == pytest.approx(0.30899, rel=1e-4)
    assert c["permute_s"] == 0.0
    name, secs = r["device_ops"][0]
    assert name == "%fused_block_spmm_kernel_call.1 f32[244,512,512]"
    assert secs > 0.99 * c["busy_s"]
    assert len(r["idle_gaps"]) == 10
    assert sum(g for _, g in r["idle_gaps"]) <= r["window_s"] - c["busy_s"] + 1e-12
