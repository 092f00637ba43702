"""SPMD execution tests (subprocess with 8 fake CPU devices — the main test
process must keep seeing 1 device)."""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp, json
from repro.core import BSMatrix, multiply
from repro.core.schedule import make_spgemm_plan, plan_stats
from repro.core.distributed import make_worker_mesh, dist_spgemm, unshard_result

rng = np.random.default_rng(0)
def banded(n, h, bs):
    a = np.zeros((n, n), dtype=np.float32)
    for i in range(n):
        lo, hi = max(0, i-h), min(n, i+h+1)
        a[i, lo:hi] = rng.standard_normal(hi-lo)
    return BSMatrix.from_dense(a, bs)

assert jax.device_count() == 8, jax.device_count()
A = banded(256, 20, 16)
ref = multiply(A, A).to_dense()
out = {}
for placement, exchange, impl in [
    ("morton", "p2p", "ref"),
    ("random", "p2p", "ref"),
    ("morton", "allgather", "ref"),
    ("morton", "p2p", "kernel"),
]:
    plan = make_spgemm_plan(A.coords, A.coords, 8, 16, placement=placement, exchange=exchange)
    res = dist_spgemm(plan, A.data, A.data, make_worker_mesh(8), impl=impl)
    C = unshard_result(plan, res, (256, 256), 16)
    err = float(np.abs(C.to_dense() - ref).max())
    st = plan_stats(plan)
    out[f"{placement}/{exchange}/{impl}"] = {"err": err, "recv": st["recv_bytes_mean"]}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def spmd_results():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True, text=True, timeout=600
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT ") :])


def test_all_modes_match_dense(spmd_results):
    for key, r in spmd_results.items():
        assert r["err"] < 1e-3, (key, r)


def test_kernel_impl_matches(spmd_results):
    assert spmd_results["morton/p2p/kernel"]["err"] < 1e-3


def test_locality_comm_ordering(spmd_results):
    morton = spmd_results["morton/p2p/ref"]["recv"]
    random = spmd_results["random/p2p/ref"]["recv"]
    ag = spmd_results["morton/allgather/ref"]["recv"]
    assert morton < random < ag


# -- two-phase fused layout: parity with the single-phase one, and phase 1
# -- independent of the exchange (subprocess, 4 fake devices) ---------------

_PHASE_SCRIPT = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from jax.extend import core as jcore
from jax.sharding import NamedSharding, PartitionSpec as P
import repro.core.distributed as D
from repro.core.families import band_block_coords, morton_sort, random_block_starts
from repro.core.schedule import make_spgemm_plan
from repro.kernels.precision import Precision

assert jax.device_count() == 4, jax.device_count()
mesh = D.make_worker_mesh(4)
sh = NamedSharding(mesh, P(D.AXIS))
rng = np.random.default_rng(0)
whole = D.fused_task_layout

def structure(name):
    if name == "banded":
        return band_block_coords(24, 3)
    coords = band_block_coords(24, 2)
    have = {tuple(x) for x in coords.tolist()}
    (b0,) = random_block_starts(24, 6, 1, seed=0)
    extra = [(i, j) for i in range(b0, b0 + 6) for j in range(b0, b0 + 6)
             if (i, j) not in have]
    coords = np.concatenate([coords, np.array(extra, np.int64)])
    return coords[morton_sort(coords)]

def build(cls, plan, split, **kw):
    # the single-phase layout is the two-phase one given no locality
    D.fused_task_layout = whole if split else (
        lambda c, n, cap, mt, local=None: whole(c, n, cap, mt))
    try:
        return cls(plan, mesh, impl="fused-interpret", **kw)
    finally:
        D.fused_task_layout = whole

def taint(jaxpr, tainted_in, calls):
    # which outputs derive from a ppermute; records each pallas_call's inputs
    bad = {v for v, t in zip(jaxpr.invars, tainted_in) if t}
    is_bad = lambda v: not isinstance(v, jcore.Literal) and v in bad
    for eqn in jaxpr.eqns:
        ins = [is_bad(v) for v in eqn.invars]
        name = eqn.primitive.name
        if name == "pallas_call":
            calls.append(ins)
            outs = [any(ins)] * len(eqn.outvars)
        elif name == "ppermute":
            outs = [True] * len(eqn.outvars)
        else:
            subs = [p for p in eqn.params.values()
                    if isinstance(p, (jcore.Jaxpr, jcore.ClosedJaxpr))]
            sub = subs[0].jaxpr if subs and isinstance(subs[0], jcore.ClosedJaxpr) else (subs[0] if subs else None)
            if len(subs) == 1 and len(sub.invars) == len(eqn.invars):
                outs = taint(sub, ins, calls)
            else:
                outs = [any(ins)] * len(eqn.outvars)
        bad |= {v for v, t in zip(eqn.outvars, outs) if t}
    return [is_bad(v) for v in jaxpr.outvars]

out = {}
for name in ("banded", "random"):
    coords = structure(name)
    plan = make_spgemm_plan(coords, coords, 4, 8, exchange="p2p")
    store = jax.device_put(
        rng.standard_normal((4, plan.a_cap, 8, 8)).astype(np.float32), sh)
    rows = plan.c_store_valid
    same = lambda x, y: bool((np.asarray(x)[rows] == np.asarray(y)[rows]).all())
    r = {}
    two = build(D.FusedSpgemmExecutable, plan, True)
    one = build(D.FusedSpgemmExecutable, plan, False)
    r["phases"] = [len(two.phases), len(one.phases)]
    r["before"] = two.tasks_before_exchange()
    r["tasks"] = int(plan.task_count.sum())
    r["fused"] = same(two(store, store), one(store, store))
    # SpAMM: prune every task that reads the last round, so it ships nothing
    valid = np.arange(plan.t_cap)[None, :] < plan.task_count[:, None]
    last = len(plan.a_offsets)
    on = valid & (rng.random(valid.shape) < 0.7)
    on &= (plan.task_a_src != last) & (plan.task_b_src != last)
    two_m = build(D.MaskedFusedSpgemmExecutable, plan, True)
    one_m = build(D.MaskedFusedSpgemmExecutable, plan, False)
    r["spamm"] = same(two_m(store, store, on), one_m(store, store, on))
    r["dropped_rounds"] = two_m.last_exchange["dropped_rounds"]
    low = (rng.random(valid.shape) < 0.5).astype(np.int32)
    kw = dict(precision=Precision("adaptive"), prune_exchange=False)
    two_a = build(D.MaskedFusedSpgemmExecutable, plan, True, **kw)
    one_a = build(D.MaskedFusedSpgemmExecutable, plan, False, **kw)
    r["adaptive"] = same(two_a(store, store, valid, low), one_a(store, store, valid, low))
    if name == "banded":
        closed = jax.make_jaxpr(two._mapped)(
            store, store, *two._idx_args, *two._send_args)
        calls = []
        taint(closed.jaxpr, [False] * len(closed.jaxpr.invars), calls)
        r["calls_fed_by_ppermute"] = [any(c) for c in calls]
    out[name] = r
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def phase_results():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _PHASE_SCRIPT], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT ") :])


@pytest.mark.parametrize("structure", ["banded", "random"])
@pytest.mark.parametrize("executable", ["fused", "spamm", "adaptive"])
def test_two_phase_layout_bit_identical(phase_results, structure, executable):
    r = phase_results[structure]
    assert r["phases"] == [2, 1]  # the split engages, and only where asked
    assert 0 < r["before"] < r["tasks"]
    assert r[executable]
    if executable == "spamm":
        assert r["dropped_rounds"] > 0


def test_phase_one_reads_no_ppermute_output(phase_results):
    # the first kernel call's inputs derive from no ppermute, the last's do
    fed = phase_results["banded"]["calls_fed_by_ppermute"]
    assert len(fed) >= 2 and not fed[0] and fed[-1]
