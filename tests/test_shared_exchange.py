"""A @ A on one resident store ships each block once (a shared exchange).

Runs in a subprocess with four virtual CPU devices, as tests/test_relayout.py
does: a band plus a dense corner, laid out for A·A by
``relayout_for_product``.  ``dist_multiply(a, a)`` plans one set of rounds
for the union of both sides' needs; ``dist_multiply(a, b)`` on a ``b`` that
holds a copy of A's store keeps the two exchanges.  Both must give the same
C bits, and the host reference.
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import json
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro.core import BSMatrix
from repro.core.distributed import make_worker_mesh
from repro.core.schedule import plan_worker_bytes
from repro.dist import (PlanCache, dist_multiply, dist_spamm, multiply_plan_key,
                        relayout_for_product, scatter)
from repro.kernels.precision import Precision
from repro.obs import Tracer

assert jax.device_count() == 4, jax.device_count()
mesh = make_worker_mesh(4)
rng = np.random.default_rng(5)
nb, bs, corner = 24, 8, 6
n = nb * bs
keep = np.abs(np.arange(nb)[:, None] - np.arange(nb)[None, :]) <= 1
keep[:corner, :corner] = True
dense = rng.standard_normal((n, n)).astype(np.float32)
dense *= np.kron(keep, np.ones((bs, bs), bool))
A = BSMatrix.from_dense(dense, bs)
tr = Tracer()
cache = PlanCache(tracer=tr)
placed = scatter(A, mesh)
a, _ = relayout_for_product(placed, placed, cache)
# another store holding the same blocks: A·B takes the two-exchange path
b = dataclasses.replace(a, store=jnp.array(a.store, copy=True))
ref = dense.astype(np.float64) @ dense.astype(np.float64)
absprod = np.abs(dense).astype(np.float64) @ np.abs(dense).astype(np.float64)

def union_padded(plan):
    # padded receive per chip, from each device's A-side and B-side needs
    t_owner = plan.c_owner[plan.tasks.c_idx]
    caps = {}
    for dst in range(4):
        mine = t_owner == dst
        need = np.union1d(plan.tasks.a_idx[mine], plan.tasks.b_idx[mine])
        src = plan.a_owner[need]
        for s in np.unique(src[src != dst]):
            d = (dst - int(s)) % 4
            caps[d] = max(caps.get(d, 0), int((src == s).sum()))
    return sum(caps.values()) * bs * bs * 4

out = {"moved": int((a.owner != placed.owner).sum()),
       "keys_differ": multiply_plan_key(a, a, exchange="p2p", impl="fused")
       != multiply_plan_key(a, b, exchange="p2p", impl="fused")}
# (keyword arguments, unit roundoff of the leaf products)
cases = {"fused": (dict(impl="fused-interpret"), 2.0**-24),
         "ref": (dict(impl="ref"), 2.0**-24),
         "bf16": (dict(impl="fused-interpret", precision=Precision("bf16")), 2.0**-8),
         "adaptive": (dict(impl="fused-interpret",
                           precision=Precision("adaptive", tau=4.0)), 2.0**-8)}
for name, (kw, u) in cases.items():
    shared0 = tr.counter("shared_exchanges").value
    c1 = dist_multiply(a, a, cache, **kw)
    p1, e1 = cache.peek(cache.last_plan_key)
    counted = tr.counter("shared_exchanges").value - shared0
    c2 = dist_multiply(a, b, cache, **kw)
    p2, e2 = cache.peek(cache.last_plan_key)
    d1, d2 = np.asarray(c1.gather().to_dense()), np.asarray(c2.gather().to_dense())
    r = dict(
        same_bits=bool(np.array_equal(d1, d2)),
        # |error| <= k u (|A||B|), with no more than k = n products an element
        oracle_ratio=float((np.abs(d1 - ref) / (n * u * absprod + 1e-30)).max()),
        shared=[bool(p1.exchange_shared), bool(p2.exchange_shared)],
        b_offsets=[list(p1.b_offsets), list(p2.b_offsets)],
        a_offsets=[list(p1.a_offsets), list(p2.a_offsets)],
        padded=[plan_worker_bytes(p1)[2].tolist(), union_padded(p1),
                plan_worker_bytes(p2)[2].tolist()],
        other_executable=e1 is not e2,
        counted=counted,
        span_arg=[sp.args.get("exchange_shared") for sp in tr.spans
                  if sp.name == "dispatch"][-2:],
    )
    out[name] = r
    if name == "fused":
        # delta SpAMM on A·A keeps two exchanges: it must not take the
        # cached shared plan, whose exchange its masks cannot prune
        c3, _ = dist_spamm(a, a, 0.0, cache, impl="fused-interpret")
        p3, _ = cache.peek(cache.last_plan_key)
        out["spamm"] = [bool(p3.exchange_shared),
                        bool(np.array_equal(np.asarray(c3.gather().to_dense()), d2))]
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def shared_results():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("case", ["fused", "ref", "bf16", "adaptive"])
def test_shared_exchange_same_bits_as_two(shared_results, case):
    r = shared_results[case]
    assert r["same_bits"]
    assert r["oracle_ratio"] <= 1.0
    assert r["shared"] == [True, False]


@pytest.mark.parametrize("case", ["fused", "ref", "bf16", "adaptive"])
def test_shared_plan_ships_the_union_once(shared_results, case):
    r = shared_results[case]
    (b_shared, b_two), (a_shared, a_two) = r["b_offsets"], r["a_offsets"]
    assert b_shared == [] and a_shared
    # A·B on two stores plans both exchanges, as before
    assert a_two and b_two
    shared, union, two = r["padded"]
    assert all(x == union for x in shared) and union > 0
    assert all(u < t for u, t in zip(shared, two))


def test_shared_key_and_executable_apart(shared_results):
    r = shared_results
    assert r["moved"] > 0  # the balancer laid A out for A·A
    assert r["keys_differ"]
    assert all(r[c]["other_executable"] for c in ("fused", "ref", "bf16", "adaptive"))


def test_shared_exchanges_counter_and_span_arg(shared_results):
    for case in ("fused", "ref", "bf16", "adaptive"):
        r = shared_results[case]
        assert r["counted"] == 1.0
        assert r["span_arg"] == [True, False]


def test_delta_spamm_keeps_two_exchanges(shared_results):
    shared, same = shared_results["spamm"]
    assert not shared and same
