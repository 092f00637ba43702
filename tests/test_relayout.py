"""Operands laid out for their product (``repro.dist.relayout_for_product``).

Runs in a subprocess with four virtual CPU devices, as tests/test_dist.py
does: a band plus a dense corner block, whose uniform Morton owners leave
the corner's references on the first chips, against the plain band, which
is balanced as placed.
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import json
import jax
import numpy as np
from repro.core import BSMatrix
from repro.core.distributed import make_worker_mesh
from repro.core.schedule import plan_stats
from repro.core.spgemm import spgemm_symbolic
from repro.dist import (PlanCache, dist_multiply, rebalanced_owner, relayout_for_product,
                        scatter)
from repro.dist.balance import block_reference_weights, peek_last_plan
from repro.obs import Tracer

assert jax.device_count() == 4, jax.device_count()
mesh = make_worker_mesh(4)
rng = np.random.default_rng(3)
n, bs = 1536, 64
out = {}

def matrix(corner):
    # the band of one block either side of the diagonal, and a dense corner
    nb = n // bs
    keep = np.abs(np.arange(nb)[:, None] - np.arange(nb)[None, :]) <= 1
    keep[:corner, :corner] = True
    dense = rng.standard_normal((n, n)).astype(np.float32)
    dense *= np.kron(keep, np.ones((bs, bs), bool))
    return BSMatrix.from_dense(dense, bs), dense.astype(np.float64)

A, dense = matrix(corner=5)
dA = scatter(A, mesh)
tr = Tracer()
cache = PlanCache(tracer=tr)
a2, b2 = relayout_for_product(dA, dA, cache)
out["squared_is_one_handle"] = a2 is b2
out["moved"] = int(np.count_nonzero(a2.owner != dA.owner))
# a squared operand weighs the tasks that read it on either side
tasks = spgemm_symbolic(A.coords, A.coords)
wa, wb = block_reference_weights(tasks, A.nnzb, A.nnzb)
out["owner_from_both_sides"] = bool(np.array_equal(
    a2.owner, rebalanced_owner(A.coords, wa + wb + 1.0, 4)))
spans = {sp.name: (k, sp.parent) for k, sp in enumerate(tr.spans)}
out["repartition_inside"] = spans["dist_repartition"][1] == spans["relayout_for_product"][0]
out["migrated_bytes"] = [tr.counter("migrated_bytes").value, out["moved"] * bs * bs * 4]

C2 = dist_multiply(a2, b2, cache).gather().to_dense()
out["recv_padded"] = [tr.counter("recv_padded_bytes").value,
                      plan_stats(peek_last_plan(cache))["recv_bytes_padded_mean"] * 4]
C1 = np.asarray(dist_multiply(dA, dA, PlanCache()).gather().to_dense())
ref = dense @ dense
absprod = np.abs(dense) @ np.abs(dense)
k = n  # no more than n products accumulate into one element
out["same_as_uniform"] = bool(np.array_equal(np.asarray(C2), C1))
# fp32 accumulation of k products: |error| <= k u (|A||B|), u = 2^-24
out["oracle_ratio"] = float((np.abs(np.asarray(C2) - ref) / (k * 2.0**-24 * absprod + 1e-30)).max())

# the plain band is balanced as placed: the operands come back untouched
B, _ = matrix(corner=0)
dB = scatter(B, mesh)
tr_b = Tracer()
cache_b = PlanCache(tracer=tr_b)
x, y = relayout_for_product(dB, dB, cache_b)
dC = scatter(B, mesh)
u, v = relayout_for_product(dB, dC, cache_b)
out["balanced_untouched"] = [x is dB, y is dB, u is dB, v is dC]
out["balanced_moved"] = [tr_b.metrics_flat().get("migrated_bytes", 0),
                         [k for k in cache_b.stats()["by_kind"] if k.startswith("repartition")]]
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def relayout_results():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_relaid_out_product_equals_uniform_and_oracle(relayout_results):
    r = relayout_results
    assert r["squared_is_one_handle"] and r["moved"] > 0
    assert r["same_as_uniform"]
    assert r["oracle_ratio"] <= 1.0


def test_squared_operand_weighs_both_sides(relayout_results):
    assert relayout_results["owner_from_both_sides"]


def test_relayout_span_holds_the_repartition(relayout_results):
    r = relayout_results
    assert r["repartition_inside"]
    counted, expected = r["migrated_bytes"]
    assert counted == expected > 0


def test_balanced_layout_is_returned_untouched(relayout_results):
    r = relayout_results
    assert r["balanced_untouched"] == [True, True, True, True]
    migrated, repartitions = r["balanced_moved"]
    assert migrated == 0 and not repartitions


def test_recv_padded_bytes_counts_the_plan(relayout_results):
    counted, planned = relayout_results["recv_padded"]
    assert counted == planned > 0
