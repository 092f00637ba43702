"""The growing-dense-block cell: its structure against the library's family,
and a toy of the cell on four virtual devices, driven as the command drives
it, with the operand laid out by the library and left as placed."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench.families.growing import growing_coords
from bench.harness import load_json
from bench.structure import product_tasks

from repro.core.families import GROWING_BLOCK_SIZE, SIZES, structure_coords

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
CONFIG = os.path.join("bench", "configs", "table1-growing-200k.json")


def test_growing_coords_match_the_library():
    cfg = load_json(ROOT, CONFIG)
    assert (cfg["n"], cfg["growing_block_size"]) == (SIZES[1], GROWING_BLOCK_SIZE[1])
    coords = growing_coords(cfg)
    np.testing.assert_array_equal(coords, structure_coords("growing", 200_000, 1, 512))
    assert len(coords) == 6_097
    assert product_tasks(coords, coords) == 120_081


_FOUR = r"""
import json, sys, time
import jax
import numpy as np
import repro.dist as rd
from bench import harness
from bench.families import growing, table1
from bench.resident import fetch_blocks
from repro.core.distributed import make_worker_mesh
from repro.core.schedule import plan_stats
from repro.dist.balance import peek_last_plan

assert len(jax.devices()) == 4
ROOT, SEED = sys.argv[1], 2**33 + 5
with open(ROOT + "/BENCHMARK.json") as f:
    spec = harness.cell_spec(json.load(f), "spgemm.growing-200k.4chip")
cfg = harness.load_json(ROOT, spec["config_entry"]["file"])
# the cell's limits and check, at a size the CPU holds: 82 blocks, 302 tasks,
# the dense corner weighs 1.31 times the mean chip under uniform owners
cfg.update(n=3072, leaf_size=128, half_width=128, growing_block_size=600,
           check_blocks=8)
traffic = harness.load_json(ROOT, "bench", "traffic", spec["cell"]["traffic"] + ".json")
traffic["warmup_calls"] = 1

def run(impl="fused", trace=False):
    return harness.run_cell(spec, cfg, traffic, seed=SEED, seconds=0.0, trace=trace,
                            t_start=time.perf_counter(), require_tpu=False, impl=impl)

out = {"sound": run("fused-interpret", trace=True)}

# the product on the re-laid-out operand and on the operand as placed
mesh = make_worker_mesh(4)
got, relayout = {}, rd.relayout_for_product
for name in ("relaid", "uniform"):
    if name == "uniform":
        rd.relayout_for_product = lambda a, b, cache=None, **_: (a, b)
    cell = growing.build(cfg, mesh, SEED)
    cell.call()
    every = [tuple(ij) for ij in cell.c.coords.tolist()]
    got[name] = dict(owner=cell.a.owner.tolist(), blocks=fetch_blocks(cell.c, every),
                     padded=plan_stats(peek_last_plan(cell.cache))["recv_bytes_padded_mean"])
    if name == "uniform":
        out["uniform_checks"] = {n: [v, lim] for n, v, lim in cell.check(cell.collect())}
    cell.release()
rd.relayout_for_product = relayout
r, u = got["relaid"], got["uniform"]
out["moved"] = int(np.sum(np.asarray(r["owner"]) != np.asarray(u["owner"])))
out["same_blocks"] = sorted(r["blocks"]) == sorted(u["blocks"])
out["err_u"] = max(float(np.linalg.norm(r["blocks"][ij] - u["blocks"][ij])
                         / np.linalg.norm(u["blocks"][ij]) / table1.U32) for ij in u["blocks"])
out["padded"] = [r["padded"], u["padded"]]

# fault: the exchange between chips left out (every ppermute delivers zeros)
jax.lax.ppermute = lambda x, axis_name, perm: jax.numpy.zeros_like(x)
jax.clear_caches()
out["no_exchange"] = run()
print("RESULT " + json.dumps(out))
"""


def test_growing_product_on_four_virtual_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(ROOT, "src"), ROOT])
    proc = subprocess.run([sys.executable, "-c", _FOUR, ROOT],
                          env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    sound = out["sound"]
    limits = load_json(ROOT, CONFIG)["limits"]
    assert sound["correct"], sound["checks"]
    assert sound["device"]["count"] == 4
    # set-up moved blocks, and each product received the plan's padded bytes
    metrics = {k: v["value"] for k, v in sound["metrics"].items()}
    assert metrics["migrated_gib.setup"] > 0
    assert metrics["exchange_gib.product.4chip"] * 2**30 == pytest.approx(out["padded"][0])
    assert out["padded"][0] < out["padded"][1]
    # the operand left as placed gives a correct product that agrees with
    # the re-laid-out one within the cell's limits
    assert out["moved"] > 0 and out["same_blocks"]
    assert all(v <= lim for v, lim in out["uniform_checks"].values()), out["uniform_checks"]
    assert out["err_u"] <= limits["c_err_u"]
    no_exchange = out["no_exchange"]
    assert not no_exchange["correct"]
    assert no_exchange["checks"]["c_err_u"]["value"] > limits["c_err_u"]
