"""Each cell's set-up, timed call and check at a toy size on the CPU, with the
Pallas kernels in interpret mode; the four-chip cell on four virtual devices.
The command itself refuses the CPU (test_bench_spec.py)."""

import json
import os
import subprocess
import sys

import pytest

from bench_toy import ROOT, one_chip_cells, run_toy


@pytest.mark.parametrize("workload", one_chip_cells())
def test_one_chip_cell_rehearsal(workload):
    r = run_toy(workload, impl="fused-interpret", trace=True)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["device"]["count"] == 1
    assert list(r)[-1] == "checks"
    # no TPU plane on the CPU: the trace-read metrics have nothing to read,
    # the program's counters still report
    names = set(r["metrics"])
    assert not any("roofline" in n or "idle" in n for n in names)
    assert any(n.startswith("compiles_in_window") for n in names)
    r0 = run_toy(workload)
    assert r0["correct"], r0["checks"]
    assert {"setup_s", "peak_hbm_gib"} <= set(r0["metrics"])


_FOUR = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
import jax
from bench_toy import run_toy
assert len(jax.devices()) == 4
# the banded product, Morton-partitioned over four devices with the p2p
# exchange, as a four-chip cell runs it
out = {"sound": run_toy("spgemm.banded-100k", impl="fused-interpret", chips=4)}
# fault: the exchange between chips left out (every ppermute delivers zeros)
jax.lax.ppermute = lambda x, axis_name, perm: jax.numpy.zeros_like(x)
jax.clear_caches()
out["no_exchange"] = run_toy("spgemm.banded-100k", chips=4)
print("RESULT " + json.dumps(out))
"""


def test_banded_product_on_four_virtual_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    here = os.path.dirname(__file__)
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(ROOT, "src"), ROOT])
    proc = subprocess.run([sys.executable, "-c", _FOUR, here], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    sound = out["sound"]
    assert sound["correct"], sound["checks"]
    assert sound["device"]["count"] == 4
    assert not out["no_exchange"]["correct"]
    assert out["no_exchange"]["checks"]["c_err_u"]["value"] > sound["checks"]["c_err_u"]["limit"]
