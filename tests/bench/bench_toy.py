"""Cells of BENCHMARK.json at a size the CPU holds, for the rehearsal tests."""

import json
import os
import time

from bench import harness

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")

# sizes only, by structure: every other key (limits, family) is the cell's own
TOY = {
    "banded": dict(n=1024, leaf_size=128, half_width=200, check_blocks=8),
    "random": dict(n=1024, leaf_size=128, half_width=200, random_block_size=300,
                   check_blocks=8),
}


def one_chip_cells() -> list[str]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"] if w["chips"] == 1]


def toy_cell(workload: str, chips: int | None = None):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = harness.cell_spec(json.load(f), workload)
    if chips is not None:
        spec["cell"] = dict(spec["cell"], chips=chips)
    cfg = harness.load_json(ROOT, spec["config_entry"]["file"])
    cfg.update(TOY[cfg["structure"]])
    traffic = harness.load_json(ROOT, "bench", "traffic", spec["cell"]["traffic"] + ".json")
    traffic["warmup_calls"] = 1
    return spec, cfg, traffic


def run_toy(workload: str, *, seed: int = 2**33 + 7, trace: bool = False,
            impl: str = "fused", chips: int | None = None) -> dict:
    spec, cfg, traffic = toy_cell(workload, chips)
    return harness.run_cell(spec, cfg, traffic, seed=seed, seconds=0.0, trace=trace,
                            t_start=time.perf_counter(), require_tpu=False, impl=impl)
