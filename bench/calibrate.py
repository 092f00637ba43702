"""Readings for the limits of a cell's check: the program over many seeds, and
the control, in one process on the chip.

    python3 -m bench.calibrate --workload <name> --seeds 101-112 --control-seeds 201-203

For each seed the cell is built at its own size, driven through its timed
call ``--calls`` times (the first plans and compiles), and checked as a run
checks it.  The control is the same path with every float32 leaf product
computed in three bf16 passes (``lax.Precision.HIGH``'s split: the
hi·hi + hi·lo + lo·hi products, accumulated in float32), the step below
the configuration's ``HIGHEST`` that a later change would be tempted to
take.  The program has no such path of its own, so the control plants it
in this process only.  One JSON line per seed; the limits in the cell's
configuration are set from these readings.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def three_pass_dot(a, b):
    """float32 ``a @ b`` as three bf16 MXU passes with float32 accumulation."""
    import jax.numpy as jnp

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


@contextlib.contextmanager
def control_precision():
    """Plant :func:`three_pass_dot` as the fused leaf kernel's product."""
    import jax

    from repro.kernels import fused_leaf

    orig = fused_leaf._dot
    fused_leaf._dot = lambda a, b: (three_pass_dot(a, b) if a.dtype == "float32"
                                    else orig(a, b))
    jax.clear_caches()
    try:
        yield
    finally:
        fused_leaf._dot = orig
        jax.clear_caches()


def readings(cfg: dict, mesh, seed: int, calls: int, impl: str = "fused") -> dict:
    family = importlib.import_module(f"bench.families.{cfg['family']}")
    cell = family.build(cfg, mesh, seed, impl=impl)
    for _ in range(calls):
        cell.call()
    got = cell.collect()
    cell.release()
    return {name: value for name, value, _ in cell.check(got)}


def _seeds(text: str) -> list[int]:
    if not text:
        return []
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Readings for a cell's check limits.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--calls", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

    from bench import harness
    import jax

    from repro.compile_cache import enable_compilation_cache
    from repro.core.distributed import make_worker_mesh

    enable_compilation_cache(ROOT)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = harness.cell_spec(json.load(f), args.workload)
    cfg = harness.load_json(ROOT, spec["config_entry"]["file"])
    harness.devices_for(jax, spec["cell"]["chips"], require_tpu=True)
    mesh = make_worker_mesh(spec["cell"]["chips"])
    for label, seeds in (("program", _seeds(args.seeds)),
                         ("control", _seeds(args.control_seeds))):
        ctx = control_precision() if label == "control" else contextlib.nullcontext()
        with ctx:
            for seed in seeds:
                t0 = time.perf_counter()
                r = readings(cfg, mesh, seed, args.calls)
                print(json.dumps(dict(workload=args.workload, run=label, seed=seed,
                                      seconds=time.perf_counter() - t0, **r)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
