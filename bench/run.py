"""Run one cell of the benchmark and print its result line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  Loads, warms up, measures for ``--seconds``,
checks the result against the plain reference, and prints one JSON object
as the last line of standard output (the numbers compared, each beside its
limit, are also the last lines of standard error).  Exits 2 and prints no
result when the checkout has no ``src/repro``, when JAX finds no TPU or
fewer chips than the cell asks for, or when the chip is not in
``bench/peaks.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no repro package under {src}; run from a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    # the compile cache sits at a fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    # libtpu writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    spec = harness.cell_spec(bench, args.workload)
    cfg = harness.load_json(ROOT, spec["config_entry"]["file"])
    traffic = harness.load_json(harness.BENCH, "traffic", spec["cell"]["traffic"] + ".json")

    from repro.compile_cache import enable_compilation_cache

    enable_compilation_cache(ROOT)
    try:
        result = harness.run_cell(spec, cfg, traffic, seed=args.seed, seconds=args.seconds,
                                  trace=bool(args.trace), t_start=T_START)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
