#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration and a traffic mix in ``BENCHMARK.json``.
With ``--trace 0`` the result line holds the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window. Without a TPU, or with fewer chips than the cell asks for,
the run exits non-zero and prints no result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
COMPILE_CACHE = os.path.join(HERE, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [REPO, os.path.join(REPO, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import harness, spec

    cell = spec.resolve(spec.load_benchmark(), args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devs)} {devs[0].platform} device(s). Nothing "
              "was run.", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
