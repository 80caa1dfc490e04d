#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/calibrate.py --cells <cell>... --seeds <n>... \
        --seconds <s> --controls <m> --out <file.json>

For every seed and cell, one run of the program at the cell's own load
and window (the lower readings), and on the first ``--controls`` seeds
the control of :mod:`chipbench.control`, answering as many requests as
the program did (the upper readings). All of it runs in one process;
each run builds its own corpus and graph, as the benchmark's runs do.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", nargs="+", required=True)
    ap.add_argument("--seeds", nargs="+", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [REPO, os.path.join(REPO, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import control, harness, spec

    bench = spec.load_benchmark()
    cells = [spec.resolve(bench, c) for c in args.cells]

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX finds no TPU; nothing was run",
              file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(HERE, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    counter = harness.CompileCounter()
    readings = {c.name: {"program": [], "bf16_control": []} for c in cells}
    for i, seed in enumerate(args.seeds):
        for cell in cells:
            sides = [("program", harness.make_engine)]
            if i < args.controls:
                sides.append(("bf16_control", control.bf16_brute_force))
            n_req = None
            for side, make in sides:
                out = harness.run_cell(
                    cell, seed, args.seconds if n_req is None else 1e9,
                    False, time.perf_counter(), make_engine=make,
                    counter=counter, max_requests=n_req)
                rec = {"seed": seed, "correct": out["correct"],
                       "attempted": out["attempted"], **{
                           k: v["value"] for k, v in out["checks"].items()},
                       **{k: v["value"] for k, v in out["metrics"].items()}}
                readings[cell.name][side].append(rec)
                print(json.dumps({"phase": "reading", "cell": cell.name,
                                  "side": side, **rec}), flush=True)
                if n_req is None:
                    n_req = out["attempted"] // cell.traffic["batch"]
    with open(args.out, "w") as f:
        json.dump(readings, f, indent=1)
    for name, sides in readings.items():
        for side, recs in sides.items():
            if recs:
                gaps = [r["dist_gap"] for r in recs]
                recalls = [r["recall_at_10"] for r in recs]
                print(json.dumps({
                    "summary": name, "side": side, "runs": len(recs),
                    "correct": sum(r["correct"] for r in recs),
                    "dist_gap_max": max(gaps), "dist_gap_min": min(gaps),
                    "recall_min": min(recalls),
                    "recall_max": max(recalls)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
