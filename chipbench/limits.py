#!/usr/bin/env python3
"""Readings a serving cell's limits are set from, on the chip, in one
process.

    python3 chipbench/limits.py --workload <cell> --seeds 1,2,3 \
        --seconds 15 [--control] [--rates 1.0,2.0]

For each seed it runs the cell's driver as a benchmark run does, with a
short window at the cell's own load and sizes, and prints the numbers
that decide ``correct`` (the program's readings: the lower end of each
limit).  ``--control`` also reads the control, the plain reference
computed a step below the configured precision in the program's place:
at every served position, how far the token that float8 puts first lies
below the float32 reference's best.  ``--rates`` offers each rate in
turn instead, to find the highest rate the system sustains.

Each reading is one JSON line on standard output.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default="",
                    help="serving cells: offer each of these rates (req/s) "
                         "in turn and print the end-to-end numbers, to find "
                         "the highest rate the system sustains")
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import bench

    spec = bench.spec()
    cell = bench.workload(spec, args.workload)
    config = bench.load_json(bench.config_file(spec, cell["config"]))
    traffic = bench.load_json(bench.HERE / "traffic"
                              / f"{cell['traffic']}.json")
    checks = bench.load_json(bench.HERE / "checks" / f"{cell['name']}.json")
    import jax
    devices = bench.require_accelerator(cell["chips"])
    from repro.runtime import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = bench.CompileClock()
    driver = bench.load_module(bench.HERE / "drivers"
                               / f"{traffic['driver']}.py")

    def one(seed, label, tr=traffic):
        run = bench.Run(cell=cell, config=config,
                        arch=bench.arch_config(config), traffic=tr,
                        checks=checks, seed=seed, seconds=args.seconds,
                        trace=False, devices=devices,
                        t_process=time.monotonic(), clock=clock,
                        peaks=bench.peaks(devices[0].device_kind))
        out = driver.run(run)
        print(json.dumps({"seed": seed, "reading": label,
                          "numbers": {k: v for k, (v, _)
                                      in out.compared.items()},
                          "attempted": out.attempted, "failed": out.failed,
                          "metrics": out.metrics}), flush=True)
        return out

    for rate in (float(r) for r in args.rates.split(",") if r):
        tr = dict(traffic, arrivals=dict(traffic["arrivals"],
                                         rate_per_s=rate))
        out = one(int(args.seeds.split(",")[0]), f"rate_{rate}", tr)
        steps = out.observed["steps"]
        lo, hi = out.observed["window"]
        late = [s for s in steps if s["t0"] >= hi]
        print(json.dumps({"rate": rate, "drain_s": (late[-1]["t1"] - hi)
                          if late else 0.0}), flush=True)
    if args.rates:
        return 0

    for seed in (int(s) for s in args.seeds.split(",")):
        out = one(seed, "program")
        if args.control:
            from chipbench.reference import qwen3
            gaps = qwen3.control_gaps(config["arch"], seed,
                                      out.observed["sample"])
            print(json.dumps({"seed": seed, "reading": "control_fp8",
                              "numbers": {"max_logit_gap": max(gaps)},
                              "per_request": gaps}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
