#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its parts
are the files ``bench.py`` names.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, read from the
device profiler trace and the run's own host timestamps.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``busy_s`` and ``window_s`` when
traced), ``breakdown`` when traced, and last ``checks``: each number that
decided ``correct`` beside its limit, which also end standard error.

The run exits non-zero and prints no result when JAX finds no TPU or
fewer chips than the cell asks for, when the program's source (``src/``)
is missing, or when anything fails.  JAX's persistent compilation cache
is the program's own (``repro.runtime.use_compile_cache``: inside the
checkout unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise), and it
keeps every program, however quick to compile, so that only a
checkout's first run compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    if args.seconds <= 0:
        ap.error("--seconds must be > 0")
    return args


def _fail(msg: str, code: int) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    args = _args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return _fail(f"no program source under {ROOT / 'src'}", 2)
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
    try:
        devices = bench.require_accelerator(cell["chips"])
    except bench.NoAccelerator as e:
        return _fail(str(e), 3)
    peaks = bench.peaks(devices[0].device_kind)

    from repro.runtime import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run = bench.Run(cell=cell, config=config,
                    arch=bench.arch_config(config), traffic=traffic,
                    checks=checks, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), devices=devices,
                    t_process=T_PROCESS, clock=bench.CompileClock(),
                    peaks=peaks)
    driver = bench.load_module(bench.HERE / "drivers"
                               / f"{traffic['driver']}.py")
    outcome = driver.run(run)
    result = report(spec, run, outcome)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def report(spec, run, outcome) -> dict:
    """The result line of one run."""
    import math
    from chipbench import bench, trace as tr

    cell = run.cell["name"]
    metrics = {}
    if not run.trace:
        for m in bench.metrics_for(spec, "end_to_end", cell):
            if m["name"] in outcome.metrics:
                metrics[m["name"]] = {"value": outcome.metrics[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in bench.metrics_for(spec, "per_layer", cell):
            reader = bench.load_module(bench.HERE / "metrics"
                                       / f"{m['name']}.py")
            value = reader.read(outcome, run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = run.devices[0]
    import jax
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": metrics,
              "device": device}
    if run.trace and outcome.trace is not None:
        lo, hi = outcome.trace.span("cb.traced")
        device["busy_s"] = tr.busy_seconds(outcome.trace, lo, hi)
        device["window_s"] = hi - lo
        result["breakdown"] = {
            "device_ops": tr.top_ops(outcome.trace, lo, hi),
            "idle_gaps": tr.idle_by_span(outcome.trace, lo, hi)}
    result["checks"] = {
        k: {"value": v if math.isfinite(v) else None, "limit": lim}
        for k, (v, lim) in outcome.compared.items()}
    return result


if __name__ == "__main__":
    sys.exit(main())
