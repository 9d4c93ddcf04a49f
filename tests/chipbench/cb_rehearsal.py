"""Runs a cell's driver on the CPU at a reduced configuration, with the
device check steered by the test (the benchmark itself refuses a host
without a TPU)."""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench  # noqa: E402

CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "source": "made up for the CPU rehearsal"}


def reduced_config(name: str) -> dict:
    cfg = bench.load_json(bench.HERE / "configs" / f"{name}.json")
    arch = bench.arch_config(cfg).reduced()
    return dict(cfg, arch=dataclasses.asdict(arch))


def make_run(cell: str, *, traffic: dict, checks: dict, seconds: float,
             seed: int = 7, trace: bool = False) -> bench.Run:
    """A run of ``cell``, an entry of ``BENCHMARK.json``."""
    import jax
    w = bench.workload(bench.spec(), cell)
    config = reduced_config(w["config"])
    return bench.Run(cell=w, config=config, arch=bench.arch_config(config),
                     traffic=traffic, checks=checks, seed=seed,
                     seconds=seconds, trace=trace,
                     devices=jax.devices()[:w["chips"]],
                     t_process=time.monotonic(),
                     clock=bench.CompileClock(), peaks=CPU_PEAKS)


def drive(run: bench.Run, readers=()):
    """The driver's outcome and the result line ``run.py`` would print;
    ``readers`` names per-layer metrics to read besides the cell's own."""
    runpy = bench.load_module(bench.HERE / "run.py")
    driver = bench.load_module(bench.HERE / "drivers"
                               / f"{run.traffic['driver']}.py")
    outcome = driver.run(run)
    result = runpy.report(bench.spec(), run, outcome)
    for name in readers:
        value = bench.load_module(bench.HERE / "metrics"
                                  / f"{name}.py").read(outcome, run)
        if value is not None:
            result["metrics"][name] = {"value": value}
    json.dumps(result)
    return outcome, result
