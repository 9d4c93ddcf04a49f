"""What every cell of the benchmark shares: the spec, the files a cell
is made of, the device check, the peaks, the compile clock and the run's
outcome.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its parts
are found by name: ``configs/<config>.json`` (the model as it is run),
``traffic/<traffic>.json`` (the traffic, which names its driver),
``drivers/<driver>.py``, ``checks/<cell>.json`` (the limits that decide
``correct``) and ``metrics/<metric>.py`` (one reader per per-layer
metric).  Adding a cell or a metric adds files; it edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
SRC = ROOT / "src"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a file by path (names under ``paths`` may hold ``-`` and
    ``.``, which an import statement cannot)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def spec() -> Dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_file(bench: Dict, config: str) -> Path:
    for c in bench["configs"]:
        if c["name"] == config:
            return ROOT / c["file"]
    raise KeyError(f"no config {config!r} in BENCHMARK.json")


def arch_config(cfg: Dict):
    """The program's ``ArchConfig`` with every field the file gives."""
    from repro.configs.base import ArchConfig
    return ArchConfig(**cfg["arch"])


def metrics_for(bench: Dict, kind: str, cell: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]


def require_accelerator(chips: int) -> List:
    """The first ``chips`` TPU devices, or :class:`NoAccelerator`."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devices)}")
    return devices[:chips]


def peaks(kind: str) -> Dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


class CompileClock:
    """Backend compiles (persistent-cache reads included) and cache
    hits, from JAX's monitoring events, with the host time of each."""

    def __init__(self):
        import jax.monitoring
        self.compiles: List[float] = []       # host time at each event
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(time.monotonic())
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.compiles)


@dataclasses.dataclass
class Run:
    """One run of one cell, as the driver sees it."""
    cell: Dict
    config: Dict
    arch: Any
    traffic: Dict
    checks: Dict
    seed: int
    seconds: float
    trace: bool
    devices: List
    t_process: float
    clock: CompileClock
    peaks: Dict


@dataclasses.dataclass
class Outcome:
    """What a driver hands back.  ``metrics`` holds end-to-end values by
    name; ``compared`` maps a check's name to (number, limit), and the
    run is correct only if every number is finite and within its limit;
    ``observed`` is what the per-layer readers read."""
    attempted: int
    failed: int
    metrics: Dict[str, float]
    compared: Dict[str, tuple]
    memory_peak_bytes: int
    observed: Dict[str, Any]
    trace: Optional[Any] = None

    @property
    def correct(self) -> bool:
        return all(math.isfinite(v) and v <= lim
                   for v, lim in self.compared.values())


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
