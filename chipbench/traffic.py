"""The one traffic generator.  A traffic file under ``traffic/`` gives
its parameters; this module turns them and a seed into requests.

Serving traffic is an open loop.  Every seed gets the same multiset of
request sizes and inter-arrival gaps, drawn once from the file's
``base_seed``; the run's seed only shuffles their order and draws the
token ids.  So two seeds offer the same work, in another order, and a
difference between seeds is the system's and not the draw's.
"""
from __future__ import annotations

import math
from typing import Dict, List, NamedTuple

import numpy as np


class Arrival(NamedTuple):
    due: float          # seconds after the window opens
    prompt_len: int
    new_tokens: int


def _round_up(n: np.ndarray, step: int) -> np.ndarray:
    return (np.ceil(n / step) * step).astype(np.int64)


def lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from a clipped lognormal, rounded up to a multiple
    of ``round_to`` where the spec gives one."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    x = np.clip(np.ceil(x), spec["min"], spec["max"]).astype(np.int64)
    step = spec.get("round_to", 1)
    return np.minimum(_round_up(x, step), spec["max"]) if step > 1 else x


def support(spec: Dict) -> List[int]:
    """Every length ``lengths`` can return for this spec."""
    step = spec.get("round_to", 1)
    lo = int(_round_up(np.array([spec["min"]]), step)[0])
    return list(range(lo, spec["max"] + 1, step))


def gaps(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Inter-arrival gaps at ``rate_per_s``: exponential (Poisson
    arrivals) or Gamma with coefficient of variation ``cv`` (bursts)."""
    rate = spec["rate_per_s"]
    if spec["process"] == "poisson":
        return rng.exponential(1.0 / rate, n)
    if spec["process"] == "gamma":
        shape = 1.0 / spec["cv"] ** 2
        return rng.gamma(shape, 1.0 / (rate * shape), n)
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def window_arrivals(tr: Dict, seed: int, seconds: float) -> List[Arrival]:
    """The requests due in a window of ``seconds``: ``rate × seconds`` of
    them, whose gaps are scaled to fill the window exactly."""
    n = max(1, round(tr["arrivals"]["rate_per_s"] * seconds))
    base = np.random.default_rng(tr["base_seed"])
    prompts = lengths(tr["prompt"], n, base)
    outputs = lengths(tr["output"], n, base)
    g = gaps(tr["arrivals"], n, base)
    g *= seconds / g.sum()
    rng = np.random.default_rng([seed, 1])
    order = rng.permutation(n)
    g = g[rng.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    return [Arrival(float(d), int(prompts[i]), int(outputs[i]))
            for d, i in zip(due, order)]


def warm_arrivals(tr: Dict, seed: int, n: int) -> List[Arrival]:
    """``n`` warm-up requests at the cell's rate and sizes, before the
    window opens (their dues are negative offsets from the first)."""
    rng = np.random.default_rng([seed, 2])
    prompts = lengths(tr["prompt"], n, rng)
    outputs = lengths(tr["output"], n, rng)
    g = gaps(tr["arrivals"], n, rng)
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    return [Arrival(float(d), int(p), int(o))
            for d, p, o in zip(due, prompts, outputs)]


def token_ids(seed: int, index: int, n: int, vocab: int) -> np.ndarray:
    """The prompt tokens of request ``index`` of a run: uniform ids."""
    rng = np.random.default_rng([seed, 3, index])
    return rng.integers(0, vocab, n, dtype=np.int32)
