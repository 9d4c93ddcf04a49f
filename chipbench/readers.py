"""Helpers the per-layer readers under ``metrics/`` share.

A reader is a file ``metrics/<metric>.py`` with ``read(outcome, run)``,
which returns the metric's value or ``None`` where the run holds nothing
to read (then the metric is left out of the result line)."""
from __future__ import annotations

from typing import List, Optional, Tuple

from chipbench import trace as tr


def traced_window(outcome) -> Optional[Tuple[float, float]]:
    if outcome.trace is None:
        return None
    return outcome.trace.span("cb.traced")


def traced_steps(outcome) -> List[Tuple[dict, float]]:
    """(step record, device busy seconds) for every engine step whose
    host span lies in the traced window, on the first traced chip."""
    win = traced_window(outcome)
    if win is None or not tr.devices(outcome.trace):
        return []
    lo, hi = win
    spans = [(int(name.split(":")[1]), a, b)
             for name, a, b in outcome.trace.spans
             if name.startswith(("cb.step:", "cb.step_prefill:"))
             and lo <= a and b <= hi]
    dev = tr.devices(outcome.trace)[0]
    busy = tr.busy_in_spans(outcome.trace, dev, [(a, b) for _, a, b in spans])
    steps = outcome.observed["steps"]
    return [(steps[i], s) for (i, _, _), s in zip(spans, busy)
            if i < len(steps)]


def decode_only(outcome) -> List[Tuple[dict, float]]:
    return [(st, s) for st, s in traced_steps(outcome)
            if not st["prefill"] and st["contexts"]]


def window_steps(outcome) -> List[dict]:
    lo, hi = outcome.observed["window"]
    return [st for st in outcome.observed["steps"]
            if lo <= st["t0"] and st["t1"] <= hi]


def idle_share(outcome) -> Optional[float]:
    """Percent of the traced window in which no operation ran, averaged
    over the traced chips."""
    win = traced_window(outcome)
    if win is None or not tr.devices(outcome.trace):
        return None
    lo, hi = win
    return 100.0 * (1.0 - tr.busy_seconds(outcome.trace, lo, hi) / (hi - lo))
