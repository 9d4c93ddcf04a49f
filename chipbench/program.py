"""The program's own spans, on the device trace's clock.

While a profiler runs, ``repro.obs`` keeps every span the program ran,
as (name, start, end, attrs) on the host's monotonic clock
(``obs.profiled_spans``).  The trace keeps only the benchmark's ``cb.``
host spans, on the profiler's clock.  Each engine step is both: a
``cb.step:<i>`` (or ``cb.step_prefill:<i>``) span in the trace and the
driver's record ``steps[i]`` with its monotonic ``t0`` and ``t1``.  A
program span inside step ``i`` is mapped linearly from [t0, t1] onto
that step's trace span.  Against the profiler's own events for the
same spans this lands within microseconds (on a v5e host: 6 µs at the
median, 42 µs at most, the time the annotation takes to open).

A program that keeps no such spans (one from before ``obs`` kept them)
gives ``None``, and the readers that use this report nothing.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from chipbench import readers
from chipbench import trace as tr

Span = Tuple[str, float, float, Dict]


def recorded() -> Optional[List[Span]]:
    """The program's profiled spans, or None where it keeps none."""
    from repro import obs
    read = getattr(obs, "profiled_spans", None)
    return None if read is None else read()


def step_clock(outcome) -> List[Tuple[float, float, float, float]]:
    """(t0, t1, start, end) of every engine step in the traced window:
    its monotonic ends from the driver, its span in the trace."""
    lo, hi = readers.traced_window(outcome)
    steps = outcome.observed["steps"]
    out = []
    for name, a, b in outcome.trace.spans:
        if (name.startswith(("cb.step:", "cb.step_prefill:"))
                and lo <= a and b <= hi):
            i = int(name.split(":")[1])
            if i < len(steps):
                out.append((steps[i]["t0"], steps[i]["t1"], a, b))
    return sorted(out)


def spans(outcome, name: str) -> Optional[List[Span]]:
    """The program's spans called ``name`` that ran inside an engine step
    of the traced window, with their times on the trace's clock; None
    where the program keeps no spans or the run took no device trace."""
    records = recorded()
    if (records is None or readers.traced_window(outcome) is None
            or not tr.devices(outcome.trace)):
        return None
    clock = step_clock(outcome)
    starts = [c[0] for c in clock]
    out = []
    for n, s0, s1, attrs in records:
        if n != name:
            continue
        k = bisect.bisect_right(starts, s0) - 1
        if k < 0 or s1 > clock[k][1]:
            continue
        t0, t1, a, b = clock[k]
        scale = (b - a) / (t1 - t0)
        out.append((n, a + (s0 - t0) * scale, a + (s1 - t0) * scale, attrs))
    return out


def idle_seconds(trace: tr.Trace, spans: List[Span]) -> float:
    """Device idle time inside ``spans``, averaged over the traced chips."""
    return sum((b - a) - tr.busy_seconds(trace, a, b)
               for _, a, b, _ in spans)
