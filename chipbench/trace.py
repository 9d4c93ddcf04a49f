"""The device profiler trace: taking it, and reducing it to intervals.

A traced run wraps part of its window in ``Capture``.  The profiler
writes an ``.xplane.pb`` under a temporary directory; ``Capture.stop``
reads it with ``jax.profiler.ProfileData`` into a ``Trace`` of plain
tuples and deletes the directory.  Everything after that is arithmetic
on ``Trace``, which the tests check on a trace recorded on a TPU v5e
(``tests/chipbench/fixtures``).

On a TPU each chip is a plane ``/device:TPU:<n>``.  Its ``XLA Ops`` line
holds one event per operation; a loop (``while``) is an event that
holds the events of its body.  The benchmark's own host spans are
``jax.profiler.TraceAnnotation`` events named ``cb.<what>`` on the
``/host:CPU`` plane.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import shutil
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "cb."
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]          # seconds, on the trace's clock


@dataclasses.dataclass
class Trace:
    """Device operations per chip and the benchmark's host spans.

    ``ops[device]`` is a list of (name, start, end), sorted by start;
    ``spans`` is a list of (name, start, end) of the ``cb.`` host spans.
    Times in seconds."""
    ops: Dict[str, List[Tuple[str, float, float]]]
    spans: List[Tuple[str, float, float]]

    @classmethod
    def from_events(cls, events: Sequence[Sequence]) -> "Trace":
        """From rows of (plane, line, name, start_ns, duration_ns)."""
        ops: Dict[str, list] = collections.defaultdict(list)
        spans = []
        for plane, line, name, start, dur in events:
            t0, t1 = start * 1e-9, (start + dur) * 1e-9
            if plane.startswith("/device:"):
                if line == OPS_LINE:
                    ops[plane].append((short_op(name), t0, t1))
            elif name.startswith(SPAN_PREFIX):
                spans.append((name, t0, t1))
        for v in ops.values():
            v.sort(key=lambda e: e[1])
        spans.sort(key=lambda e: e[1])
        return cls(dict(ops), spans)

    def span(self, name: str) -> Optional[Interval]:
        for n, a, b in self.spans:
            if n == name:
                return a, b
        return None


def short_op(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` → ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


class Capture:
    """Starts the profiler with host tracing cut to annotations and no
    Python tracer (which would record every Python call)."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.active = False

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True

    def stop(self) -> Trace:
        import jax
        jax.profiler.stop_trace()
        self.active = False
        try:
            paths = glob.glob(self.dir + "/**/*.xplane.pb", recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            data = jax.profiler.ProfileData.from_file(paths[0])
            rows = []
            for plane in data.planes:
                device = plane.name.startswith("/device:")
                for line in plane.lines:
                    if device and line.name != OPS_LINE:
                        continue
                    for e in line.events:
                        if device or e.name.startswith(SPAN_PREFIX):
                            rows.append((plane.name, line.name, e.name,
                                         e.start_ns, e.duration_ns))
            return Trace.from_events(rows)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

    def close(self) -> None:
        if self.active:
            import jax
            jax.profiler.stop_trace()
            self.active = False
        shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Sequence[Interval], lo: float = float("-inf"),
          hi: float = float("inf")) -> List[Interval]:
    """Disjoint sorted intervals covering ``intervals`` within [lo, hi]."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(base: Sequence[Interval], cut: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of the disjoint sorted ``base`` not covered by ``cut``."""
    cut = union(cut)
    out: List[Interval] = []
    for a, b in base:
        cur = a
        for c, d in cut:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
        if cur < b:
            out.append((cur, b))
    return out


def busy(trace: Trace, device: str, lo: float, hi: float) -> List[Interval]:
    """Union of the device's operations within [lo, hi]."""
    return union([(a, b) for _, a, b in trace.ops.get(device, [])], lo, hi)


def devices(trace: Trace) -> List[str]:
    return sorted(trace.ops)


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Busy time within [lo, hi], averaged over the traced chips."""
    devs = devices(trace)
    if not devs:
        return 0.0
    return sum(length(busy(trace, d, lo, hi)) for d in devs) / len(devs)


def busy_in_spans(trace: Trace, device: str, spans: Sequence[Interval]
                  ) -> List[float]:
    """For each host span, the device's busy time from the operations
    that start inside it (an operation belongs to the span that issued
    it, though it may end after the span)."""
    ops = trace.ops.get(device, [])
    starts = [a for _, a, _ in ops]
    out = []
    for lo, hi in spans:
        i, j = bisect.bisect_left(starts, lo), bisect.bisect_left(starts, hi)
        out.append(length(union([(a, b) for _, a, b in ops[i:j]])))
    return out


def leaf_ops(ops: Sequence[Tuple[str, float, float]]
             ) -> List[Tuple[str, float, float]]:
    """The operations that hold no other: a loop's event spans its body's
    events, so only the body's count toward what each operation took."""
    out = []
    for k, (name, a, b) in enumerate(ops):
        nxt = ops[k + 1][1] if k + 1 < len(ops) else float("inf")
        if nxt >= b:
            out.append((name, a, b))
    return out


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10
            ) -> List[List]:
    """The ``n`` operations that took the most device time in [lo, hi],
    summed by name over the traced chips and averaged per chip."""
    tot: Dict[str, float] = collections.defaultdict(float)
    devs = devices(trace)
    for d in devs:
        for name, a, b in leaf_ops(trace.ops[d]):
            if lo <= a < hi:
                tot[name] += b - a
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(len(devs), 1)] for k, v in ranked]


def idle_by_span(trace: Trace, lo: float, hi: float, n: int = 10
                 ) -> List[List]:
    """Idle device time in [lo, hi], summed by what the host was doing:
    each idle gap goes to the innermost ``cb.`` span that covers most of
    it (``none`` where no span does), averaged over the traced chips.
    A span named ``cb.step:12`` counts as ``cb.step``."""
    spans = [(b - a, name.split(":")[0], a, b)
             for name, a, b in trace.spans if name != "cb.traced"]
    tot: Dict[str, float] = collections.defaultdict(float)
    devs = devices(trace)
    for d in devs:
        for ga, gb in subtract([(lo, hi)], busy(trace, d, lo, hi)):
            best, label = (0.0, 0.0), "none"
            for width, name, a, b in spans:
                cover = min(b, gb) - max(a, ga)
                if cover > 0 and (cover, -width) > best:
                    best, label = (cover, -width), name
            tot[label] += gb - ga
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(len(devs), 1)] for k, v in ranked]

