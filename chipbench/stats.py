"""Arithmetic the benchmark applies to what it observed: exact
percentiles, ratios of totals and the serving accounting.

Nothing here imports JAX, so the tests check it on the host alone."""
from __future__ import annotations

import math
from typing import Dict, List, Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile: the smallest observed value with at
    least ``p`` percent of the values at or below it.  No interpolation,
    so the result is always one of the values."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0 < p <= 100:
        raise ValueError(f"percentile {p} outside (0, 100]")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(p / 100 * len(ordered)) - 1)]


class RequestLog:
    """What the load generator saw of one request.

    ``due`` is when the open loop meant to send it; ``token_times``
    holds, for each output token, the host time at which the engine
    step that produced it returned.  All times are seconds on one
    monotonic clock."""

    __slots__ = ("ident", "due", "prompt_len", "new_tokens",
                 "token_times", "done", "request")

    def __init__(self, ident: int, due: float, prompt_len: int,
                 new_tokens: int):
        self.ident = ident            # which token ids it carries
        self.due = due
        self.prompt_len = prompt_len
        self.new_tokens = new_tokens
        self.token_times: List[float] = []
        self.done = False
        self.request = None


def serve_summary(logs: Sequence[RequestLog], drain_end: float) -> Dict:
    """End-to-end serving numbers over every request due in the window.

    A request that never finished counts as failed, and in the TTFT
    tail as having waited from its due time to the end of the drain (it
    had no first token by then, so its TTFT is at least that)."""
    ttft, failed = [], 0
    decode_s, decode_tokens = 0.0, 0
    for r in logs:
        if r.done and r.token_times:
            ttft.append(r.token_times[0] - r.due)
            decode_s += r.token_times[-1] - r.token_times[0]
            decode_tokens += len(r.token_times) - 1
        else:
            failed += 1
            ttft.append((r.token_times[0] if r.token_times else drain_end)
                        - r.due)
    out = {"attempted": len(logs), "failed": failed}
    if ttft:
        out["ttft_p90_ms"] = 1e3 * percentile(ttft, 90)
    if decode_tokens:
        out["tpot_ms"] = 1e3 * decode_s / decode_tokens
    return out


def token_gaps(logs: Sequence[RequestLog]) -> List[float]:
    """Every gap between consecutive tokens of a request, in seconds
    (0 where one step returned two tokens of it)."""
    gaps: List[float] = []
    for r in logs:
        t = r.token_times
        gaps.extend(b - a for a, b in zip(t, t[1:]))
    return gaps
