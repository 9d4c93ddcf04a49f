"""The program-span readers on a profile recorded on a TPU v5e: three
decode-only engine steps and one that prefilled, the program's own
record of its spans (``repro.obs.profiled_spans``) over those steps,
and the profiler's ``serve.`` events of the same spans."""
import collections
import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench, program, trace  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
SPAN_NAMES = ("serve.step", "serve.prefill", "serve.prefill.wait",
              "serve.decode", "serve.decode.wait")
READERS = ("serve.prefill.idle_ms_per_request",
           "serve.decode.idle_ms_per_step",
           "serve.prefill.device_ms_per_1k_tokens")


@pytest.fixture(scope="module")
def recorded():
    return json.loads((FIXTURES / "v5e_serve_spans.json").read_text())


@pytest.fixture
def outcome(recorded, monkeypatch):
    """The run as the readers see it, with the program's span record as
    the process held it after the run."""
    import repro.obs.core as core
    monkeypatch.setattr(core, "_PROFILED", collections.deque(
        tuple(r) for r in recorded["program"]))
    return types.SimpleNamespace(
        trace=trace.Trace.from_events(recorded["rows"]),
        observed={"steps": recorded["steps"]})


def _read(name, outcome):
    return bench.load_module(bench.HERE / "metrics"
                             / f"{name}.py").read(outcome, None)


@pytest.mark.parametrize("name", SPAN_NAMES)
def test_program_spans_land_on_the_profilers_own(outcome, recorded, name):
    """Mapped through the steps' clocks, the program's record of each span
    agrees with the profiler's event for it."""
    mapped = program.spans(outcome, name)
    events = sorted((s * 1e-9, (s + d) * 1e-9, stats)
                    for n, s, d, stats in recorded["profiler_spans"]
                    if n == name)
    assert mapped and len(mapped) == len(events)
    for (_, a, b, attrs), (ea, eb, stats) in zip(mapped, events):
        assert abs(a - ea) < 2e-5 and abs(b - eb) < 2e-5
        assert attrs == stats


def test_span_readers_on_recorded_trace(outcome):
    values = {name: _read(name, outcome) for name in READERS}
    # the prefill's host work holds the device idle for most of its span;
    # a decode leaves about 3 ms of its 23 ms span idle
    assert values == pytest.approx({
        "serve.prefill.idle_ms_per_request": 158.50077,
        "serve.decode.idle_ms_per_step": 2.9202572,
        "serve.prefill.device_ms_per_1k_tokens": 170.99928}, rel=1e-6)
    # measured inside the prefill's span, the device time agrees with
    # the older reading: the prefill step's busy less a decode step
    assert values["serve.prefill.device_ms_per_1k_tokens"] == pytest.approx(
        _read("prefill.device_ms_per_1k_tokens", outcome), rel=1e-3)


def test_idle_in_program_spans_covers_the_step_idle(outcome):
    """Nearly all of the device's idle time inside the benchmark's step
    spans lies inside the program's prefill and decode spans."""
    t = outcome.trace
    steps = [(n, a, b, {}) for n, a, b in t.spans
             if n.startswith("cb.step")]
    inner = program.spans(outcome, "serve.prefill") + program.spans(
        outcome, "serve.decode")
    assert program.idle_seconds(t, inner) >= 0.9 * program.idle_seconds(
        t, steps)


def test_span_readers_read_nothing_from_an_older_program(outcome,
                                                         monkeypatch):
    from repro import obs
    monkeypatch.delattr(obs, "profiled_spans")
    assert program.recorded() is None
    assert all(_read(name, outcome) is None for name in READERS)


def test_spans_outside_the_traced_steps_are_left_out(outcome, recorded):
    import repro.obs.core as core
    before = len(program.spans(outcome, "serve.decode"))
    t0 = recorded["steps"][0]["t0"]
    core._PROFILED.appendleft(("serve.decode", t0 - 1.0, t0 - 0.5,
                               {"active": 1}))
    assert len(program.spans(outcome, "serve.decode")) == before == 4
