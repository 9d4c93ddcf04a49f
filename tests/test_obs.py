"""Tests for repro.obs — the observability plane.

Four families:

* **Core recording** — spans/counters/events/heartbeats land in the
  trace directory, worker processes merge into the parent run, the
  disabled path is a shared no-op object.
* **Faces** — Chrome-trace timeline export (schema + disjoint-lane
  invariants), per-component energy attribution (shares sum to 1,
  groups partition the component set), serve-metrics histograms.
* **Observational-only contract** — an obs-enabled sweep produces
  byte-identical CostReports, identical rows, and identical cache keys
  vs the same sweep with obs disabled.
* **Stats semantics** — ``RunStats.merge`` arithmetic and the
  cumulative-vs-``last_stats`` split across repeated ``run()`` calls.
"""
from __future__ import annotations

import json
import math
import os
from pathlib import Path

import pytest

from repro import obs
from repro.core import (TABLE_II_PATTERNS, default_mapping, resnet18,
                        row_wise, simulate, usecase_arch)
from repro.core.report import CostReport
from repro.core.schedule import SchedulePolicy
from repro.explore import ExploreJob, SweepRunner, sparsity_sweep
from repro.explore.runner import RunStats
from repro.obs.energy import (append_energy_csv, component_group,
                              component_rows, energy_table)
from repro.obs.metrics import ServeMetrics, StreamingHistogram
from repro.obs.timeline import (chrome_trace, check_chrome_trace,
                                write_chrome_trace)

RATIOS = (0.7, 0.8)


def _pattern_factory(r):
    return TABLE_II_PATTERNS(r, c_in=16)


@pytest.fixture(scope="module")
def arch16():
    return usecase_arch(16)


@pytest.fixture(scope="module")
def partitioned_report(arch16):
    return simulate(arch16, resnet18(32), default_mapping(arch16),
                    schedule=SchedulePolicy(policy="partitioned"))


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with recording disabled."""
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------------------
# core recording
# ---------------------------------------------------------------------------

def test_disabled_entry_points_are_shared_noops():
    assert obs.get_observer() is None or True   # env may differ; force off
    obs.disable()
    assert not obs.is_enabled()
    s1, s2 = obs.span("a"), obs.span("b", k=1)
    assert s1 is s2                              # one shared null object
    with s1:
        s1.set(x=1)
    assert obs.heartbeat("h", total=3) is s1
    obs.counter("c")                             # returns None, no write
    obs.event("e", k="v")


def test_enable_disable_roundtrip(tmp_path):
    o = obs.enable(tmp_path / "t", run_id="test-run")
    assert obs.is_enabled() and obs.get_observer() is o
    assert os.environ.get("REPRO_OBS_DIR") == str(o.dir)
    obs.disable()
    assert not obs.is_enabled()
    assert "REPRO_OBS_DIR" not in os.environ
    manifest = obs.read_manifest(tmp_path / "t")
    assert manifest["run_id"] == "test-run"
    assert manifest["obs_schema"] == obs.OBS_SCHEMA


def test_span_counter_event_recorded(tmp_path):
    with obs.enabled(tmp_path / "t"):
        with obs.span("work.block", stage="x") as sp:
            sp.set(items=3)
        obs.counter("work.count", 7, kind="unit")
        obs.event("work.done", ok=True)
    recs = obs.read_events(tmp_path / "t")
    by_name = {r["name"]: r for r in recs}
    assert by_name["work.block"]["type"] == "span"
    assert by_name["work.block"]["dur_s"] >= 0
    assert by_name["work.block"]["attrs"] == {"stage": "x", "items": 3}
    assert by_name["work.count"]["value"] == 7
    assert by_name["work.done"]["attrs"] == {"ok": True}
    # monotonic ordering of the merged stream
    ts = [r["t"] for r in recs]
    assert ts == sorted(ts)


def test_span_records_exception(tmp_path):
    with obs.enabled(tmp_path / "t"):
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("x")
    (rec,) = obs.read_events(tmp_path / "t", name="boom")
    assert rec["error"] == "ValueError"


def test_heartbeat_rate_limited_but_final_tick_always(tmp_path):
    with obs.enabled(tmp_path / "t"):
        hb = obs.heartbeat("loop", total=1000, min_interval_s=3600)
        for i in range(1000):
            hb.tick(i + 1)
    beats = obs.read_events(tmp_path / "t", name="loop.heartbeat")
    # first beat (interval forced on the first call) + the final one
    assert 1 <= len(beats) <= 2
    last = beats[-1]["attrs"]
    assert last["done"] == last["total"] == 1000
    assert last["points_per_s"] > 0


def test_env_auto_enable(tmp_path, monkeypatch):
    import repro.obs.core as core
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path / "envrun"))
    monkeypatch.setattr(core, "_OBSERVER", None)
    monkeypatch.setattr(core, "_ENV_CHECKED", False)
    assert obs.is_enabled()
    obs.event("from.env")
    obs.disable()
    assert [r["name"] for r in obs.read_events(tmp_path / "envrun")] == \
        ["from.env"]


class _FakeAnnotator:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what the
    bridge builds and calls."""

    on = False
    built: list = []

    @classmethod
    def is_enabled(cls) -> bool:
        return cls.on

    def __init__(self, name, **attrs):
        self.name, self.attrs, self.calls = name, dict(attrs), []
        type(self).built.append(self)

    def __enter__(self):
        self.calls.append("enter")
        return self

    def __exit__(self, *exc):
        self.calls.append("exit")

    def set_metadata(self, **attrs):
        self.calls.append(("set_metadata", attrs))


@pytest.fixture
def fake_annotator(monkeypatch):
    import collections

    import repro.obs.core as core
    monkeypatch.setattr(_FakeAnnotator, "built", [])
    monkeypatch.setattr(_FakeAnnotator, "on", False)
    monkeypatch.setattr(core, "_PROFILED", collections.deque(maxlen=4))
    monkeypatch.setattr(core, "_ANNOTATOR", None)   # restored afterwards
    obs.set_annotator(_FakeAnnotator)
    return _FakeAnnotator


def test_bridge_with_no_profiler_builds_nothing(fake_annotator):
    s1, s2 = obs.span("a"), obs.span("b", k=1)
    assert s1 is s2                              # the shared null object
    with s1:
        s1.set(x=1)
    assert fake_annotator.built == []
    assert obs.profiled_spans() == []


def test_bridge_with_profiler_annotates(fake_annotator):
    fake_annotator.on = True
    with obs.span("serve.step", k=1) as sp:
        with obs.span("serve.decode", active=3):
            pass
        sp.set(done=2)
    outer, inner = fake_annotator.built
    assert (outer.name, outer.attrs) == ("serve.step", {"k": 1})
    assert outer.calls == ["enter", ("set_metadata", {"done": 2}), "exit"]
    assert (inner.name, inner.attrs) == ("serve.decode", {"active": 3})
    assert inner.calls == ["enter", "exit"]
    # the in-process record: innermost ends first, times nest
    (n1, a1, b1, at1), (n2, a2, b2, at2) = obs.profiled_spans()
    assert (n1, at1) == ("serve.decode", {"active": 3})
    assert (n2, at2) == ("serve.step", {"k": 1, "done": 2})
    assert a2 <= a1 <= b1 <= b2
    # bounded: only the newest spans are kept
    for i in range(6):
        with obs.span("n", i=i):
            pass
    assert [at["i"] for _, _, _, at in obs.profiled_spans()] == [2, 3, 4, 5]


@pytest.mark.parametrize("profiler_on", [False, True])
def test_bridge_leaves_jsonl_records_unchanged(tmp_path, fake_annotator,
                                               profiler_on):
    fake_annotator.on = profiler_on
    with obs.enabled(tmp_path / "t"):
        with obs.span("work.block", stage="x") as sp:
            sp.set(items=3)
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("x")
    recs = obs.read_events(tmp_path / "t")
    assert [(r["type"], r["name"], r["attrs"], r.get("error"))
            for r in recs] == [
        ("span", "work.block", {"stage": "x", "items": 3}, None),
        ("span", "boom", {}, "ValueError")]
    assert all(set(r) == {"type", "name", "t", "dur_s", "attrs", "pid"}
               | ({"error"} if r["name"] == "boom" else set())
               for r in recs)
    assert len(fake_annotator.built) == (2 if profiler_on else 0)


# ---------------------------------------------------------------------------
# timeline export
# ---------------------------------------------------------------------------

def test_chrome_trace_schema_and_tracks(partitioned_report, tmp_path):
    doc = chrome_trace(partitioned_report)
    assert check_chrome_trace(doc) == []
    meta = doc["otherData"]
    assert meta["n_macros"] == 16
    assert meta["policy"] == "partitioned"
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"
         and e["cat"] == "op"]
    # ops land on distinct macro tracks (the acceptance criterion)
    assert len({e["tid"] for e in x}) > 1
    # critical-path lane present and consistent with the schedule
    cp = [e for e in doc["traceEvents"] if e.get("cat") == "critical-path"]
    assert {e["name"] for e in cp} == \
        set(partitioned_report.schedule.critical_path) & \
        {o.name for o in partitioned_report.schedule.ops
         if o.end > o.start}
    out = write_chrome_trace(partitioned_report, tmp_path / "t.json")
    assert check_chrome_trace(json.loads(out.read_text())) == []


def test_chrome_trace_lanes_never_overlap(partitioned_report):
    """The lane replay must put at most one op on a macro at a time."""
    doc = chrome_trace(partitioned_report)
    per_lane = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X" and e.get("cat") == "op":
            per_lane.setdefault(e["tid"], []).append((e["ts"],
                                                      e["ts"] + e["dur"]))
    post_tid = doc["otherData"]["n_macros"]
    for tid, spans in per_lane.items():
        if tid == post_tid:
            continue                    # post unit serialises by scheduler
        spans.sort()
        for (s0, e0), (s1, e1) in zip(spans, spans[1:]):
            assert s1 >= e0 - 1e-9, f"lane {tid}: [{s0},{e0}) vs [{s1},{e1})"


def test_chrome_trace_requires_schedule(arch16):
    from repro.core.costmodel import simulate_reference
    rep = simulate_reference(arch16, resnet18(32), default_mapping(arch16))
    with pytest.raises(ValueError):
        chrome_trace(rep)


def test_check_chrome_trace_flags_bad_docs():
    assert check_chrome_trace({}) != []
    assert check_chrome_trace({"traceEvents": []}) != []
    bad = {"traceEvents": [{"ph": "X", "name": "op"}]}   # missing ts/dur
    assert any("missing" in p for p in check_chrome_trace(bad))


# ---------------------------------------------------------------------------
# energy attribution (+ the satellite invariants on CostReport views)
# ---------------------------------------------------------------------------

def test_energy_shares_sum_to_one(partitioned_report):
    shares = partitioned_report.energy_shares()
    assert shares                                  # non-degenerate report
    assert all(v > 0 for v in shares.values())
    assert math.isclose(sum(shares.values()), 1.0, rel_tol=1e-9)


def test_grouped_energy_partitions_components(partitioned_report):
    rep = partitioned_report
    groups = rep.grouped_energy()
    # groups partition the ledger: totals match exactly...
    assert math.isclose(sum(groups.values()), sum(rep.energy_pj.values()),
                        rel_tol=1e-12)
    # ...and every component is claimed by exactly one group, the same
    # one repro.obs.energy reports
    for comp, pj in rep.energy_pj.items():
        g = component_group(comp)
        assert g in groups, f"{comp} classified into unknown group {g}"


def test_component_rows_align_with_report(partitioned_report):
    rows = component_rows(partitioned_report, meta={"pattern": "dense"})
    assert {r["component"] for r in rows} == set(
        partitioned_report.energy_pj)
    assert math.isclose(sum(r["share"] for r in rows), 1.0, rel_tol=1e-9)
    assert all(r["pattern"] == "dense" for r in rows)
    assert "cim_array" in energy_table(partitioned_report)


def test_append_energy_csv_accumulates(tmp_path, partitioned_report):
    path = tmp_path / "e.csv"
    rows = component_rows(partitioned_report)
    append_energy_csv(rows, path)
    append_energy_csv(rows, path)
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 1 + 2 * len(rows)         # one header only


# ---------------------------------------------------------------------------
# report/schedule satellites
# ---------------------------------------------------------------------------

def test_summary_includes_schedule_line(partitioned_report):
    s = partitioned_report.summary()
    assert "schedule[partitioned]" in s
    assert "critical-path=" in s and "macro-util=" in s


def test_summary_without_schedule_has_no_schedule_line(arch16):
    from repro.core.costmodel import simulate_reference
    rep = simulate_reference(arch16, resnet18(32), default_mapping(arch16))
    assert "schedule[" not in rep.summary()


def test_report_from_dict_roundtrip(partitioned_report):
    clone = CostReport.from_dict(
        json.loads(partitioned_report.to_json()))
    assert clone.to_json() == partitioned_report.to_json()
    assert clone.schedule.policy == "partitioned"
    assert clone.op_costs[0].name == partitioned_report.op_costs[0].name


def test_macro_time_utilization_bounds(partitioned_report, arch16):
    sched = partitioned_report.schedule
    u = sched.macro_time_utilization()
    assert 0.0 < u <= 1.0
    # a zero-length schedule reports 0, not a division error
    import dataclasses
    empty = dataclasses.replace(sched, makespan_cycles=0.0, ops=[])
    assert empty.macro_time_utilization() == 0.0


# ---------------------------------------------------------------------------
# serve metrics accumulators
# ---------------------------------------------------------------------------

def test_streaming_histogram_percentiles():
    h = StreamingHistogram()
    for v in (0.001, 0.002, 0.003, 0.004, 0.100):
        h.observe(v)
    assert h.count == 5
    assert h.min == 0.001 and h.max == 0.100
    assert math.isclose(h.mean, 0.022, rel_tol=1e-9)
    assert 0.001 <= h.percentile(50) <= 0.004
    assert h.percentile(99) <= 0.100 + 1e-9
    assert h.percentile(0) == 0.001 and h.percentile(100) == 0.100
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["p99"] >= snap["p50"]


def test_streaming_histogram_empty_and_single():
    h = StreamingHistogram()
    assert h.percentile(50) == 0.0 and h.snapshot()["count"] == 0
    h.observe(0.5)
    for p in (0, 50, 99, 100):
        assert math.isclose(h.percentile(p), 0.5, rel_tol=1e-6)


def test_serve_metrics_lifecycle():
    m = ServeMetrics()
    for _ in range(3):
        m.on_submit()
    assert m.queue_depth == 3
    for _ in range(3):
        m.on_scheduled()
        m.on_first_token(0.05)
    for _ in range(4):                   # 4 steps × 3 active slots
        m.on_step(3, 0.01)
        m.on_tokens(3, 0.01)
    for _ in range(3):
        m.on_complete()
    snap = m.snapshot()
    assert snap["requests"] == {"submitted": 3, "completed": 3,
                                "queue_depth": 0}
    assert snap["tokens_generated"] == 12
    assert snap["ttft_s"]["count"] == 3
    assert snap["token_latency_s"]["count"] == 12
    assert math.isclose(snap["tokens_per_s"], 12 / 0.04, rel_tol=1e-6)
    text = m.render_text()
    assert "serve.tokens 12" in text and "p99" in text
    json.loads(m.render_json())          # valid JSON exposition


# ---------------------------------------------------------------------------
# RunStats semantics
# ---------------------------------------------------------------------------

def test_runstats_merge_arithmetic():
    a = RunStats(requested=10, unique=6, memory_hits=2, disk_hits=1,
                 evaluated=3, workers=2, wall_s=1.5, tile_grid_hits=4,
                 tile_grid_misses=2)
    b = RunStats(requested=4, unique=2, memory_hits=2, disk_hits=0,
                 evaluated=0, workers=4, wall_s=0.5, tile_grid_hits=1,
                 tile_grid_misses=0)
    m = a.merge(b)
    assert (m.requested, m.unique, m.evaluated) == (14, 8, 3)
    assert (m.memory_hits, m.disk_hits) == (4, 1)
    assert m.workers == 4                          # max, not sum
    assert math.isclose(m.wall_s, 2.0)
    assert (m.tile_grid_hits, m.tile_grid_misses) == (5, 2)
    assert m.cache_hits == 14 - 3
    assert a.merge(RunStats()).requested == a.requested   # identity-ish


def test_runstats_cumulative_vs_last_stats():
    arch = usecase_arch(4)
    runner = SweepRunner(workers=1)
    wl_fn = lambda: resnet18(32)  # noqa: E731
    m = default_mapping(arch)
    sparsity_sweep(arch, wl_fn, {}, ratios=RATIOS, mapping=m,
                   pattern_factory=_pattern_factory, runner=runner)
    first_total = runner.stats.requested
    first_unique = runner.stats.unique
    sparsity_sweep(arch, wl_fn, {}, ratios=RATIOS, mapping=m,
                   pattern_factory=_pattern_factory, runner=runner)
    # last_stats covers only the second call; stats keeps accumulating
    assert runner.last_stats.requested == first_total
    assert runner.last_stats.evaluated == 0        # all served from cache
    assert runner.stats.requested == 2 * first_total
    # cumulative unique counts distinct keys over the runner's lifetime
    assert runner.stats.unique == first_unique


# ---------------------------------------------------------------------------
# the observational-only contract: obs on == obs off, bit for bit
# ---------------------------------------------------------------------------

def test_obs_enabled_sweep_bit_identical_and_artifacts(tmp_path):
    arch = usecase_arch(4)
    m = default_mapping(arch)
    wl_fn = lambda: resnet18(32)  # noqa: E731

    off = sparsity_sweep(arch, wl_fn, {}, ratios=RATIOS, mapping=m,
                         pattern_factory=_pattern_factory, workers=1)
    with obs.enabled(tmp_path / "run"):
        on = sparsity_sweep(arch, wl_fn, {}, ratios=RATIOS, mapping=m,
                            pattern_factory=_pattern_factory, workers=1)

    assert on.rows == off.rows                     # bit-identical rows
    # cache keys are obs-independent
    j_off = ExploreJob.simulate(arch, wl_fn().set_sparsity(row_wise(0.8)), m)
    with obs.enabled(tmp_path / "run2"):
        j_on = ExploreJob.simulate(arch,
                                   wl_fn().set_sparsity(row_wise(0.8)), m)
    assert j_on.key == j_off.key
    # byte-identical CostReports
    rep_off = simulate(arch, wl_fn().set_sparsity(row_wise(0.8)), m)
    with obs.enabled(tmp_path / "run3"):
        rep_on = simulate(arch, wl_fn().set_sparsity(row_wise(0.8)), m)
    assert rep_on.to_json() == rep_off.to_json()

    # the recorded run produced the promised artifacts
    run_dir = tmp_path / "run"
    assert (run_dir / "manifest.json").exists()
    runs = list(obs.core.iter_runs(run_dir)) if hasattr(obs, "core") else []
    ecsv = run_dir / "energy_components.csv"
    assert ecsv.exists()
    header = ecsv.read_text().splitlines()[0]
    assert "component" in header and "energy_pj" in header
    spans = obs.read_events(run_dir, name="explore.evaluate_job")
    assert len(spans) == len(on.rows) + 1          # points + shared dense
    beats = obs.read_events(run_dir, name="explore.run.heartbeat")
    assert beats and beats[-1]["attrs"]["done"] == len(on.rows) + 1


def test_worker_processes_merge_into_parent_run(tmp_path):
    """Parallel evaluation lands worker events in the same trace dir."""
    arch = usecase_arch(4)
    m = default_mapping(arch)
    wl_fn = lambda: resnet18(32)  # noqa: E731
    with obs.enabled(tmp_path / "prun"):
        res = sparsity_sweep(arch, wl_fn, {}, ratios=RATIOS, mapping=m,
                             pattern_factory=_pattern_factory, workers=2)
    spans = obs.read_events(tmp_path / "prun", name="explore.evaluate_job")
    assert len(spans) == len(res.rows) + 1
    assert len({r["pid"] for r in spans}) >= 2     # >1 process recorded
    # sequential twin matches row for row (telemetry changed nothing)
    seq = sparsity_sweep(arch, wl_fn, {}, ratios=RATIOS, mapping=m,
                         pattern_factory=_pattern_factory, workers=1)
    assert res.rows == seq.rows


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_obs_cli_timeline_and_check(tmp_path, capsys):
    from repro.obs.__main__ import main as obs_main
    out = tmp_path / "trace.json"
    rc = obs_main(["timeline", "--model", "resnet18", "--policy",
                   "partitioned", "--out", str(out)])
    assert rc == 0
    assert obs_main(["check", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert doc["otherData"]["policy"] == "partitioned"
    # corrupt it -> check fails
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": "nope"}))
    assert obs_main(["check", str(bad)]) == 1


def test_obs_cli_energy_and_report(tmp_path, capsys):
    from repro.obs.__main__ import main as obs_main
    csv_out = tmp_path / "energy.csv"
    rc = obs_main(["energy", "--model", "resnet18", "--ratio", "0.8",
                   "--csv", str(csv_out)])
    assert rc == 0
    assert csv_out.exists()
    capsys.readouterr()
    with obs.enabled(tmp_path / "rrun"):
        obs.event("x.y", n=1)
    assert obs_main(["report", str(tmp_path / "rrun")]) == 0
    out = capsys.readouterr().out
    assert "x.y" in out
    assert obs_main(["report", str(tmp_path / "missing")]) == 1
