"""``BENCHMARK.json`` keeps the shape the benchmark's contract asks for,
and every name in it has the files the harness looks up by that name."""
import re

import pytest

import cb_rehearsal  # noqa: F401  (puts the checkout on sys.path)
from chipbench import bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return bench.spec()


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"][0] == "python3"
    assert 1 <= spec["run_seconds"] <= 51
    assert (bench.ROOT / "BENCHMARK.json").stat().st_size < 64 * 1024


def test_configs(spec):
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        f = bench.ROOT / c["file"]
        cfg = bench.load_json(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        bench.arch_config(cfg)
        assert any(w["config"] == c["name"] for w in spec["workloads"])


def test_workloads_have_their_files(spec):
    seen = set()
    four = 0
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
        tr = bench.load_json(bench.HERE / "traffic" / f"{w['traffic']}.json")
        assert (bench.HERE / "drivers" / f"{tr['driver']}.py").is_file()
        checks = bench.load_json(bench.HERE / "checks" / f"{w['name']}.json")
        assert all("limit" in v for k, v in checks.items()
                   if isinstance(v, dict))
    assert four <= max(1, len(spec["workloads"]) // 2)


def test_metrics(spec):
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    names = set()
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        assert m["name"] not in names
        names.add(m["name"])
    for m in spec["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert (bench.HERE / "metrics" / f"{m['name']}.py").is_file()
        assert m["name"].endswith("_roofline") == ("roofline" in m["name"])
    assert "setup_s" in e2e


def test_every_cell_reports_enough(spec):
    for w in spec["workloads"]:
        e2e = [m for m in bench.metrics_for(spec, "end_to_end", w["name"])]
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        per = bench.metrics_for(spec, "per_layer", w["name"])
        assert per
        reported = {m["name"] for m in e2e}
        assert all(m["moves"] in reported for m in per)
