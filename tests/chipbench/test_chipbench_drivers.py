"""The serving driver rehearsed end to end on the CPU at a reduced
configuration (the test steers the device check), the fault that
``correct`` must catch, and the command's refusals."""
import shutil
import subprocess

import jax

import cb_rehearsal as R
from chipbench import bench

SERVE_TRAFFIC = dict(
    engine={"slots": 2, "max_len": 64, "kv_dtype": "bfloat16"},
    prompt={"dist": "lognormal", "median": 16, "sigma": 0.5, "min": 8,
            "max": 32, "round_to": 8},
    output={"dist": "lognormal", "median": 5, "sigma": 0.5, "min": 3,
            "max": 8},
    arrivals={"process": "gamma", "cv": 2.0, "rate_per_s": 4.0},
    warm_requests=4, warm_s=0.3, drain_s=30, trace_seconds=0.5)


def _serve_run(trace=False, seed=7):
    tr = dict(bench.load_json(bench.HERE / "traffic" / "chat.json"),
              **SERVE_TRAFFIC)
    checks = bench.load_json(bench.HERE / "checks" / "qwen3-4b.chat.json")
    return R.make_run("qwen3-4b.chat", traffic=tr,
                      checks=dict(checks, sample_tokens=24),
                      seconds=1.5, trace=trace, seed=seed)


def test_serve_cell_rehearsal():
    outcome, result = R.drive(_serve_run(trace=True, seed=2**31 + 5))
    assert result["correct"], result["checks"]
    assert result["attempted"] == 6 and result["failed"] == 0
    names = set(result["metrics"])
    assert {"serve.itl_p99_ms", "serve.compiles_in_window",
            "serve.mfu"} <= names
    # no TPU plane on the CPU: the device readers find nothing to read
    assert "decode_step_roofline" not in names
    assert list(result)[-1] == "checks"
    assert outcome.observed["sample_tokens"] >= 24


def test_serve_cell_end_to_end_metrics():
    outcome, result = R.drive(_serve_run(trace=False))
    assert result["correct"]
    assert set(result["metrics"]) == {"tpot_ms", "setup_s"}
    assert outcome.metrics["ttft_p90_ms"] > 0
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_serve_altered_token_is_incorrect(monkeypatch):
    """A served token altered where the decode step produces it."""
    from repro.serve import engine
    real = engine.decode_step

    def shifted(params, tokens, cfg, cache):
        logits, cache = real(params, tokens, cfg, cache)
        return jax.numpy.roll(logits, 1, axis=-1), cache

    monkeypatch.setattr(engine, "decode_step", shifted)
    _, result = R.drive(_serve_run())
    assert not result["correct"]
    gap = result["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_run_refuses_a_host_without_tpu(capsys):
    runpy = bench.load_module(bench.HERE / "run.py")
    assert jax.devices()[0].platform != "tpu"
    for cell in bench.spec()["workloads"]:
        rc = runpy.main(["--workload", cell["name"], "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
        assert rc != 0
    assert capsys.readouterr().out == ""


def test_run_refuses_without_the_program(tmp_path):
    """A checkout that holds only the benchmark's files runs nothing."""
    spec = bench.spec()
    shutil.copy(bench.ROOT / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(bench.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cell = spec["workloads"][0]["name"]
    proc = subprocess.run(
        spec["command"] + ["--workload", cell, "--seed", "1", "--seconds",
                           "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
