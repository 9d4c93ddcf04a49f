"""The benchmark's arithmetic on the host: traffic, percentiles and
serving accounting, operation and byte counts, and the reduction of a
device trace recorded on a TPU v5e."""
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench, flops, stats, trace, traffic  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def _traffic(name):
    return bench.load_json(bench.HERE / "traffic" / f"{name}.json")


def _serve_traffics():
    return [p.stem for p in sorted((bench.HERE / "traffic").glob("*.json"))
            if bench.load_json(p)["driver"] == "serve"]


# -- traffic ------------------------------------------------------------------

@pytest.mark.parametrize("name", _serve_traffics())
def test_window_is_deterministic_per_seed(name):
    tr = _traffic(name)
    a = traffic.window_arrivals(tr, 2**31 + 11, 40)
    b = traffic.window_arrivals(tr, 2**31 + 11, 40)
    c = traffic.window_arrivals(tr, 5, 40)
    assert a == b
    assert a != c
    np.testing.assert_array_equal(
        traffic.token_ids(2**31 + 11, 3, 64, 1000),
        traffic.token_ids(2**31 + 11, 3, 64, 1000))


@pytest.mark.parametrize("name", _serve_traffics())
def test_every_seed_offers_the_same_work(name):
    """Sizes and gaps are one multiset for every seed, in another order."""
    tr = _traffic(name)
    a = traffic.window_arrivals(tr, 1, 40)
    b = traffic.window_arrivals(tr, 99, 40)
    assert sorted((x.prompt_len, x.new_tokens) for x in a) == \
        sorted((x.prompt_len, x.new_tokens) for x in b)
    gaps = lambda arr: sorted(np.round(np.diff([x.due for x in arr]), 9))
    assert len(a) == len(b) == round(tr["arrivals"]["rate_per_s"] * 40)
    assert abs(sum(gaps(a)) - sum(gaps(b))) < 5  # one gap may differ


@pytest.mark.parametrize("name", _serve_traffics())
def test_lengths_and_dues_stay_in_their_ranges(name):
    tr = _traffic(name)
    arr = traffic.window_arrivals(tr, 123, 40)
    sup = set(traffic.support(tr["prompt"]))
    assert all(x.prompt_len in sup for x in arr)
    out = tr["output"]
    assert all(out["min"] <= x.new_tokens <= out["max"] for x in arr)
    dues = [x.due for x in arr]
    assert dues == sorted(dues) and dues[0] == 0.0 and dues[-1] < 40
    eng = tr["engine"]
    assert max(sup) + out["max"] < eng["max_len"]


def test_support_lists_every_rounded_length():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 64,
            "max": 768, "round_to": 64}
    assert traffic.support(spec) == list(range(64, 769, 64))
    drawn = traffic.lengths(spec, 5000, np.random.default_rng(0))
    assert set(drawn.tolist()) <= set(traffic.support(spec))


def test_gamma_gaps_have_the_asked_rate_and_spread():
    g = traffic.gaps({"process": "gamma", "cv": 2.0, "rate_per_s": 3.0},
                     200_000, np.random.default_rng(1))
    assert abs(g.mean() - 1 / 3) < 0.01
    assert abs(g.std() / g.mean() - 2.0) < 0.05


# -- percentiles and serving accounting --------------------------------------

def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 99) == 99
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([3, 1, 2], 50) == 2
    assert stats.percentile([1, 2, 3, 4], 90) == 4


def _log(ident, due, times, done=True, prompt=64, n=None):
    r = stats.RequestLog(ident, due, prompt, n or len(times))
    r.token_times = list(times)
    r.done = done
    return r


def test_ttft_from_due_time_and_tpot_as_ratio_of_totals():
    logs = [_log(0, 0.0, [0.5, 0.5, 0.6, 0.7]),      # 3 gaps over 0.2 s
            _log(1, 1.0, [1.2, 2.2])]                 # 1 gap over 1.0 s
    s = stats.serve_summary(logs, drain_end=10.0)
    assert s["attempted"] == 2 and s["failed"] == 0
    assert math.isclose(s["tpot_ms"], 1e3 * (0.2 + 1.0) / 4)
    assert math.isclose(s["ttft_p90_ms"], 500.0)      # max of 500, 200


def test_unfinished_requests_fail_and_count_in_the_tail():
    logs = [_log(i, float(i), [i + 0.1, i + 0.2]) for i in range(8)]
    logs.append(_log(8, 8.0, [8.3], done=False))          # cut mid-decode
    logs.append(_log(9, 9.0, [], done=False))             # never started
    s = stats.serve_summary(logs, drain_end=19.0)
    assert s["attempted"] == 10 and s["failed"] == 2
    assert math.isclose(s["ttft_p90_ms"], 1e3 * 0.3)      # 9th of 10
    ttfts = sorted([0.1] * 8 + [0.3, 10.0])
    assert stats.percentile(ttfts, 100) == 10.0           # drain-censored
    assert math.isclose(s["tpot_ms"], 100.0)              # finished only


def test_token_gaps():
    logs = [_log(0, 1.0, [1.5, 1.5, 1.6])]
    assert stats.token_gaps(logs) == [0.0, pytest.approx(0.1)]


# -- operation and byte counts ---------------------------------------------------

def _arch(name):
    return bench.load_json(bench.HERE / "configs" / f"{name}.json")["arch"]


def test_qwen3_4b_decode_reads_8_05_gb_of_weights():
    a = _arch("qwen3-4b")
    d, hd, ff, V, L = 2560, 128, 9728, 151936, 36
    per_layer = d * hd * (32 + 8 + 8) + 32 * hd * d + 3 * d * ff \
        + 2 * d + 2 * hd
    want = 2 * (L * per_layer + d + V * d)
    assert flops.weight_bytes(a) == want
    assert 8.04e9 < want < 8.05e9
    # one slot at 1000 valid positions adds its keys and values
    kv = 2 * L * 8 * hd * 2 * 1000
    assert flops.decode_bytes(a, [1000]) == want + kv
    # 2 flops per weight per token, plus the scores and values
    per_tok = 2 * (L * (per_layer - 2 * d - 2 * hd) + V * d)
    assert flops.decode_flops(a, [1000]) == per_tok + L * 4 * 32 * hd * 1000


def test_prefill_counts_the_causal_triangle():
    a = _arch("qwen3-4b")
    S = 768
    att = 4 * 32 * 128 * S * (S + 1) / 2
    dense = 2 * flops.active_layer_params(a) * S
    assert flops.prefill_flops(a, S) == 36 * (dense + att) \
        + 2 * 151936 * 2560


# -- trace reduction -----------------------------------------------------------

@pytest.fixture(scope="module")
def v5e_trace():
    rows = json.loads((FIXTURES / "v5e_serve_trace.json").read_text())
    return trace.Trace.from_events(rows["rows"])


def test_recorded_trace_busy_and_idle(v5e_trace):
    lo, hi = v5e_trace.span("cb.traced")
    assert hi - lo == pytest.approx(0.6274, abs=1e-4)
    busy = trace.busy_seconds(v5e_trace, lo, hi)
    assert busy == pytest.approx(0.1292, abs=5e-4)
    # a loop's event covers its body and the loop's own overhead
    dev = trace.devices(v5e_trace)[0]
    leaves = trace.union([(a, b) for _, a, b in
                          trace.leaf_ops(v5e_trace.ops[dev])], lo, hi)
    assert trace.length(leaves) <= busy
    assert busy - trace.length(leaves) < 1e-4


def test_recorded_trace_per_step_busy(v5e_trace):
    dev = trace.devices(v5e_trace)[0]
    spans = [(a, b) for n, a, b in v5e_trace.spans if n != "cb.traced"]
    busy = trace.busy_in_spans(v5e_trace, dev, spans)
    # three decode-only steps: the decode program takes 20.23 ms
    for b in busy[:3]:
        assert 0.0202 < b < 0.0204
    # the prefill step: layer scan 39.5 ms, cache updates 7.7 ms, decode
    assert 0.067 < busy[3] < 0.070


def test_recorded_trace_idle_gaps_by_host_span(v5e_trace):
    lo, hi = v5e_trace.span("cb.traced")
    gaps = dict(trace.idle_by_span(v5e_trace, lo, hi))
    assert set(gaps) <= {"cb.step", "cb.step_prefill", "none"}
    # the host spends about half a second in the prefill step before the
    # layer scan reaches the device
    assert gaps["cb.step_prefill"] > 0.45
    idle = sum(gaps.values())
    assert idle == pytest.approx(hi - lo - trace.busy_seconds(
        v5e_trace, lo, hi), abs=1e-9)


def test_recorded_trace_top_ops_are_leaves(v5e_trace):
    lo, hi = v5e_trace.span("cb.traced")
    top = trace.top_ops(v5e_trace, lo, hi)
    assert len(top) == 10
    assert all(not n.startswith("while") for n, _ in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)


def test_interval_helpers():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.union([(0, 2), (1, 3)], 1.5, 2.5) == [(1.5, 2.5)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert trace.length([(0, 1), (2, 4)]) == 3
