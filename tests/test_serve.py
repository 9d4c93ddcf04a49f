"""Serving engine: slot management, per-slot positions, determinism."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models.transformer import decode_step, init_params, prefill
from repro.serve.engine import Request, ServeEngine

CFG = get_config("llama3-8b").reduced()


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _served_reference(cfg, params, prompt, n_new, max_len):
    """Sequential batch-1 greedy decode of any served config: the
    prefill's K/V (if any) padded to ``max_len``, SSM state as is."""
    logits, cache = prefill(params, jnp.asarray(prompt, jnp.int32)[None], cfg)
    for key in ("k", "v"):
        if key in cache:
            pad = max_len - cache[key].shape[2]
            cache[key] = jnp.pad(cache[key],
                                 ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    out = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(n_new - 1):
        lg, cache = decode_step(params, jnp.asarray([out[-1]], jnp.int32),
                                cfg, cache)
        out.append(int(jnp.argmax(lg[0])))
    return out


def _greedy_reference(params, prompt, n_new):
    """Sequential batch-1 reference decode."""
    return _served_reference(CFG, params, prompt, n_new, 64)


def test_engine_matches_reference(params):
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, CFG.vocab_size, size=n).astype(np.int32)
               for n in (5, 9)]
    engine = ServeEngine(CFG, params, slots=2, max_len=64)
    reqs = [Request(prompt=p, max_new_tokens=6) for p in prompts]
    for r in reqs:
        engine.submit(r)
    engine.run()
    for r, p in zip(reqs, prompts):
        assert r.done and len(r.output) == 6
        ref = _greedy_reference(params, p, 6)
        assert r.output == ref, (r.output, ref)


@pytest.mark.parametrize("name", ["llama3-8b", "mamba2-130m", "hymba-1.5b"])
def test_engine_matches_sequential_reference_per_cache_kind(name):
    """K/V only, SSM only and hybrid: the slot is a traced index into
    every cache entry the config has, and a refilled slot is written
    over, so each request's tokens match its batch-1 greedy decode."""
    cfg = get_config(name).reduced()
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (6, 11, 6)]
    engine = ServeEngine(cfg, params, slots=2, max_len=32)
    reqs = [Request(prompt=p, max_new_tokens=n)
            for p, n in zip(prompts, (4, 6, 5))]
    for r in reqs:
        engine.submit(r)
    engine.run()
    for r, p in zip(reqs, prompts):
        assert r.done
        assert r.output == _served_reference(cfg, params, p,
                                             r.max_new_tokens, 32)


def test_decode_donates_the_cache(params):
    """The decode program takes the engine's cache as a donated
    argument: after a decode-only step the previous K buffer is gone
    (the step wrote into it in place), and the tokens are unchanged."""
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, CFG.vocab_size, size=n).astype(np.int32)
               for n in (7, 12)]
    engine = ServeEngine(CFG, params, slots=2, max_len=64)
    reqs = [Request(prompt=p, max_new_tokens=5) for p in prompts]
    for r in reqs:
        engine.submit(r)
    engine.step()                       # prefills both slots, decodes
    k = engine.cache["k"]
    assert not engine.queue
    engine.step()                       # decode only
    assert k.is_deleted()
    assert not engine.cache["k"].is_deleted()
    engine.run()
    for r, p in zip(reqs, prompts):
        assert r.done and r.output == _greedy_reference(params, p, 5)


def test_prefill_traces_once_per_prompt_length(params, monkeypatch,
                                               tmp_path):
    """The prefill program is traced once per distinct prompt length,
    never once per slot, and the ``serve.prefill`` span's ``compiled``
    is 1 on exactly the first request of each length."""
    from repro import obs
    from repro.serve import engine as engine_mod

    traced = []
    real = engine_mod.prefill

    def counting(p, tokens, cfg):
        traced.append(tokens.shape[1])
        return real(p, tokens, cfg)

    monkeypatch.setattr(engine_mod, "prefill", counting)
    rng = np.random.default_rng(12)
    lengths = (4, 4, 9, 4, 9, 9, 4)
    engine = ServeEngine(CFG, params, slots=2, max_len=48)
    reqs = [Request(prompt=rng.integers(0, CFG.vocab_size, size=n)
                    .astype(np.int32), max_new_tokens=2 + i % 3)
            for i, n in enumerate(lengths)]
    for r in reqs:
        engine.submit(r)
    with obs.enabled(tmp_path / "obs"):
        engine.run()
        spans = obs.read_events(tmp_path / "obs", "serve.prefill")
    assert all(r.done for r in reqs)
    assert sorted(traced) == [4, 9]
    attrs = [ev["attrs"] for ev in spans]
    assert [a["tokens"] for a in attrs] == list(lengths)
    assert {a["slot"] for a in attrs} == {0, 1}
    assert [a["compiled"] for a in attrs] == [1, 0, 1, 0, 0, 0, 0]
    for r in reqs:
        assert r.output == _greedy_reference(params, r.prompt,
                                             r.max_new_tokens)


def test_free_slots_read_no_kv_and_the_kernel_serves_the_same_tokens(
        params, monkeypatch, tmp_path):
    """Each decode hands a free slot position -1 (length 0: no K/V read
    or written); ``serve.decode`` reports ``kv_read_share``, the live
    slots' lengths rounded up to the kernel's block over slots x
    max_len (1.0 on the XLA path); and the engine serves the same
    tokens through the XLA path and the interpreted decode kernel."""
    from repro import obs
    from repro.kernels import ops
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, CFG.vocab_size, size=n).astype(np.int32)
               for n in (5, 40, 9)]
    slots, max_len = 3, 96
    blk = ops.decode_attention_block(max_len)
    assert blk == 32

    def serve(impl):
        monkeypatch.setattr(ops, "resolve_decode_attention",
                            lambda *_: impl)
        engine = ServeEngine(CFG, params, slots=slots, max_len=max_len)
        calls, real = [], engine._decode

        def spy(p, t, c):
            calls.append((np.asarray(c["pos"]),
                          [r is not None for r in engine.slot_req]))
            return real(p, t, c)

        engine._decode = spy
        reqs = [Request(prompt=p, max_new_tokens=n)
                for p, n in zip(prompts, (4, 7, 3))]
        engine.submit(reqs[0])
        engine.submit(reqs[1])
        engine.step()
        engine.submit(reqs[2])
        with obs.enabled(tmp_path / impl):
            engine.run()
            spans = obs.read_events(tmp_path / impl, "serve.decode")
        shares = [ev["attrs"]["kv_read_share"] for ev in spans]
        return reqs, calls, shares

    outs = {}
    for impl in ("ref", "pallas_interpret"):
        reqs, calls, shares = serve(impl)
        assert all(r.done for r in reqs)
        outs[impl] = [r.output for r in reqs]
        assert any(not all(busy) for _, busy in calls)
        for pos, busy in calls:
            assert np.all((pos == -1) == ~np.asarray(busy)), (pos, busy)
        want = [float((-(-(pos + 1) // blk) * blk).sum())
                / (slots * max_len) for pos, _ in calls[-len(shares):]]
        assert shares == (want if impl != "ref" else [1.0] * len(shares))
    assert 0 < min(want) and max(want) < 1
    assert outs["ref"] == outs["pallas_interpret"]
    for out, p, r in zip(outs["ref"], prompts, reqs):
        assert out == _served_reference(CFG, params, p, r.max_new_tokens,
                                        max_len)


def test_more_requests_than_slots(params):
    rng = np.random.default_rng(1)
    engine = ServeEngine(CFG, params, slots=2, max_len=48)
    reqs = [Request(prompt=rng.integers(0, CFG.vocab_size, size=4)
                    .astype(np.int32), max_new_tokens=3) for _ in range(5)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done and len(r.output) == 3 for r in reqs)


def test_heterogeneous_prompt_lengths(params):
    """Slots at different positions must decode independently."""
    rng = np.random.default_rng(2)
    pa = rng.integers(0, CFG.vocab_size, size=3).astype(np.int32)
    pb = rng.integers(0, CFG.vocab_size, size=17).astype(np.int32)
    engine = ServeEngine(CFG, params, slots=2, max_len=64)
    ra, rb = Request(prompt=pa, max_new_tokens=5), Request(prompt=pb,
                                                           max_new_tokens=5)
    engine.submit(ra)
    engine.submit(rb)
    engine.run()
    assert ra.output == _greedy_reference(params, pa, 5)
    assert rb.output == _greedy_reference(params, pb, 5)


def test_engine_metrics_cumulative_vs_last_stats(params):
    """metrics accumulates across run() calls; last_stats is per-call."""
    rng = np.random.default_rng(3)

    def _submit(engine, n, toks):
        reqs = [Request(prompt=rng.integers(0, CFG.vocab_size, size=4)
                        .astype(np.int32), max_new_tokens=toks)
                for _ in range(n)]
        for r in reqs:
            engine.submit(r)
        return reqs

    engine = ServeEngine(CFG, params, slots=2, max_len=48)
    _submit(engine, 3, 4)
    engine.run()
    first = dict(engine.last_stats)
    assert first["requests_completed"] == 3
    assert first["tokens_generated"] == 3 * 4     # prefill token + decodes
    assert first["steps"] > 0 and first["wall_s"] > 0
    snap1 = engine.stats_snapshot()
    assert snap1["requests"] == {"submitted": 3, "completed": 3,
                                 "queue_depth": 0}
    assert snap1["ttft_s"]["count"] == 3
    assert snap1["token_latency_s"]["count"] > 0

    _submit(engine, 2, 3)
    engine.run()
    # last_stats covers only the second call...
    assert engine.last_stats["requests_completed"] == 2
    assert engine.last_stats["tokens_generated"] == 2 * 3
    # ...while the engine-lifetime metrics keep cumulating
    snap2 = engine.stats_snapshot()
    assert snap2["requests"] == {"submitted": 5, "completed": 5,
                                 "queue_depth": 0}
    assert snap2["tokens_generated"] == 3 * 4 + 2 * 3
    assert snap2["steps"] == first["steps"] + engine.last_stats["steps"]
    assert snap2["ttft_s"]["count"] == 5
    text = engine.stats_text()
    assert "serve.requests submitted=5 completed=5" in text
    assert "p99" in text


def test_engine_metrics_do_not_change_outputs(params):
    """Instrumented engine output still matches the batch-1 reference."""
    rng = np.random.default_rng(4)
    p = rng.integers(0, CFG.vocab_size, size=6).astype(np.int32)
    engine = ServeEngine(CFG, params, slots=1, max_len=48)
    r = Request(prompt=p, max_new_tokens=4)
    engine.submit(r)
    engine.run()
    assert r.output == _greedy_reference(params, p, 4)
    snap = engine.stats_snapshot()
    assert snap["ttft_s"]["p50"] > 0
    assert snap["tokens_per_s"] > 0


def test_queue_full_backpressure(params):
    """Bounded admission: submits past max_queue are rejected with a
    structured reason and never perturb the admitted requests."""
    rng = np.random.default_rng(7)
    engine = ServeEngine(CFG, params, slots=1, max_len=48, max_queue=2)
    reqs = [Request(prompt=rng.integers(0, CFG.vocab_size, size=4)
                    .astype(np.int32), max_new_tokens=3) for _ in range(3)]
    assert engine.submit(reqs[0]) is True
    assert engine.submit(reqs[1]) is True
    assert engine.submit(reqs[2]) is False
    assert reqs[2].reject_reason == "queue_full"
    assert reqs[2].output is None and not reqs[2].done
    assert engine.metrics.requests_rejected == 1
    assert engine.metrics.queue_depth == 2   # rejected one never entered

    engine.run()
    for r in reqs[:2]:
        assert r.done
        assert r.output == _greedy_reference(params, r.prompt, 3)
    snap = engine.stats_snapshot()
    assert snap["requests"]["submitted"] == 2
    assert snap["requests"]["completed"] == 2
    assert snap["failures"] == {"rejected": 1, "expired": 0}
    assert "rejected=1 expired=0" in engine.stats_text()


def test_deadline_drops_queued_request(params):
    """A request whose deadline lapses while queued is dropped before
    prefill; requests ahead of it are unaffected."""
    rng = np.random.default_rng(8)
    engine = ServeEngine(CFG, params, slots=1, max_len=48)
    ok = Request(prompt=rng.integers(0, CFG.vocab_size, size=4)
                 .astype(np.int32), max_new_tokens=3)
    late = Request(prompt=rng.integers(0, CFG.vocab_size, size=4)
                   .astype(np.int32), max_new_tokens=3, deadline_s=0.0)
    assert engine.submit(ok) and engine.submit(late)
    engine.run()

    assert ok.done
    assert ok.output == _greedy_reference(params, ok.prompt, 3)
    assert not late.done
    assert late.reject_reason == "deadline"
    assert late.output == []                 # admitted but never prefilled
    assert engine.metrics.requests_expired == 1
    snap = engine.stats_snapshot()
    assert snap["requests"]["queue_depth"] == 0
    assert snap["failures"] == {"rejected": 0, "expired": 1}


def test_deadline_cuts_off_mid_decode(params):
    """A deadline crossed mid-decode keeps the partial output, frees the
    slot, and counts as expired — the engine keeps draining."""
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, CFG.vocab_size, size=4).astype(np.int32)
    engine = ServeEngine(CFG, params, slots=1, max_len=48)
    req = Request(prompt=prompt, max_new_tokens=20, deadline_s=5.0)
    assert engine.submit(req)
    engine.step()                            # prefill + first decode step
    assert len(req.output) == 2
    req.submit_t -= 10.0                     # force the deadline to lapse
    engine.step()

    assert not req.done
    assert req.reject_reason == "deadline"
    assert req.output == _greedy_reference(params, prompt, 3)  # partial
    assert all(r is None for r in engine.slot_req)
    assert engine.metrics.requests_expired == 1
    assert engine.metrics.requests_completed == 0
    engine.run()                             # nothing left; terminates
    assert engine.last_stats["steps"] == 0
    snap = engine.stats_snapshot()
    assert snap["requests"]["queue_depth"] == 0
    assert snap["failures"] == {"rejected": 0, "expired": 1}


def _profiled_run(params, tmp_path):
    """Two requests through a 2-slot engine under the profiler, as the
    benchmark takes a trace (host annotations only, no Python tracer);
    returns the requests and the ``serve.`` host events as
    (name, start_ns, end_ns, stats)."""
    import glob

    rng = np.random.default_rng(5)
    engine = ServeEngine(CFG, params, slots=2, max_len=48)
    reqs = [Request(prompt=rng.integers(0, CFG.vocab_size, size=n)
                    .astype(np.int32), max_new_tokens=3) for n in (4, 7)]
    engine.submit(reqs[0])
    engine.step()                         # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        engine.submit(reqs[1])
        engine.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   dict(e.stats)))
    return reqs, events


def test_engine_spans_nest_in_the_profiler_trace(params, tmp_path):
    reqs, events = _profiled_run(params, tmp_path)
    assert all(r.done and len(r.output) == 3 for r in reqs)
    by = {}
    for ev in events:
        by.setdefault(ev[0], []).append(ev)
    assert set(by) == {"serve.step", "serve.prefill", "serve.prefill.wait",
                       "serve.decode", "serve.decode.wait"}

    def inside(child, parents):
        return [p for p in parents if p[1] <= child[1] and child[2] <= p[2]]

    for name, parent in [("serve.prefill", "serve.step"),
                         ("serve.decode", "serve.step"),
                         ("serve.prefill.wait", "serve.prefill"),
                         ("serve.decode.wait", "serve.decode")]:
        for ev in by[name]:
            assert len(inside(ev, by[parent])) == 1, (name, ev)
    pre, = by["serve.prefill"]            # the second request's prefill
    assert pre[3] == {"req": reqs[1].req_id, "tokens": 7, "slot": 1,
                      "compiled": 1}
    assert reqs[1].req_id == 1
    assert {ev[3]["active"] for ev in by["serve.decode"]} == {1, 2}
    steps = by["serve.step"]
    assert sum(ev[3]["prefills"] for ev in steps) == 1
    assert sum(ev[3]["completed"] for ev in steps) == 2
    assert all({"active", "queue_depth"} <= set(ev[3]) for ev in steps)


def test_engine_builds_no_annotation_without_profiler(params, monkeypatch):
    from repro import obs

    built = []

    class Spy(jax.profiler.TraceAnnotation):
        def __init__(self, name, **attrs):
            built.append(name)
            super().__init__(name, **attrs)

    monkeypatch.setattr("repro.obs.core._ANNOTATOR", Spy)
    rng = np.random.default_rng(6)
    engine = ServeEngine(CFG, params, slots=2, max_len=48)
    r = Request(prompt=rng.integers(0, CFG.vocab_size, size=5)
                .astype(np.int32), max_new_tokens=3)
    engine.submit(r)
    n = len(obs.profiled_spans())
    engine.run()
    assert r.done and built == []
    assert len(obs.profiled_spans()) == n
