"""Serving cells: ``ServeEngine`` under open-loop traffic.

Set-up makes the weights on the device from the seed, builds the
engine, warms the decode program and every prompt length the traffic
can send, and then offers warm traffic for ``warm_s`` seconds, so that
the window opens on an engine in its steady state.  The window offers
the arrivals of ``traffic.window_arrivals`` at their due times and steps
the engine; it stops admitting when the window closes and keeps stepping
until every request due in it has finished, or ``drain_s`` has passed.

Each request's tokens are observed when the ``step()`` that produced
them returns, which is when a streaming front end could send them.
Once the window has closed, a sample of the finished requests drawn from
the seed, the longest among them, goes to the plain reference
(``reference/qwen3.py``), and ``max_logit_gap`` is the widest gap by
which a served token's logit lies below the reference's best.
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict, List

import numpy as np

from chipbench import bench, stats, traffic
from chipbench.trace import Capture

_DTYPES = {"bfloat16", "float32"}
# token-id streams of the warm-up requests (window requests use 0, 1, ...)
_WARM_IDS, _COMPILE_IDS = 1_000_000, 2_000_000


def _init_params(arch, seed: int):
    """Random weights in the type they are served in, made on the
    device in one program."""
    import jax
    from repro.models.transformer import init_params
    params = jax.jit(init_params, static_argnums=0)(
        arch, jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


class _Loop:
    """Drives one engine: submits arrivals when due, steps, and logs what
    every request saw."""

    def __init__(self, engine, arch, seed: int, annotate: bool):
        import jax
        self.engine, self.arch, self.seed = engine, arch, seed
        self.annotation = jax.profiler.TraceAnnotation if annotate else None
        self.inflight: List[stats.RequestLog] = []
        self.pending: List[stats.RequestLog] = []
        self.steps: List[Dict] = []

    def submit(self, log: stats.RequestLog) -> None:
        from repro.serve.engine import Request
        prompt = traffic.token_ids(self.seed, log.ident, log.prompt_len,
                                   self.arch.vocab_size)
        log.request = Request(prompt=prompt, max_new_tokens=log.new_tokens)
        if self.engine.submit(log.request):
            self.inflight.append(log)

    def _span(self, name: str):
        if self.annotation is None:
            return contextlib.nullcontext()
        return self.annotation(f"{name}:{len(self.steps)}")

    def step(self) -> None:
        eng = self.engine
        prefill = bool(eng.queue) and any(r is None for r in eng.slot_req)
        before = [len(r.request.output) for r in self.inflight]
        t0 = time.monotonic()
        with self._span("cb.step_prefill" if prefill else "cb.step"):
            eng.step()
        t1 = time.monotonic()
        prompts, contexts, still = [], [], []
        for log, n0 in zip(self.inflight, before):
            out = log.request.output
            for k in range(n0, len(out)):
                log.token_times.append(t1)
                if k == 0:
                    prompts.append(log.prompt_len)
                else:
                    contexts.append(log.prompt_len + k)
            if log.request.done or log.request.reject_reason:
                log.done = log.request.done
            else:
                still.append(log)
        self.inflight = still
        self.steps.append({"t0": t0, "t1": t1, "prompts": prompts,
                           "contexts": contexts, "prefill": bool(prompts)})

    def wait_until(self, t: float) -> None:
        with self._span("cb.wait"):
            time.sleep(max(0.0, t - time.monotonic()))

    def busy(self) -> bool:
        eng = self.engine
        return bool(eng.queue) or any(r is not None for r in eng.slot_req)

    def offer(self, until: float) -> None:
        """Submit ``self.pending`` when due, stepping the engine in
        between, until the host clock reads ``until``."""
        while True:
            now = time.monotonic()
            while self.pending and self.pending[0].due <= now:
                self.submit(self.pending.pop(0))
            if now >= until:
                return
            if self.busy():
                self.step()
            else:
                nxt = self.pending[0].due if self.pending else until
                self.wait_until(min(nxt, until))

    def drain(self, logs: List[stats.RequestLog], until: float) -> float:
        """Step until every one of ``logs`` has ended, or ``until``."""
        while time.monotonic() < until and self.busy() and any(
                not (r.done or (r.request is not None
                                and r.request.reject_reason))
                for r in logs):
            self.step()
        return time.monotonic()


def _warm_compile(engine, tr: Dict, arch, seed: int) -> None:
    """Every prompt length the traffic can send, and the decode step."""
    from repro.serve.engine import Request
    for i, n in enumerate(traffic.support(tr["prompt"])):
        prompt = traffic.token_ids(seed, _COMPILE_IDS + i, n,
                                   arch.vocab_size)
        engine.submit(Request(prompt=prompt, max_new_tokens=2))
    engine.run()


def _sample(logs: List[stats.RequestLog], seed: int, tokens: int
            ) -> List[stats.RequestLog]:
    """Finished requests for the reference: the longest, then others in
    an order drawn from the seed, until ``tokens`` served tokens."""
    done = [r for r in logs if r.done]
    if not done:
        return []
    done.sort(key=lambda r: -(r.prompt_len + len(r.request.output)))
    rest = done[1:]
    order = np.random.default_rng([seed, 4]).permutation(len(rest))
    picked, total = [done[0]], len(done[0].request.output)
    for i in order:
        if total >= tokens:
            break
        picked.append(rest[i])
        total += len(rest[i].request.output)
    return picked



def _logs(arrivals, first_ident: int, t0: float) -> List[stats.RequestLog]:
    return [stats.RequestLog(first_ident + i, t0 + a.due, a.prompt_len,
                             a.new_tokens) for i, a in enumerate(arrivals)]


def run(run: bench.Run) -> bench.Outcome:
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import ServeEngine

    tr, arch = run.traffic, run.arch
    eng_cfg = tr["engine"]
    if eng_cfg["kv_dtype"] not in _DTYPES:
        raise ValueError(f"kv_dtype {eng_cfg['kv_dtype']!r}")
    params = _init_params(arch, run.seed)
    engine = ServeEngine(arch, params, slots=eng_cfg["slots"],
                         max_len=eng_cfg["max_len"],
                         dtype=getattr(jnp, eng_cfg["kv_dtype"]))
    _warm_compile(engine, tr, arch, run.seed)

    loop = _Loop(engine, arch, run.seed, annotate=run.trace)
    t_warm = time.monotonic()
    loop.pending = _logs(traffic.warm_arrivals(tr, run.seed,
                                               tr["warm_requests"]),
                         _WARM_IDS, t_warm)
    loop.offer(t_warm + tr["warm_s"])

    t0 = time.monotonic()
    setup_s = t0 - run.t_process
    loop.steps.clear()
    window = _logs(traffic.window_arrivals(tr, run.seed, run.seconds), 0, t0)
    loop.pending = list(window)
    t_close = t0 + run.seconds
    capture, traced = None, None
    try:
        if run.trace:
            loop.offer(t_close - min(tr["trace_seconds"], run.seconds))
            capture = Capture()
            capture.start()
            with jax.profiler.TraceAnnotation("cb.traced"):
                loop.offer(t_close)
        else:
            loop.offer(t_close)
        compiles = run.clock.between(t0, time.monotonic())
        drain_end = loop.drain(window, t_close + tr["drain_s"])
        if capture is not None:
            traced = capture.stop()
    finally:
        if capture is not None:
            capture.close()
    memory = bench.memory_peak(run.devices)
    summary = stats.serve_summary(window, drain_end)
    sample = [(r.request.prompt, np.asarray(r.request.output, np.int32))
              for r in _sample(window, run.seed,
                               run.checks["sample_tokens"])]
    steps = loop.steps
    del loop, engine, params
    gc.collect()

    compared = {}
    if sample:
        from chipbench.reference import qwen3 as ref
        gap = ref.max_served_gap(run.config["arch"], run.seed, sample)
        compared["max_logit_gap"] = (gap,
                                     run.checks["max_logit_gap"]["limit"])
    else:
        compared["max_logit_gap"] = (float("inf"),
                                     run.checks["max_logit_gap"]["limit"])
    metrics = {k: summary[k] for k in ("ttft_p90_ms", "tpot_ms")
               if k in summary}
    metrics["setup_s"] = setup_s
    observed = {"window": (t0, t_close), "logs": window, "steps": steps,
                "compiles_in_window": compiles, "sample": sample,
                "sample_tokens": sum(len(o) for _, o in sample)}
    return bench.Outcome(attempted=summary["attempted"],
                         failed=summary["failed"], metrics=metrics,
                         compared=compared, memory_peak_bytes=memory,
                         observed=observed, trace=traced)
