"""Batched serving engine: continuous-batching-lite over a fixed slot pool.

``ServeEngine`` owns a prefill program and a decode step, both jitted
once in ``__init__``.  The decode step compiles once (slot count and
max length); the prefill compiles once per distinct prompt length and
writes a request's cache into its slot in place, so serving a length it
has seen never recompiles.  Requests occupy slots; every engine step
decodes one token for all active slots; finished slots (EOS or max
tokens) free and refill from the queue.  This is the standard
static-shape continuous batching pattern for TPU serving.

While a profiler runs, the engine's ``repro.obs`` spans appear in its
trace (``docs/observability.md``, face 4): ``serve.step`` around each
step, ``serve.prefill`` around each request's prefill and
``serve.decode`` around each decode, each with a ``.wait`` child where
the host blocks on the device; the decode program is ``jit_serve_decode``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Set

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..configs.base import ArchConfig
from ..kernels import ops
from ..models.transformer import STATE_KEYS, decode_step, init_cache, prefill
from ..obs.metrics import ServeMetrics
from ..runtime import annotate_spans

__all__ = ["Request", "ServeEngine"]

annotate_spans()


@dataclasses.dataclass
class Request:
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 16
    eos_id: int = -1                # -1 = never
    # wall-second budget from submit(); a request still queued past it
    # is dropped, one mid-decode is cut off with partial output.
    # None = no deadline.
    deadline_s: Optional[float] = None
    # filled by the engine:
    output: Optional[List[int]] = None
    done: bool = False
    # why the engine refused/abandoned this request ("queue_full",
    # "deadline"); None while healthy.  ``done`` stays False for a
    # request that never produced output.
    reject_reason: Optional[str] = None
    # telemetry (observational only): monotonic submit time, for TTFT,
    # and the engine's id for the request, which its spans carry
    submit_t: Optional[float] = None
    req_id: Optional[int] = None


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, *, slots: int = 4,
                 max_len: int = 256, greedy: bool = True,
                 max_queue: Optional[int] = None,
                 dtype=jnp.float32):
        self.cfg, self.params = cfg, params
        self.slots, self.max_len = slots, max_len
        # admission bound: submit() rejects (reject_reason="queue_full")
        # once this many requests wait, instead of growing without limit.
        # None = unbounded (the historical behaviour).
        self.max_queue = max_queue
        # warn-only pre-flight: surface a structurally broken config
        # (bad dims, incoherent DAG) at engine construction instead of
        # as a shape error mid-request
        from ..analysis import preflight
        from ..core.workload import lm_workload
        preflight(lm_workload(cfg, seq_len=max_len, batch=slots),
                  strict=False, where="serve.engine")
        self.greedy = greedy
        self.cache = init_cache(cfg, slots, max_len, dtype=dtype)
        # per-slot positions from the start, so the cache's shapes (and
        # the prefill program's signature) never change while serving
        self.cache["pos"] = jnp.zeros((slots,), jnp.int32)
        self.slot_req: List[Optional[Request]] = [None] * slots
        self.slot_remaining = np.zeros(slots, np.int64)
        self.slot_pos = np.zeros(slots, np.int64)     # per-slot lengths
        self.queue: List[Request] = []

        def serve_prefill(p, prompt, c, slot):
            # one program per prompt length: run the prompt, write its
            # cache into the slot's lanes (slot traced, cache donated, so
            # in place), return the first token
            logits, pc = prefill(p, prompt, cfg)
            c = dict(c)
            for key in STATE_KEYS:
                if key in c:
                    start = (0, slot) + (0,) * (c[key].ndim - 2)
                    c[key] = jax.lax.dynamic_update_slice(
                        c[key], pc[key].astype(c[key].dtype), start)
            return jnp.argmax(logits[0, -1]).astype(jnp.int32), c

        def serve_decode(p, t, c):
            # the cache is donated: the step writes each slot's new row
            # into it in place
            return decode_step(p, t, cfg, c)

        # decode attention fetches each live slot's K/V in blocks of this
        # many positions; the XLA path (None) reads every slot whole
        k = self.cache.get("k")
        self._kv_block = (ops.decode_attention_block(max_len)
                          if k is not None
                          and ops.resolve_decode_attention("auto", k) != "ref"
                          else None)
        self._prefill = jax.jit(serve_prefill, donate_argnums=2)
        self._prefilled_lengths: Set[int] = set()
        self._decode = jax.jit(serve_decode, donate_argnums=2)
        self._submitted = 0
        self._last_tokens = np.zeros(slots, np.int32)
        # cumulative across the engine's lifetime; run() additionally
        # leaves a per-call delta in ``last_stats`` (mirroring the sweep
        # engine's RunStats split)
        self.metrics = ServeMetrics()
        self.last_stats: Dict[str, Any] = {}

    # -- request management --------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Admit ``req`` (True) or reject it with backpressure (False).

        Rejection is immediate and structured — ``req.reject_reason`` is
        set to ``"queue_full"`` and the request never enters the queue —
        so a load generator can shed or retry instead of the queue
        growing without bound."""
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            req.reject_reason = "queue_full"
            self.metrics.on_reject()
            return False
        req.output = []
        req.submit_t = time.monotonic()
        req.req_id = self._submitted
        self._submitted += 1
        self.queue.append(req)
        self.metrics.on_submit()
        return True

    def _expired(self, req: Request, now: float) -> bool:
        return (req.deadline_s is not None and req.submit_t is not None
                and now - req.submit_t > req.deadline_s)

    def _fill_slots(self) -> int:
        now = time.monotonic()
        # drop queued requests whose deadline already passed — decoding
        # them would only delay every request behind them
        kept: List[Request] = []
        for req in self.queue:
            if self._expired(req, now):
                req.reject_reason = "deadline"
                self.metrics.on_expire(queued=True)
            else:
                kept.append(req)
        self.queue = kept
        prefills = 0
        for s in range(self.slots):
            if self.slot_req[s] is None and self.queue:
                req = self.queue.pop(0)
                n = len(req.prompt)
                compiled = int(n not in self._prefilled_lengths)
                with obs.span("serve.prefill", req=req.req_id, tokens=n,
                              slot=s, compiled=compiled):
                    self._prefill_slot(s, req)
                prefills += 1
        return prefills

    def _prefill_slot(self, s: int, req: Request) -> None:
        """Per-slot prefill: one ``serve_prefill`` call runs the prompt,
        writes its cache into slot ``s`` and returns the first token;
        per-slot variable positions are tracked host-side."""
        prompt = np.asarray(req.prompt, np.int32)[None]
        S = prompt.shape[1]
        if S >= self.max_len:
            raise ValueError(f"prompt {S} ≥ max_len {self.max_len}")
        tok, self.cache = self._prefill(self.params, prompt, self.cache,
                                        np.int32(s))
        self._prefilled_lengths.add(S)
        with obs.span("serve.prefill.wait"):
            tok = int(tok)
        req.output.append(tok)
        self._last_tokens[s] = tok
        self.slot_req[s] = req
        self.slot_remaining[s] = req.max_new_tokens - 1
        self.slot_pos[s] = S
        self.metrics.on_scheduled()
        self.metrics.tokens_generated += 1       # the prefill's first token
        if req.submit_t is not None:
            self.metrics.on_first_token(time.monotonic() - req.submit_t)

    # -- decoding ------------------------------------------------------------
    def step(self) -> int:
        """Decode one token for all active slots; returns #active."""
        with obs.span("serve.step") as sp:
            t0 = time.monotonic()
            prefills = self._fill_slots()
            active = [s for s in range(self.slots)
                      if self.slot_req[s] is not None]
            completed = self._decode_active(active) if active else 0
            m = self.metrics
            if active:
                step_s = time.monotonic() - t0
                m.on_step(len(active), step_s)
                m.on_tokens(len(active), step_s)
                for _ in range(completed):
                    m.on_complete()
            sp.set(active=len(active), queue_depth=m.queue_depth,
                   completed=completed, prefills=prefills)
        return len(active)

    def _decode_active(self, active: List[int]) -> int:
        """One decode step for the ``active`` slots; returns how many
        requests it completed."""
        with obs.span("serve.decode", active=len(active)) as sp:
            # per-slot positions: each slot decodes at its own cache
            # length; a free slot is marked -1, so it reads and writes no
            # K/V
            pos = np.full(self.slots, -1, np.int32)
            pos[active] = self.slot_pos[active]
            sp.set(kv_read_share=self._kv_read_share(pos))
            self.cache["pos"] = jnp.asarray(pos)
            tokens = jnp.asarray(self._last_tokens)
            logits, self.cache = self._decode(self.params, tokens, self.cache)
            with obs.span("serve.decode.wait"):
                next_tokens = np.asarray(jnp.argmax(logits, axis=-1),
                                         np.int32)
            completed = 0
            now = time.monotonic()
            for s in active:
                req = self.slot_req[s]
                tok = int(next_tokens[s])
                req.output.append(tok)
                self._last_tokens[s] = tok
                self.slot_pos[s] += 1
                self.slot_remaining[s] -= 1
                if (self.slot_remaining[s] <= 0 or tok == req.eos_id
                        or self.slot_pos[s] >= self.max_len - 1):
                    req.done = True
                    self.slot_req[s] = None
                    completed += 1
                elif self._expired(req, now):
                    # deadline passed mid-decode: keep the partial output,
                    # free the slot for requests that can still make it
                    req.reject_reason = "deadline"
                    self.slot_req[s] = None
                    self.metrics.on_expire(queued=False)
        return completed

    def _kv_read_share(self, pos: np.ndarray) -> float:
        """Share of the K/V cache the decode attention reads: each live
        slot's length (``pos + 1``) rounded up to the kernel's block,
        over slots x max_len (1.0 on the XLA path, which reads every
        slot whole).  A sliding window reads less than this."""
        if self._kv_block is None:
            return 1.0
        blk = self._kv_block
        fetched = -(-(pos + 1) // blk) * blk
        return float(fetched.sum()) / (self.slots * self.max_len)

    def run(self) -> None:
        """Drain queue + slots; leaves this call's deltas in
        ``last_stats`` (``metrics`` keeps cumulating across calls)."""
        m = self.metrics
        before = (m.steps, m.tokens_generated, m.requests_completed, m.busy_s)
        t0 = time.monotonic()
        while self.queue or any(r is not None for r in self.slot_req):
            self.step()
        self.last_stats = {
            "steps": m.steps - before[0],
            "tokens_generated": m.tokens_generated - before[1],
            "requests_completed": m.requests_completed - before[2],
            "busy_s": m.busy_s - before[3],
            "wall_s": time.monotonic() - t0,
        }

    # -- exposition ----------------------------------------------------------
    def stats_snapshot(self) -> Dict[str, Any]:
        """Cumulative metrics as a JSON-able dict (queue depth, TTFT and
        per-token latency p50/p99, tokens/s, …)."""
        return self.metrics.snapshot()

    def stats_text(self) -> str:
        return self.metrics.render_text()
