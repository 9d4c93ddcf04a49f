#!/usr/bin/env python3
"""Drive the execution plane once on a TPU and check what comes out.

With no arguments (one chip) it runs three phases:

* serve: qwen3-4b at its published widths (36 layers, random bf16
  weights from ``--seed``) behind ``ServeEngine(slots=4, max_len=1024)``.
  Eight seeded requests of 32-512 prompt tokens ask for 16-64 new
  tokens each.  Every request must finish with the tokens it asked for,
  and the engine's first token of one request must equal the argmax of
  ``forward`` at that prompt's last position.
* train: mamba2-130m at its published widths, three ``Trainer`` steps
  on a seeded ``TokenPipeline`` at batch 8 x 2048 (eight microbatches,
  so one step fits one chip).  Every loss must be finite, no step
  skipped, and the first loss must equal a separate forward pass over
  the same weights and batch.
* kernels: every Pallas kernel with ``impl="pallas"`` at qwen3-4b
  widths.  The compiled program must hold a Mosaic kernel
  (``tpu_custom_call``) and the output must match ``kernels/ref.py``.

``--chips 4`` runs only the expert-parallel MoE path: a prefill of
qwen3-moe-30b-a3b (published widths, 4 of its 48 layers, dropless
capacity) on a (1, 4) ("data", "model") mesh, compared in the same
process with the same model on device 0 alone.

The script is one process and reads nothing but the repository's
``src/``.  It exits non-zero, printing no result, when JAX finds no
TPU or any check fails.  Its last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Compiled programs are kept by ``repro.runtime.use_compile_cache``.

Usage: python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# what a Mosaic (Pallas TPU) kernel lowers to in the compiled HLO
KERNEL_MARK = "tpu_custom_call"


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


class CompileClock:
    """Sums backend compile seconds (cache reads included) and counts
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.seconds, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _jit_init(cfg, seed: int):
    """Random bf16 weights, built on the device in one program (eager
    init would hold each stacked weight in f32 before the cast)."""
    import jax
    from repro.models.transformer import init_params
    params = jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def serve_phase(cfg, seed: int, *, slots: int = 4, max_len: int = 1024,
                n_requests: int = 8, prompt_len=(32, 512),
                new_tokens=(16, 64)) -> None:
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import forward
    from repro.serve.engine import Request, ServeEngine

    t0 = time.monotonic()
    params = _jit_init(cfg, seed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n_requests):
        n = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        reqs.append(Request(prompt=prompt, max_new_tokens=int(
            rng.integers(new_tokens[0], new_tokens[1] + 1))))
    engine = ServeEngine(cfg, params, slots=slots, max_len=max_len)
    for r in reqs:
        check(engine.submit(r), "serve: a request was refused")
    engine.run()
    short = [(i, len(r.output), r.max_new_tokens) for i, r in enumerate(reqs)
             if not r.done or len(r.output) != r.max_new_tokens]
    check(not short, f"serve: requests not done with their tokens: {short}")

    logits = jax.jit(lambda p, t: forward(p, t, cfg))(
        params, jnp.asarray(reqs[0].prompt)[None])
    last = np.asarray(logits[0, -1], np.float32)
    top2 = np.sort(last)[-2:]
    check(bool(np.isfinite(last).all()), "serve: forward logits not finite")
    check(reqs[0].output[0] == int(last.argmax()),
          f"serve: engine's first token {reqs[0].output[0]} != forward "
          f"argmax {int(last.argmax())}")
    st = engine.last_stats
    log(f"serve: {cfg.name} layers={cfg.n_layers} params={n_params} "
        f"requests={len(reqs)} prompt_tokens="
        f"{sum(len(r.prompt) for r in reqs)} "
        f"tokens_generated={st['tokens_generated']} steps={st['steps']} "
        f"engine_wall_s={st['wall_s']:.3f} "
        f"first_token={reqs[0].output[0]} == forward_argmax "
        f"(top-2 logit gap {float(top2[1] - top2[0]):.4f}) "
        f"phase_wall_s={time.monotonic() - t0:.3f}")


def train_phase(cfg, seed: int, *, batch: int = 8, seq_len: int = 2048,
                microbatches: int = 8, steps: int = 3) -> None:
    import jax
    from repro.data.pipeline import PipelineConfig, TokenPipeline
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import make_loss_fn
    from repro.train.trainer import Trainer, TrainerConfig

    t0 = time.monotonic()
    pcfg = PipelineConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                          global_batch=batch, seed=seed)
    trainer = Trainer(cfg, AdamWConfig(),
                      TrainerConfig(steps=steps, ckpt_every=steps,
                                    microbatches=microbatches, seed=seed),
                      TokenPipeline(pcfg))
    params0 = trainer.params
    metrics = trainer.train()
    losses = [m["loss"] for m in metrics]
    check(len(metrics) == steps, f"train: {len(metrics)} of {steps} steps")
    check(trainer.skipped_nonfinite == 0,
          f"train: {trainer.skipped_nonfinite} non-finite step(s) skipped")
    check(all(np.isfinite(losses)), f"train: losses {losses}")

    # step 0's loss, from a separate forward of one sequence at a time
    batch0 = TokenPipeline(pcfg).next_batch()
    loss_fn = jax.jit(make_loss_fn(cfg))
    ref = float(np.mean([
        float(loss_fn(params0, {k: v[i:i + 1] for k, v in batch0.items()}))
        for i in range(batch)]))
    check(abs(losses[0] - ref) <= 1e-3 * abs(ref),
          f"train: step-0 loss {losses[0]} != forward loss {ref}")
    log(f"train: {cfg.name} layers={cfg.n_layers} batch={batch}x{seq_len} "
        f"microbatches={microbatches} losses={losses} "
        f"step0_vs_forward={ref} "
        f"step_wall_s={[round(m['wall_s'], 3) for m in metrics]} "
        f"phase_wall_s={time.monotonic() - t0:.3f}")


def kernel_phase(seed: int, *, K: int = 2560, N: int = 9728,
                 rows: int = 256, seq: int = 2048, heads=(32, 8),
                 head_dim: int = 128, block: int = 128) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.flexblock import IntraBlock
    from repro.core.pruning import intrablock_mask
    from repro.kernels import ops
    from repro.kernels import ref as R

    rng = np.random.default_rng(seed)
    bf16 = jnp.bfloat16

    def run(name, fn, ref_fn, args, tol, exact=False):
        t0 = time.monotonic()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.monotonic() - t0
        check(KERNEL_MARK in compiled.as_text(),
              f"kernels: {name} compiled without a Pallas kernel")
        got = jax.block_until_ready(compiled(*args))
        want = jax.jit(ref_fn)(*args)
        if exact:
            err = float(np.abs(np.asarray(got, np.int64)
                               - np.asarray(want, np.int64)).max())
        else:
            err = _rel_err(got, want)
        check(err <= tol, f"kernels: {name} error {err} > {tol}")
        log(f"kernels: {name} shapes={[tuple(a.shape) for a in args]} "
            f"compile_s={compile_s:.3f} max_err={err:.3g} (tol {tol})")

    hq, hkv = heads
    q = jnp.asarray(rng.standard_normal((1, seq, hq, head_dim)), bf16)
    k = jnp.asarray(rng.standard_normal((1, seq, hkv, head_dim)), bf16)
    v = jnp.asarray(rng.standard_normal((1, seq, hkv, head_dim)), bf16)
    run("flash_attention",
        lambda q, k, v: ops.flash_attention(q, k, v, impl="pallas"),
        lambda q, k, v: ops.flash_attention(q, k, v, impl="ref"),
        (q, k, v), tol=3e-2)

    w = rng.standard_normal((K, N)).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((rows, K)), bf16)
    keep = rng.random((K // block, N // block)) < 0.5
    keep[0, :] = True
    wc, idx = ops.compress_fullblock(w, keep, block, block)
    run("block_sparse_matmul",
        lambda x, w, i: ops.block_sparse_matmul(x, w, i, impl="pallas"),
        R.block_sparse_matmul_ref,
        (x, jnp.asarray(wc, bf16), jnp.asarray(idx)), tol=2e-2)

    mask = intrablock_mask(w, IntraBlock(4, 1, 0.5), align_cols=True)
    wc, ridx = ops.compress_intrablock(w, mask, 4)
    run("intrablock_gather_matmul",
        lambda x, w, i: ops.intrablock_gather_matmul(x, w, i,
                                                     impl="pallas"),
        R.intrablock_gather_matmul_ref,
        (x, jnp.asarray(wc, bf16), jnp.asarray(ridx)), tol=2e-2)

    run("block_importance",
        lambda w: ops.block_importance(w, block, block, impl="pallas"),
        lambda w: R.block_importance_ref(w, block, block),
        (jnp.asarray(w, bf16),), tol=1e-4)

    qi = jnp.asarray(rng.integers(-40, 41, (1024, K)), jnp.int8)
    run("bitserial_zero_profile",
        lambda q: ops.bitserial_zero_profile(q, block, impl="pallas"),
        lambda q: R.bitserial_zero_profile_ref(q, block),
        (qi,), tol=0, exact=True)


def ep_moe_phase(cfg, seed: int, devices, *, batch: int = 4,
                 seq: int = 512) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.sharding import tree_shardings
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import prefill

    t0 = time.monotonic()
    params = _jit_init(cfg, seed)
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)

    def run(p, t):
        return prefill(p, t, cfg)

    ref_logits, ref_cache = jax.jit(run)(params, tokens)   # device 0 alone
    ref_logits, ref_k = np.asarray(ref_logits), np.asarray(ref_cache["k"])

    mesh = make_mesh((1, len(devices)), ("data", "model"), devices=devices)
    p_shard = tree_shardings(mesh, params)
    t_shard = NamedSharding(mesh, P("data", None))
    sharded = jax.device_put(params, p_shard)
    del params
    with jax.set_mesh(mesh):
        compiled = jax.jit(run, in_shardings=(p_shard, t_shard)).lower(
            sharded, tokens).compile()
        check("all-to-all" in compiled.as_text(),
              "ep_moe: the sharded prefill has no all-to-all (EP dispatch "
              "did not run)")
        logits, cache = compiled(sharded, jax.device_put(tokens, t_shard))
    logits, k = np.asarray(logits), np.asarray(cache["k"])

    def frob(a, b):
        a, b = a.astype(np.float32), b.astype(np.float32)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    logit_err, k_err = frob(logits, ref_logits), frob(k, ref_k)
    agree = float((logits.argmax(-1) == ref_logits.argmax(-1)).mean())
    check(bool(np.isfinite(logits).all()), "ep_moe: logits not finite")
    check(logit_err <= 5e-2 and k_err <= 5e-2,
          f"ep_moe: sharded vs one-chip relative error logits={logit_err} "
          f"k_cache={k_err} > 5e-2")
    log(f"ep_moe: {cfg.name} layers={cfg.n_layers} experts={cfg.n_experts} "
        f"top_k={cfg.top_k} mesh=(data=1, model={len(devices)}) "
        f"batch={batch}x{seq} logits_rel_err={logit_err:.4g} "
        f"k_cache_rel_err={k_err:.4g} argmax_agree={agree:.3f} "
        f"phase_wall_s={time.monotonic() - t0:.3f}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the expert-parallel MoE prefill on a "
                         "(1, 4) mesh against one chip")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository source at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.runtime import use_compile_cache

    cache_dir = use_compile_cache()
    clock = CompileClock()
    if args.chips == 4:
        base = get_config("qwen3-moe-30b-a3b")
        cfg = dataclasses.replace(
            base, n_layers=4,
            capacity_factor=float(base.n_experts // base.top_k))
        log(f"ep_moe: cuts: n_layers {base.n_layers} -> {cfg.n_layers}; "
            f"capacity_factor {base.capacity_factor} -> "
            f"{cfg.capacity_factor} (dropless: E/k)")
        ep_moe_phase(cfg, args.seed, devices[:4])
    else:
        serve_phase(get_config("qwen3-4b"), args.seed)
        train_phase(get_config("mamba2-130m"), args.seed)
        kernel_phase(args.seed)
    log(f"compile: backend_compile_s={clock.seconds:.3f} "
        f"persistent_cache_hits={clock.hits} cache_dir={cache_dir}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
