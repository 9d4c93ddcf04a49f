"""Per-arch smoke tests: reduced config, one forward + one train step on
CPU, asserting output shapes and finiteness (assignment requirement)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import all_configs, cells_for, get_config, list_archs
from repro.models.transformer import (decode_step, forward, init_cache,
                                      init_params, prefill)
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.step import make_train_step

ARCHS = list_archs()


def _batch_for(cfg, B=2, S=16):
    key = jax.random.PRNGKey(0)
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
    }
    if cfg.prefix_len:
        batch["prefix_embed"] = 0.02 * jax.random.normal(
            key, (B, cfg.prefix_len, cfg.d_model))
    if cfg.enc_dec:
        batch["enc_embed"] = 0.02 * jax.random.normal(
            key, (B, cfg.enc_seq, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finiteness(arch):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    batch = _batch_for(cfg)
    kwargs = {k: batch[k] for k in ("prefix_embed", "enc_embed")
              if k in batch}
    logits = forward(params, batch["tokens"], cfg, **kwargs)
    S_out = 16 + (cfg.prefix_len or 0)
    assert logits.shape == (2, S_out, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step(arch):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3)))
    batch = _batch_for(cfg)
    p1, o1, m1 = step(params, opt, batch)
    assert np.isfinite(float(m1["loss"]))
    assert int(o1["step"]) == 1
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.abs(a - b).sum()), params, p1))
    assert delta > 0


_DECODE_ARCHS = ["llama3-8b", "gemma2-9b", "mamba2-130m", "hymba-1.5b",
                 "whisper-medium", "qwen3-moe-30b-a3b", "paligemma-3b"]


@pytest.mark.parametrize("arch,impl", [
    *[pytest.param(a, None, id=a) for a in _DECODE_ARCHS],
    *[pytest.param(a, "pallas_interpret", id=f"{a}-kernel")
      for a in _DECODE_ARCHS if get_config(a).attention != "none"],
])
def test_decode_matches_forward(arch, impl, monkeypatch):
    """One decode step after a prefill gives the logits a full forward
    gives at that position; ``impl`` forces the decode attention kernel
    (interpret mode) where the CPU would take the XLA path."""
    from repro.kernels import ops
    if impl is not None:
        monkeypatch.setattr(ops, "resolve_decode_attention",
                            lambda *_: impl)
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key, dtype=jnp.float32)
    B, S = 2, 12
    batch = _batch_for(cfg, B, S + 1)
    kwargs = {k: batch[k] for k in ("prefix_embed", "enc_embed")
              if k in batch}
    full = forward(params, batch["tokens"], cfg, **kwargs)
    _, cache = prefill(params, batch["tokens"][:, :S], cfg, **kwargs)
    if "k" in cache:
        cache["k"] = jnp.pad(cache["k"], ((0, 0), (0, 0), (0, 4),
                                          (0, 0), (0, 0)))
        cache["v"] = jnp.pad(cache["v"], ((0, 0), (0, 0), (0, 4),
                                          (0, 0), (0, 0)))
    lg, _ = decode_step(params, batch["tokens"][:, S], cfg, cache)
    pfx = cfg.prefix_len or 0
    ref = full[:, pfx + S, :]
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref),
                               atol=5e-4, rtol=1e-3)


@pytest.mark.parametrize("impl", [None, "pallas_interpret"],
                         ids=["xla", "kernel"])
def test_decode_step_free_slot_reads_and_writes_nothing(impl, monkeypatch):
    """A slot at position -1 (free) gets no K/V row written, and the
    live slot's logits are the ones it gets beside a busy slot."""
    from repro.kernels import ops
    if impl is not None:
        monkeypatch.setattr(ops, "resolve_decode_attention",
                            lambda *_: impl)
    cfg = get_config("llama3-8b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    cache = init_cache(cfg, 2, 32, dtype=jnp.float32)
    for i, key in enumerate(("k", "v")):
        cache[key] = jax.random.normal(jax.random.PRNGKey(5 + i),
                                       cache[key].shape)
    tokens = jnp.array([3, 5], jnp.int32)
    step = jax.jit(lambda p, t, c: decode_step(p, t, cfg, c))
    lg_busy, _ = step(params, tokens, dict(cache, pos=jnp.array([9, 20])))
    lg, new = step(params, tokens, dict(cache, pos=jnp.array([9, -1])))
    for key in ("k", "v"):
        old, upd = np.asarray(cache[key]), np.asarray(new[key])
        np.testing.assert_array_equal(upd[:, 1], old[:, 1])
        np.testing.assert_array_equal(np.delete(upd[:, 0], 9, axis=1),
                                      np.delete(old[:, 0], 9, axis=1))
    np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(lg_busy[0]),
                               atol=1e-5, rtol=1e-5)
    assert bool(jnp.all(jnp.isfinite(lg)))


def test_ssd_gradient_finite_over_a_full_chunk():
    """Above the diagonal the SSD decay exponent grows with the chunk
    length and overflows f32 at 256 positions; that must not reach the
    gradient (it made every mamba2-130m step after the first NaN)."""
    from repro.models.layers import ssm_block
    cfg = get_config("mamba2-130m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (1, cfg.ssm_chunk, cfg.d_model))
    grads = jax.grad(lambda lp: ssm_block(x, lp, cfg)[0].sum())(lp)
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree.leaves(grads))


def test_gemma2_local_global_flags():
    from repro.models.transformer import layer_flags
    cfg = get_config("gemma2-9b")
    flags = np.asarray(layer_flags(cfg))
    assert flags.shape == (42,)
    assert flags[1] and not flags[0]       # alternating local/global


def test_sliding_window_masks_old_tokens():
    """A token outside the window must not influence attention."""
    cfg = get_config("hymba-1.5b").reduced()
    import dataclasses
    cfg = dataclasses.replace(cfg, window=4, ssm_state=0, family="dense",
                              attention="sliding")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    t = jax.random.randint(jax.random.PRNGKey(2), (1, 10), 0, cfg.vocab_size)
    base = forward(params, t, cfg)
    t2 = t.at[0, 0].set((int(t[0, 0]) + 1) % cfg.vocab_size)
    pert = forward(params, t2, cfg)
    # last position is > window away from position 0
    np.testing.assert_allclose(np.asarray(base[0, -1]),
                               np.asarray(pert[0, -1]), atol=1e-5)


def test_long_500k_eligibility():
    eligible = {a for a, c in all_configs().items()
                if "long_500k" in cells_for(c)}
    assert eligible == {"mamba2-130m", "hymba-1.5b", "gemma2-9b"}


def test_param_counts_near_nameplate():
    """Parameter counts should be in the ballpark of the model names."""
    expect = {"llama3-8b": 8.0e9, "gemma-7b": 8.5e9, "qwen3-4b": 4.0e9,
              "gemma2-9b": 9.2e9, "dbrx-132b": 132e9, "mamba2-130m": 0.13e9,
              "hymba-1.5b": 1.5e9, "qwen3-moe-30b-a3b": 30.5e9}
    for arch, target in expect.items():
        n = get_config(arch).param_count()
        assert 0.55 * target < n < 1.45 * target, (arch, n, target)


@pytest.mark.parametrize("arch,scopes", [
    ("qwen3-4b", {"attention", "ffn"}),
    ("qwen3-moe-30b-a3b", {"attention", "ffn"}),
    ("hymba-1.5b", {"attention", "ssm", "ffn"}),
    ("mamba2-130m", {"ssm"}),
    ("whisper-medium", {"attention", "cross_attention", "ffn"}),
])
def test_decode_step_named_scopes(arch, scopes, monkeypatch):
    """The compiled decode step names its parts in the op metadata a
    profiler trace carries, and the scopes change no bit of the logits."""
    import contextlib
    import re
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    cache = init_cache(cfg, 2, 16, dtype=jnp.float32)
    tokens = jnp.array([3, 5], jnp.int32)

    def step(p, t, c):
        return decode_step(p, t, cfg, c)[0]

    def op_scopes(fn):
        text = fn.lower(params, tokens, cache).compile().as_text()
        return {part for name in re.findall(r'op_name="([^"]+)"', text)
                for part in name.split("/")}

    parts = op_scopes(jax.jit(step))
    assert {"embed", "layers", "unembed"} | scopes <= parts
    assert not ({"attention", "ssm", "ffn", "cross_attention"} - scopes) \
        & parts
    scoped = np.asarray(jax.jit(step)(params, tokens, cache))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = jax.jit(lambda p, t, c: step(p, t, c))
    assert not scopes & op_scopes(plain)
    np.testing.assert_array_equal(scoped, np.asarray(plain(params, tokens,
                                                           cache)))


@pytest.mark.parametrize("arch,per_slot,impl", [
    ("llama3-8b", True, None), ("llama3-8b", False, None),
    ("mamba2-130m", True, None), ("hymba-1.5b", True, None),
    ("gemma2-9b", True, None), ("whisper-medium", True, None),
    ("hymba-1.5b", True, "pallas_interpret"),
    ("gemma2-9b", True, "pallas_interpret"),
], ids=["dense-per-slot-pos", "dense-scalar-pos", "mamba2-130m",
        "hymba-1.5b", "gemma2-9b", "whisper-medium", "hymba-1.5b-kernel",
        "gemma2-9b-kernel"])
def test_decode_step_writes_only_each_slots_new_row(arch, per_slot, impl,
                                                    monkeypatch):
    """From a cache whose slots were prefilled to different lengths,
    each decode step changes only each slot's new K/V row (every other
    row is bit-identical to its input), leaves the cross K/V untouched,
    and gives the logits a full forward gives at that position; with
    ``impl`` the decode attention kernel (interpret mode) reads the
    sliding windows."""
    from repro.kernels import ops
    if impl is not None:
        monkeypatch.setattr(ops, "resolve_decode_attention",
                            lambda *_: impl)
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    # past the reduced window (32), so sliding masks take effect
    lengths = (37, 29) if per_slot else (33, 33)
    B, steps, max_len = len(lengths), 3, 48
    batch = _batch_for(cfg, B, max(lengths) + steps)
    extra = {k: batch[k] for k in ("prefix_embed", "enc_embed")
             if k in batch}
    full = forward(params, batch["tokens"], cfg, **extra)
    cache = init_cache(cfg, B, max_len, dtype=jnp.float32)
    for b, n in enumerate(lengths):
        _, pc = prefill(params, batch["tokens"][b:b + 1, :n], cfg,
                        **{k: v[b:b + 1] for k, v in extra.items()})
        for key, entry in pc.items():
            if key != "pos":
                cache[key] = jax.lax.dynamic_update_slice(
                    cache[key], entry, (0, b) + (0,) * (entry.ndim - 2))
    cache["pos"] = (jnp.asarray(lengths, jnp.int32) if per_slot
                    else jnp.int32(lengths[0]))
    step = jax.jit(lambda p, t, c: decode_step(p, t, cfg, c))
    pfx = cfg.prefix_len or 0
    for i in range(steps):
        pos = np.asarray(lengths) + i
        tokens = batch["tokens"][np.arange(B), pos]
        lg, new = step(params, tokens, cache)
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(full[np.arange(B), pfx + pos]),
            atol=5e-4, rtol=1e-3)
        np.testing.assert_array_equal(np.asarray(new["pos"]),
                                      np.asarray(cache["pos"]) + 1)
        for key in ("k", "v"):
            if key not in cache:
                continue
            old, upd = np.asarray(cache[key]), np.asarray(new[key])
            written = np.zeros(old.shape[:3], bool)
            written[:, np.arange(B), pos] = True
            np.testing.assert_array_equal(upd[~written], old[~written])
            assert np.all(np.any(upd[written] != 0, axis=(-2, -1)))
        for key in ("cross_k", "cross_v"):
            if key in cache:
                np.testing.assert_array_equal(np.asarray(new[key]),
                                              np.asarray(cache[key]))
        cache = new
