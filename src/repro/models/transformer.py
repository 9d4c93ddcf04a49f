"""Unified model stack for all assigned architectures.

One parameterised decoder (+ optional encoder) covering: dense GQA
transformers (llama3, qwen3, gemma, gemma2 incl. local/global
alternation + softcaps + post-norms), MoE (dbrx, qwen3-moe), SSM
(mamba2 SSD), hybrid attn∥SSM (hymba), encoder-decoder (whisper stub
frontend), and prefix-VLM (paligemma stub frontend).

Layers are stacked on a leading L axis and driven by ``jax.lax.scan`` so
HLO size / compile time stay bounded for full-size dry-run cells.

Three entry modes share one layer body:

* ``forward``      — training / scoring over a full sequence → logits
* ``prefill``      — forward + emit per-layer KV / SSM states → cache
* ``decode_step``  — one token against a cache (serve_step)

Named scopes mark the parts of a step in the compiled program's op
metadata, and so in a profiler trace: ``embed``, ``layers`` (the layer
scan) with ``attention``, ``ssm``, ``cross_attention`` and ``ffn`` inside
each layer, and ``unembed``.  They change no numerics.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..distributed.sharding import maybe_shard
from .layers import (attention_block, chunked_attention, mlp_block,
                     moe_block, rms_norm, rope, softcap, ssm_block)

Params = Dict[str, Any]
Cache = Dict[str, Any]

# Layer-scan unroll factor.  Default 1 = rolled (compact HLO, fast
# compiles).  The dry-run's FLOP-extrapolation pass sets this >= L so
# XLA cost analysis sees every layer (a rolled while-loop body is
# counted once by cost_analysis).  Set via `scan_unroll(n)`.
_SCAN_UNROLL: int = 1


def scan_unroll(n: int):
    """Context manager overriding the layer-scan unroll factor."""
    import contextlib

    @contextlib.contextmanager
    def _ctx():
        global _SCAN_UNROLL
        prev = _SCAN_UNROLL
        _SCAN_UNROLL = n
        try:
            yield
        finally:
            _SCAN_UNROLL = prev

    return _ctx()


def _scan(body, init, xs):
    return jax.lax.scan(body, init, xs, unroll=_SCAN_UNROLL)


# ---------------------------------------------------------------------------
# Parameter initialisation
# ---------------------------------------------------------------------------

def _layer_shapes(cfg: ArchConfig, *, encoder: bool = False) -> Dict[str, Tuple]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    shapes: Dict[str, Tuple] = {"ln1": (d,)}
    attn = cfg.attention != "none" or encoder
    if attn:
        shapes.update({
            "wq": (d, Hq, hd), "wk": (d, Hkv, hd), "wv": (d, Hkv, hd),
            "wo": (Hq, hd, d),
        })
        if cfg.qk_norm:
            shapes.update({"q_norm": (hd,), "k_norm": (hd,)})
    if encoder or cfg.d_ff > 0:
        shapes["ln2"] = (d,)
        ff = cfg.d_ff
        if not encoder and cfg.n_experts > 1:
            E = cfg.n_experts
            shapes.update({
                "w_router": (d, E),
                "w_up": (E, d, ff), "w_down": (E, ff, d),
            })
            if cfg.gated_mlp:
                shapes["w_gate"] = (E, d, ff)
        else:
            shapes.update({"w_up": (d, ff), "w_down": (ff, d)})
            if cfg.gated_mlp:
                shapes["w_gate"] = (d, ff)
    if not encoder and cfg.ssm_state > 0:
        din, N, H = cfg.ssm_inner(), cfg.ssm_state, cfg.ssm_heads
        e = 2 * din + 2 * N + H
        shapes.update({
            "w_in": (d, e), "w_out": (din, d), "conv_w": (4, din),
            "dt_bias": (H,), "A_log": (H,), "D_skip": (H,),
        })
        if cfg.family == "hybrid":
            shapes.update({"attn_branch_norm": (d,), "ssm_branch_norm": (d,)})
        elif cfg.attention == "none":
            pass  # pure SSM: ssm is the only mixer
    if cfg.post_norms and not encoder:
        shapes.update({"post_ln1": (d,), "post_ln2": (d,)})
    return shapes


def _init_stacked(key, shapes: Dict[str, Tuple], L: int, dtype, d_model: int):
    params = {}
    keys = jax.random.split(key, len(shapes))
    for (name, shp), k in zip(sorted(shapes.items()), keys):
        full = (L,) + shp
        if name.startswith(("ln", "post_ln")) or name.endswith("_norm"):
            params[name] = jnp.zeros(full, dtype)
        elif name == "A_log":
            params[name] = jnp.zeros(full, dtype)          # A = -1
        elif name in ("dt_bias", "D_skip"):
            params[name] = jnp.full(full, 0.5 if name == "D_skip" else 0.0,
                                    dtype)
        else:
            fan_in = shp[0] if len(shp) == 1 else math.prod(shp[:-1])
            if name in ("wq", "wk", "wv"):
                fan_in = d_model
            std = 1.0 / math.sqrt(max(fan_in, 1))
            params[name] = (jax.random.normal(k, full, jnp.float32)
                            * std).astype(dtype)
    return params


def init_params(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> Params:
    k_emb, k_layers, k_enc, k_head = jax.random.split(key, 4)
    d = cfg.d_model
    params: Params = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_size, d), jnp.float32)
                  / math.sqrt(d)).astype(dtype),
        "final_norm": jnp.zeros((d,), dtype),
        "layers": _init_stacked(k_layers, _layer_shapes(cfg), cfg.n_layers,
                                dtype, d),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (d, cfg.vocab_size), jnp.float32)
            / math.sqrt(d)).astype(dtype)
    if cfg.enc_dec:
        hd, Hq, Hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
        ks = jax.random.split(k_enc, 3)
        params["enc_layers"] = _init_stacked(
            ks[0], _layer_shapes(cfg, encoder=True), cfg.enc_layers, dtype, d)
        params["enc_final_norm"] = jnp.zeros((d,), dtype)
        std = 1.0 / math.sqrt(d)
        params["enc_cross"] = {
            "wk": (jax.random.normal(ks[1], (cfg.n_layers, d, Hkv, hd),
                                     jnp.float32) * std).astype(dtype),
            "wv": (jax.random.normal(ks[1], (cfg.n_layers, d, Hkv, hd),
                                     jnp.float32) * std).astype(dtype),
        }
        params["dec_cross"] = {
            "wq": (jax.random.normal(ks[2], (cfg.n_layers, d, Hq, hd),
                                     jnp.float32) * std).astype(dtype),
            "wo": (jax.random.normal(ks[2], (cfg.n_layers, Hq, hd, d),
                                     jnp.float32) * std).astype(dtype),
            "ln": jnp.zeros((cfg.n_layers, d), dtype),
        }
    return params


def layer_flags(cfg: ArchConfig) -> jnp.ndarray:
    """Per-layer is-global-attention flags (gemma2 alternation)."""
    if cfg.attention == "local_global":
        return (jnp.arange(cfg.n_layers) % 2 == 1)
    if cfg.attention == "sliding":
        return jnp.zeros(cfg.n_layers, bool)
    return jnp.ones(cfg.n_layers, bool)


_BIG_WINDOW = 1 << 30

# the cache entries that advance with the sequence (the rest are
# read-only); each is (L, batch, ...)
STATE_KEYS = ("k", "v", "ssm", "conv")


# ---------------------------------------------------------------------------
# Layer body (shared by train / prefill / decode)
# ---------------------------------------------------------------------------

def _decoder_layer(x, lp, cfg: ArchConfig, *, positions, is_global,
                   mode: str, cache=None, layer=None, cross_slice=None,
                   cache_len=None, prefix: int = 0):
    """One decoder layer.  Returns (x, new_cache).

    prefill: ``new_cache`` holds this layer's K/V and SSM states.
    decode: ``cache`` holds every layer's entries stacked on a leading
    L axis and ``layer`` is this layer's index; ``new_cache`` is those
    stacks with only this layer's new entries written.
    """
    B, S, D = x.shape
    new_cache = dict(cache) if mode == "decode" else {}
    window = None
    if cfg.attention == "sliding":
        window = cfg.window
    elif cfg.attention == "local_global":
        window = jnp.where(is_global, _BIG_WINDOW, cfg.window)

    def run_attn(xin):
        kwargs = dict(positions=positions, causal=True, window=window,
                      prefix=prefix)
        if mode == "decode":
            kwargs.update(cache_kv=(cache["k"], cache["v"]),
                          cache_len=cache_len, layer=layer)
        y, kv = attention_block(xin, lp, cfg, **kwargs)
        if kv is not None:
            new_cache["k"], new_cache["v"] = kv
        return y

    def run_ssm(xin):
        state = conv = None
        if mode == "decode":
            state, conv = (jax.lax.dynamic_index_in_dim(cache[key], layer,
                                                        keepdims=False)
                           for key in ("ssm", "conv"))
        y, hT, convT = ssm_block(xin, lp, cfg, state=state, conv_state=conv)
        if mode == "prefill":
            new_cache["ssm"], new_cache["conv"] = hT, convT
        elif mode == "decode":
            for key, new in (("ssm", hT), ("conv", convT)):
                new_cache[key] = jax.lax.dynamic_update_index_in_dim(
                    cache[key], new.astype(cache[key].dtype), layer, 0)
        return y

    # ---- mixer(s) ----------------------------------------------------------
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.family == "hybrid":
        with jax.named_scope("attention"):
            ya = run_attn(h)
        with jax.named_scope("ssm"):
            ys = run_ssm(h)
        mix = rms_norm(ya, lp["attn_branch_norm"], cfg.norm_eps) \
            + rms_norm(ys, lp["ssm_branch_norm"], cfg.norm_eps)
    elif cfg.attention == "none":
        with jax.named_scope("ssm"):
            mix = run_ssm(h)
    else:
        with jax.named_scope("attention"):
            mix = run_attn(h)
    if cfg.post_norms:
        mix = rms_norm(mix, lp["post_ln1"], cfg.norm_eps)
    x = x + mix
    x = maybe_shard(x, P(("pod", "data"), None, None))

    # ---- cross-attention (whisper decoder) ----------------------------------
    if cfg.enc_dec and cross_slice is not None:
        hq = rms_norm(x, cross_slice["ln"], cfg.norm_eps)
        with jax.named_scope("cross_attention"):
            q = jnp.einsum("bsd,dhk->bshk", hq,
                           cross_slice["wq"]).astype(x.dtype)
            attn = chunked_attention(q, cross_slice["k"], cross_slice["v"],
                                     causal=False, chunk=512)
            y = jnp.einsum("bshk,hkd->bsd", attn,
                           cross_slice["wo"]).astype(x.dtype)
        x = x + y

    # ---- FFN ------------------------------------------------------------------
    if cfg.d_ff > 0:
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        with jax.named_scope("ffn"):
            ff = (moe_block(h2, lp, cfg) if cfg.n_experts > 1
                  else mlp_block(h2, lp, cfg))
        if cfg.post_norms:
            ff = rms_norm(ff, lp["post_ln2"], cfg.norm_eps)
        x = x + ff
        x = maybe_shard(x, P(("pod", "data"), None, None))
    return x, new_cache


def _encoder_stack(params, enc_embed, cfg: ArchConfig):
    """Bidirectional encoder over stub frontend embeddings."""
    def body(x, lp):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        y, _ = attention_block(
            h, lp, cfg, positions=jnp.arange(x.shape[1])[None], causal=False)
        x = x + y
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + mlp_block(h2, lp, cfg)
        return x, None

    x, _ = _scan(body, enc_embed, params["enc_layers"])
    return rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


def _cross_kv(params, enc_out, cfg: ArchConfig):
    """Precompute per-decoder-layer cross K/V from encoder output."""
    k = jnp.einsum("bsd,ldhk->lbshk", enc_out, params["enc_cross"]["wk"])
    v = jnp.einsum("bsd,ldhk->lbshk", enc_out, params["enc_cross"]["wv"])
    return k.astype(enc_out.dtype), v.astype(enc_out.dtype)


def _embed(params, tokens, cfg: ArchConfig):
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        return maybe_shard(x, P(("pod", "data"), None, None))


def _unembed(params, x, cfg: ArchConfig):
    with jax.named_scope("unembed"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"],
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                                preferred_element_type=jnp.float32)
        logits = softcap(logits, cfg.logit_softcap)
        return maybe_shard(logits, P(("pod", "data"), None, "model"))


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

REMAT_POLICIES = {
    # minimal saved state: recompute everything except weight-stationary
    # dots — smallest footprint, most recompute (legacy default)
    "minimal": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    # save every dot output: no matmul recompute in backward — the §Perf
    # winner whenever peak memory has headroom
    "dots": jax.checkpoint_policies.dots_saveable,
    # save nothing (maximum recompute)
    "nothing": jax.checkpoint_policies.nothing_saveable,
}


def forward(
    params: Params,
    tokens: jnp.ndarray,                 # (B, S) int32
    cfg: ArchConfig,
    *,
    prefix_embed: Optional[jnp.ndarray] = None,   # VLM stub (B, P, D)
    enc_embed: Optional[jnp.ndarray] = None,      # audio stub (B, Se, D)
    remat: bool = False,
    remat_policy: str = "minimal",
) -> jnp.ndarray:
    """Training / scoring forward pass → logits (B, S[, +P], V).

    ``remat=True`` checkpoints each scanned layer (activation
    rematerialisation): backward saves only what ``remat_policy`` allows,
    the standard memory/compute trade for full-size training cells.
    """
    x = _embed(params, tokens, cfg)
    if prefix_embed is not None:
        x = jnp.concatenate([prefix_embed.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    flags = layer_flags(cfg)

    cross = None
    if cfg.enc_dec:
        if enc_embed is None:
            raise ValueError("enc-dec arch requires enc_embed")
        enc_out = _encoder_stack(params, enc_embed.astype(x.dtype), cfg)
        ck, cv = _cross_kv(params, enc_out, cfg)
        cross = {"k": ck, "v": cv, "wq": params["dec_cross"]["wq"],
                 "wo": params["dec_cross"]["wo"], "ln": params["dec_cross"]["ln"]}

    pfx = prefix_embed.shape[1] if prefix_embed is not None else 0

    def body(x, scanned):
        lp, flag = scanned[0], scanned[1]
        cs = scanned[2] if cfg.enc_dec else None
        x, _ = _decoder_layer(x, lp, cfg, positions=positions, is_global=flag,
                              mode="train", cross_slice=cs, prefix=pfx)
        return x, None

    if remat:
        body = jax.checkpoint(body, policy=REMAT_POLICIES[remat_policy])
    xs = (params["layers"], flags) + ((cross,) if cfg.enc_dec else ())
    with jax.named_scope("layers"):
        x, _ = _scan(body, x, xs)
    return _unembed(params, x, cfg)


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, *, enc_seq: int = 0) -> Cache:
    L, Hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
    cache: Cache = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.attention != "none":
        cache["k"] = jnp.zeros((L, batch, max_len, Hkv, hd), dtype)
        cache["v"] = jnp.zeros((L, batch, max_len, Hkv, hd), dtype)
    if cfg.ssm_state > 0:
        din, N, H = cfg.ssm_inner(), cfg.ssm_state, cfg.ssm_heads
        cache["ssm"] = jnp.zeros((L, batch, H, din // H, N), jnp.float32)
        cache["conv"] = jnp.zeros((L, batch, 3, din), dtype)
    if cfg.enc_dec:
        se = enc_seq or cfg.enc_seq
        cache["cross_k"] = jnp.zeros((L, batch, se, Hkv, hd), dtype)
        cache["cross_v"] = jnp.zeros((L, batch, se, Hkv, hd), dtype)
    return cache


def prefill(
    params: Params,
    tokens: jnp.ndarray,                 # (B, S)
    cfg: ArchConfig,
    *,
    prefix_embed: Optional[jnp.ndarray] = None,
    enc_embed: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Cache]:
    """Run the prompt, build the serving cache, return last-token logits."""
    x = _embed(params, tokens, cfg)
    if prefix_embed is not None:
        x = jnp.concatenate([prefix_embed.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    flags = layer_flags(cfg)

    cross = None
    if cfg.enc_dec:
        enc_out = _encoder_stack(params, enc_embed.astype(x.dtype), cfg)
        ck, cv = _cross_kv(params, enc_out, cfg)
        cross = {"k": ck, "v": cv, "wq": params["dec_cross"]["wq"],
                 "wo": params["dec_cross"]["wo"], "ln": params["dec_cross"]["ln"]}

    pfx = prefix_embed.shape[1] if prefix_embed is not None else 0

    def body(x, scanned):
        lp, flag = scanned[0], scanned[1]
        cs = scanned[2] if cfg.enc_dec else None
        x, nc = _decoder_layer(x, lp, cfg, positions=positions, is_global=flag,
                               mode="prefill", cross_slice=cs, prefix=pfx)
        return x, nc

    xs = (params["layers"], flags) + ((cross,) if cfg.enc_dec else ())
    with jax.named_scope("layers"):
        x, caches = _scan(body, x, xs)
    logits = _unembed(params, x[:, -1:], cfg)

    cache: Cache = {"pos": jnp.full((), S, jnp.int32)}
    if "k" in caches:
        cache["k"], cache["v"] = caches["k"], caches["v"]
    if "ssm" in caches:
        cache["ssm"], cache["conv"] = caches["ssm"], caches["conv"]
    if cfg.enc_dec:
        cache["cross_k"], cache["cross_v"] = cross["k"], cross["v"]
    return logits, cache


def decode_step(
    params: Params,
    tokens: jnp.ndarray,                 # (B,) or (B, 1) int32
    cfg: ArchConfig,
    cache: Cache,
) -> Tuple[jnp.ndarray, Cache]:
    """serve_step: one new token against the cache."""
    if tokens.ndim == 1:
        tokens = tokens[:, None]
    x = _embed(params, tokens, cfg)
    B = x.shape[0]
    pos = jnp.asarray(cache["pos"])   # scalar, or (B,) per-slot; -1 = free
    positions = jnp.broadcast_to(
        pos if pos.ndim == 0 else pos[:, None], (B, 1))
    flags = layer_flags(cfg)

    # the entries a step writes ride in the scan's carry as whole stacks,
    # with the layer index: each layer writes only its new entries, so a
    # donated cache is updated in place; read-only cross K/V stay in xs
    stacks = {key: cache[key] for key in STATE_KEYS if key in cache}
    xs = [params["layers"], flags]
    if cfg.enc_dec:
        xs.append({"k": cache["cross_k"], "v": cache["cross_v"],
                   "wq": params["dec_cross"]["wq"],
                   "wo": params["dec_cross"]["wo"],
                   "ln": params["dec_cross"]["ln"]})

    def body(carry, scanned):
        x, layer, stacks = carry
        lp, flag = scanned[0], scanned[1]
        cross_s = scanned[2] if cfg.enc_dec else None
        x, stacks = _decoder_layer(x, lp, cfg, positions=positions,
                                   is_global=flag, mode="decode",
                                   cache=stacks, layer=layer,
                                   cross_slice=cross_s, cache_len=pos)
        return (x, layer + 1, stacks), None

    with jax.named_scope("layers"):
        (x, _, stacks), _ = _scan(body, (x, jnp.int32(0), stacks),
                                  tuple(xs))
    logits = _unembed(params, x, cfg)

    new_cache = dict(cache, **stacks)
    new_cache["pos"] = pos + 1
    return logits[:, 0], new_cache
