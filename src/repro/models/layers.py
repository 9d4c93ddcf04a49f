"""Model-layer primitives shared by every assigned architecture.

Pure-functional JAX (no framework deps): RMSNorm, RoPE, chunked
(flash-style) attention with GQA / sliding windows / softcaps / qk-norm,
gated & plain MLPs, capacity-based MoE with scatter dispatch, and the
Mamba-2 SSD mixer (chunked state-space duality) with single-step decode.

All matmul-bearing layers accept an optional FlexBlock weight mask set
(applied multiplicatively), which is how the paper's pruning workflow
reaches the execution plane.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import batch_axes, divides, maybe_shard
from ..kernels import ops

Params = Dict[str, Any]

# Measurement override: when >1, sequence-chunk scans (attention KV
# chunks, SSD inter-chunk recurrence) cap their chunk count at this value
# and fully unroll, so XLA cost analysis counts every chunk's FLOPs and
# bytes (a rolled scan body is counted once).  Enabled only by the
# dry-run's per-layer measurement variants via ``chunk_unroll``.
_CHUNK_UNROLL: int = 1


def chunk_unroll(n: int):
    """Context manager overriding the sequence-chunk unroll factor."""
    import contextlib

    @contextlib.contextmanager
    def _ctx():
        global _CHUNK_UNROLL
        prev = _CHUNK_UNROLL
        _CHUNK_UNROLL = n
        try:
            yield
        finally:
            _CHUNK_UNROLL = prev

    return _ctx()


# ---------------------------------------------------------------------------
# Norms / positions
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embeddings. x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs        # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return cap * jnp.tanh(x / cap) if cap > 0 else x


# ---------------------------------------------------------------------------
# Attention (chunked online-softmax; GQA; windows; caps)
# ---------------------------------------------------------------------------

def _gqa_scores(q, k, scale):
    """q: (B,Sq,Hkv,G,hd), k: (B,Skv,Hkv,hd) → (B,Hkv,G,Sq,Skv) f32
    scores."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32)
    return s * jnp.asarray(scale, s.dtype)


def _attn_bias(q_idx, k_idx, *, causal, window, prefix, kv_len, valid_kv,
               B, nonneg_k: bool = False):
    """Additive attention bias (B, Tq, Tk) f32: 0 attendable / -inf masked.

    Folding every mask condition into ONE additive tensor (instead of two
    ``where``s over the full (B,H,G,Sq,ck) score tensor) halves the number
    of score-sized f32 materialisations in the flash body — a direct
    HLO-bytes win on the memory-roofline term.
    """
    ok = jnp.ones((B, q_idx.shape[1], k_idx.shape[0]), bool)
    if causal:
        cm = k_idx[None, None, :] <= q_idx[:, :, None]
        if prefix > 0:
            # prefix-LM: bidirectional attention within the prefix
            cm |= ((q_idx[:, :, None] < prefix)
                   & (k_idx[None, None, :] < prefix))
        ok &= cm
    if window is not None:
        ok &= k_idx[None, None, :] > (q_idx[:, :, None] - window)
    if kv_len is not None:
        kvl = jnp.asarray(kv_len)
        if kvl.ndim == 0:
            kvl = jnp.broadcast_to(kvl, (B,))
        ok &= k_idx[None, None, :] < kvl[:, None, None]
    if valid_kv is not None:
        ok &= (k_idx < valid_kv)[None, None, :]
    if nonneg_k:
        ok &= (k_idx >= 0)[None, None, :]
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def _attn_tile(qg, k_i, v_i, bias, carry, *, scale, attn_cap):
    """One flash tile: online-softmax update of the f32 (m, l, acc)."""
    m_prev, l_prev, acc_prev = carry
    s = _gqa_scores(qg, k_i, scale)               # (B,Hkv,G,Tq,Tk)
    s = softcap(s, attn_cap)
    s = s + bias[:, None, None].astype(s.dtype)   # -inf ⇒ exp → 0
    m_cur = jnp.maximum(m_prev, s.max(axis=-1).astype(jnp.float32))
    m_safe = jnp.where(jnp.isinf(m_cur), 0.0, m_cur)
    p = jnp.exp(s - m_safe[..., None].astype(s.dtype))
    corr = jnp.where(jnp.isinf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    l_cur = l_prev * corr + jnp.sum(p, axis=-1, dtype=jnp.float32)
    pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v_i.dtype), v_i,
                    preferred_element_type=jnp.float32)
    acc_cur = acc_prev * corr[..., None] + pv
    return m_cur, l_cur, acc_cur


def chunked_attention(
    q: jnp.ndarray,            # (B, Sq, Hq, hd)
    k: jnp.ndarray,            # (B, Skv, Hkv, hd)
    v: jnp.ndarray,            # (B, Skv, Hkv, hd)
    *,
    causal: bool = True,
    window: Optional[Any] = None,  # None = unbounded; int or traced scalar
    q_offset: Any = 0,             # absolute position of q[0] (may be traced)
    kv_len: Optional[jnp.ndarray] = None,   # valid cache length (decode)
    attn_cap: float = 0.0,
    prefix: int = 0,               # bidirectional prefix length (prefix-LM)
    chunk: int = 1024,
) -> jnp.ndarray:
    """Flash-style attention: scan over KV chunks with online softmax.

    Never materialises the (Sq × Skv) score matrix — memory is
    O(Sq × chunk) — which is what makes the 32k-prefill and 500k-decode
    cells lowerable without TB-scale buffers.
    """
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, Hkv, G, hd)
    if _CHUNK_UNROLL > 1:
        # measurement mode: bound the chunk count and unroll the scans so
        # XLA cost analysis counts every tile
        chunk = max(chunk, -(-Skv // _CHUNK_UNROLL))
        chunk = -(-chunk // 128) * 128
    nchunks = max(1, math.ceil(Skv / chunk))
    pad = nchunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, nchunks, chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nchunks, chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    # q_offset may be a scalar or a per-batch (B,) vector (serving slots
    # at heterogeneous positions) — normalise to (B, Sq).
    q_off = jnp.asarray(q_offset)
    static_q0 = isinstance(q_offset, int) and q_offset == 0
    if q_off.ndim == 0:
        q_off = jnp.broadcast_to(q_off, (B,))
    q_idx = q_off[:, None] + jnp.arange(Sq)[None, :]          # (B, Sq)
    static_window = window if isinstance(window, int) else None
    valid_kv = Skv if pad else None

    # ---- statically tiled path (training / prefill self-attention) -------
    # Tiles q as well as kv and SKIPS tiles that are fully masked by the
    # causal structure (triangular: ~2× fewer tiles) or by a static
    # sliding window (hymba w=1024 at 32k: ~16× fewer tiles).  This is
    # FullBlock sparsity applied to the attention score matrix — the same
    # block-skip idea the paper applies to CIM weight tiles.
    use_tiled = (causal and kv_len is None and static_q0
                 and Sq == Skv and Sq > chunk)
    if use_tiled:
        tq = chunk
        nq = math.ceil(Sq / tq)
        q_pad = nq * tq - Sq
        if q_pad:
            qg = jnp.pad(qg, ((0, 0), (0, q_pad), (0, 0), (0, 0), (0, 0)))
        outs = []
        for qi in range(nq):
            lo, hi = 0, min(qi, nchunks - 1)
            if prefix > 0:
                # prefix-LM: kv tiles holding prefix columns stay visible
                hi = min(max(qi, -(-prefix // chunk) - 1), nchunks - 1)
            elif static_window is not None:
                lo = max(0, (qi * tq - static_window + 1) // chunk)
            q_tile_idx = q_idx[:, qi * tq:(qi + 1) * tq]
            if q_pad and qi == nq - 1:
                q_tile_idx = jnp.pad(q_tile_idx, ((0, 0), (0, q_pad)),
                                     constant_values=Sq)
            qt = qg[:, qi * tq:(qi + 1) * tq]
            m0 = jnp.full((B, Hkv, G, qt.shape[1]), -jnp.inf, jnp.float32)
            l0 = jnp.zeros((B, Hkv, G, qt.shape[1]), jnp.float32)
            a0 = jnp.zeros((B, Hkv, G, qt.shape[1], hd), jnp.float32)
            n_tiles = hi - lo + 1
            if n_tiles <= max(4, _CHUNK_UNROLL):
                carry = (m0, l0, a0)
                for ki in range(lo, hi + 1):
                    k_tile_idx = ki * chunk + jnp.arange(chunk)
                    bias = _attn_bias(
                        q_tile_idx, k_tile_idx, causal=causal,
                        window=window, prefix=prefix, kv_len=None,
                        valid_kv=valid_kv, B=B)
                    carry = _attn_tile(qt, kc[ki], vc[ki], bias, carry,
                                       scale=scale, attn_cap=attn_cap)
                m, l, acc = carry
            else:
                # long kv range: rolled scan over the STATIC slice
                # [lo, hi] keeps HLO size bounded (one body per q-tile)
                def body(carry, inputs):
                    ki, k_i, v_i = inputs
                    k_tile_idx = ki * chunk + jnp.arange(chunk)
                    bias = _attn_bias(
                        q_tile_idx, k_tile_idx, causal=causal,
                        window=window, prefix=prefix, kv_len=None,
                        valid_kv=valid_kv, B=B)
                    return _attn_tile(qt, k_i, v_i, bias, carry,
                                      scale=scale, attn_cap=attn_cap), None
                (m, l, acc), _ = jax.lax.scan(
                    body, (m0, l0, a0),
                    (jnp.arange(lo, hi + 1), kc[lo:hi + 1], vc[lo:hi + 1]),
                    unroll=min(n_tiles, _CHUNK_UNROLL))
            o = acc / jnp.maximum(l[..., None], 1e-20)
            outs.append(o)
        out = jnp.concatenate(outs, axis=3)                 # (B,Hkv,G,Sq+,hd)
        if q_pad:
            out = out[:, :, :, :Sq]
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, hd)
        return out.astype(q.dtype)

    # ---- generic scan path (decode / cross-attn / dynamic offsets) -------
    def body(carry, inputs):
        ci, k_i, v_i = inputs
        k_idx = ci * chunk + jnp.arange(chunk)
        bias = _attn_bias(q_idx, k_idx, causal=causal, window=window,
                          prefix=prefix, kv_len=kv_len, valid_kv=valid_kv,
                          B=B)
        return _attn_tile(qg, k_i, v_i, bias, carry,
                          scale=scale, attn_cap=attn_cap), None

    m0 = jnp.full((B, Hkv, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(nchunks), kc, vc),
        unroll=min(nchunks, _CHUNK_UNROLL))
    out = acc / jnp.maximum(l[..., None], 1e-20)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, hd)   # (B,Sq,Hq,hd)
    return out.astype(q.dtype)


def _swa_seqpar_attention(x, p, cfg, mesh, *, window: int,
                          chunk: int = 1024):
    """Sequence-parallel sliding-window attention via shard_map.

    For archs whose head counts do not divide the "model" axis (hymba:
    25 q / 5 kv heads), plain SPMD replicates the whole attention block
    across all model ranks — 16× redundant score tensors dominate both
    the compute and memory roofline terms.  Here each model rank instead
    processes a contiguous 1/M slice of the QUERY sequence: with a static
    window the kv extent per rank is the STATIC size S/M + window at a
    rank-dependent offset, so every rank runs the same program on
    different sequence slices.  Per-device attention flops/bytes drop M×;
    the only collective is the output all-gather (tiny next to scores).

    Projections (q/k/v/o) run inside on the slice, so they parallelise
    too.  Returns (y, k_full, v_full) — the gathered k/v feed the prefill
    cache (DCE'd in training, where the cache is unused).
    """
    B, S, D = x.shape
    M = mesh.shape["model"]
    S_loc = S // M
    hd, Hq, Hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    G = Hq // Hkv
    W = window
    baxes = batch_axes(mesh)
    scale = 1.0 / math.sqrt(hd)
    n_tiles = max(1, S_loc // chunk)
    tq = S_loc // n_tiles

    def body(xl, wq, wk, wv, wo):
        B_loc = xl.shape[0]
        mi = jax.lax.axis_index("model")
        start = mi * S_loc
        xq = jax.lax.dynamic_slice_in_dim(xl, start, S_loc, 1)
        xp = jnp.pad(xl, ((0, 0), (W, 0), (0, 0)))
        xkv = jax.lax.dynamic_slice_in_dim(xp, start, S_loc + W, 1)
        q = jnp.einsum("bsd,dhk->bshk", xq, wq).astype(xl.dtype)
        k = jnp.einsum("bsd,dhk->bshk", xkv, wk).astype(xl.dtype)
        v = jnp.einsum("bsd,dhk->bshk", xkv, wv).astype(xl.dtype)
        qpos = start + jnp.arange(S_loc)
        kpos = start - W + jnp.arange(S_loc + W)
        q = rope(q, jnp.broadcast_to(qpos, (B_loc, S_loc)), cfg.rope_theta)
        k = rope(k, jnp.broadcast_to(kpos, (B_loc, S_loc + W)),
                 cfg.rope_theta)
        outs = []
        for j in range(n_tiles):
            qt = q[:, j * tq:(j + 1) * tq].reshape(B_loc, tq, Hkv, G, hd)
            kt = k[:, j * tq:j * tq + W + tq]
            vt = v[:, j * tq:j * tq + W + tq]
            q_idx = jnp.broadcast_to(qpos[j * tq:(j + 1) * tq],
                                     (B_loc, tq))
            k_idx = start - W + j * tq + jnp.arange(W + tq)
            bias = _attn_bias(q_idx, k_idx, causal=True, window=W,
                              prefix=0, kv_len=None, valid_kv=None,
                              B=B_loc, nonneg_k=True)
            m0 = jnp.full((B_loc, Hkv, G, tq), -jnp.inf, jnp.float32)
            l0 = jnp.zeros((B_loc, Hkv, G, tq), jnp.float32)
            a0 = jnp.zeros((B_loc, Hkv, G, tq, hd), jnp.float32)
            m, l, acc = _attn_tile(qt, kt, vt, bias, (m0, l0, a0),
                                   scale=scale, attn_cap=cfg.attn_softcap)
            o = acc / jnp.maximum(l[..., None], 1e-20)
            outs.append(o)
        out = jnp.concatenate(outs, axis=3)            # (B,Hkv,G,S_loc,hd)
        out = out.transpose(0, 3, 1, 2, 4).reshape(B_loc, S_loc, Hq, hd)
        y = jnp.einsum("bshk,hkd->bsd", out.astype(xl.dtype),
                       wo).astype(xl.dtype)
        y = jax.lax.all_gather(y, "model", axis=1, tiled=True)
        kc = jax.lax.all_gather(k[:, W:], "model", axis=1, tiled=True)
        vc = jax.lax.all_gather(v[:, W:], "model", axis=1, tiled=True)
        return y, kc, vc

    wspec = P(None, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(baxes, None, None), wspec, wspec, wspec, wspec),
        out_specs=(P(baxes, None, None), P(baxes, None, None, None),
                   P(baxes, None, None, None)),
        check_vma=False,
    )(x, p["wq"], p["wk"], p["wv"], p["wo"])


def attention_block(
    x: jnp.ndarray,             # (B, S, D)
    p: Params,                  # q/k/v/o (+ q_norm/k_norm)
    cfg,
    *,
    positions: jnp.ndarray,
    causal: bool = True,
    window: Optional[Any] = None,
    prefix: int = 0,
    cache_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    cache_len: Optional[jnp.ndarray] = None,
    layer: Optional[jnp.ndarray] = None,
    cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """Full attention sub-block: projections + RoPE + chunked attention.

    * training/prefill: ``cache_kv=None`` → attends within ``x``.
    * decode: ``cache_kv=(K, V)`` are every layer's buffers stacked
      (L, B, Smax, Hkv, hd), ``layer`` this layer's index and
      ``cache_len`` the current length, per slot or uniform (-1 marks a
      free slot); the new K/V row is written at (``layer``, slot,
      ``cache_len``), ``kernels.ops.decode_attention`` attends over each
      slot's valid positions of this layer, and the updated stacks are
      returned.  Only the new rows change, so a donated stack is updated
      in place.
    * cross-attention (whisper decoder): ``cross_kv`` precomputed from
      the encoder; no cache update.
    """
    B, S, D = x.shape
    hd, Hq, Hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads

    # sequence-parallel path: static sliding window + non-divisible heads
    # (otherwise head sharding already parallelises over "model")
    mesh = jax.sharding.get_abstract_mesh()
    if (cache_kv is None and cross_kv is None and causal
            and isinstance(window, int) and not cfg.qk_norm
            and prefix == 0 and "model" in mesh.axis_names
            and not divides(Hq, mesh)
            and S % (mesh.shape["model"] * 1024) == 0):
        y, kc, vc = _swa_seqpar_attention(x, p, cfg, mesh, window=window)
        return y, (kc, vc)

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"]).astype(x.dtype)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)

    if cross_kv is not None:
        k, v = cross_kv
        out = chunked_attention(q, k, v, causal=False, attn_cap=cfg.attn_softcap)
        new_cache = None
    else:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"]).astype(x.dtype)
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"]).astype(x.dtype)
        if cfg.qk_norm:
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        k = rope(k, positions, cfg.rope_theta)
        if cache_kv is None:
            out = chunked_attention(q, k, v, causal=causal, window=window,
                                    prefix=prefix, attn_cap=cfg.attn_softcap)
            new_cache = (k, v)
        else:
            K, V = cache_kv
            pos = jnp.asarray(cache_len)
            if pos.ndim == 0:
                # uniform position: cheap dynamic_update_slice
                start = (layer, 0, pos, 0, 0)
                K = jax.lax.dynamic_update_slice(K, k[None].astype(K.dtype),
                                                 start)
                V = jax.lax.dynamic_update_slice(V, v[None].astype(V.dtype),
                                                 start)
            else:
                # per-slot positions (serving): scatter one row per slot;
                # a free slot (pos -1) is sent out of range and dropped
                bidx = jnp.arange(K.shape[1])
                row = jnp.where(pos < 0, K.shape[2], pos)
                K = K.at[layer, bidx, row].set(k[:, 0], mode="drop")
                V = V.at[layer, bidx, row].set(v[:, 0], mode="drop")
            new_cache = (K, V)
            # attend after the write, so the new row is attended: each
            # slot's first pos + 1 positions (none for a free slot), read
            # in place in the stacks
            lengths = jnp.broadcast_to(pos + 1, (B,))
            out = ops.decode_attention(
                q[:, 0], K, V, layer, lengths, window,
                attn_cap=cfg.attn_softcap)[:, None]
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"]).astype(x.dtype)
    return y, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_block(x: jnp.ndarray, p: Params, cfg) -> jnp.ndarray:
    # gelu runs in the compute dtype: the (B,S,F) activation chain is the
    # largest per-layer tensor and f32 upcasting doubled its bytes (§Perf)
    if cfg.gated_mlp:
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"]).astype(x.dtype)
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"]).astype(x.dtype)
        h = jax.nn.gelu(g) * u
    else:
        h = jax.nn.gelu(
            jnp.einsum("bsd,df->bsf", x, p["w_up"]).astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE: top-k routing with capacity-bounded scatter dispatch
# ---------------------------------------------------------------------------

def _moe_dispatch(xt, w_router, E, K, capacity_factor, dtype):
    """Route tokens: returns (eb, top_p, keep, dest, tok_idx, C).

    Sort-based capacity dispatch into an (E·C+1, D) scatter buffer (no
    one-hot einsum: keeps HLO FLOPs ≈ active FLOPs so the roofline's
    useful-compute ratio stays honest).  Overflow beyond capacity C is
    dropped — standard GShard capacity semantics.
    """
    T, D = xt.shape
    logits = jnp.einsum("td,de->te", xt, w_router,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                        # (T, K)
    top_p = (top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)).astype(dtype)

    C = max(1, math.ceil(T * K / E * capacity_factor))
    e_flat = top_e.reshape(-1)                                    # (T·K,)
    # position of each (token, slot) within its expert via sort
    order = jnp.argsort(e_flat)
    ranks = jnp.zeros((T * K,), jnp.int32).at[order].set(
        jnp.arange(T * K, dtype=jnp.int32))
    sorted_e = e_flat[order]
    # start offset of each expert group in the sorted order
    starts = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos = ranks - starts[e_flat]                                  # (T·K,)
    keep = pos < C
    dest = jnp.where(keep, e_flat * C + pos, E * C)               # overflow bin

    tok_idx = jnp.repeat(jnp.arange(T), K)
    buf = jnp.zeros((E * C + 1, D), dtype)
    buf = buf.at[dest].add(xt[tok_idx])
    return buf[:-1].reshape(E, C, D), top_p, keep, dest, tok_idx, C


def _moe_combine(eo, top_p, keep, dest, tok_idx, T, D, dtype):
    """Inverse of dispatch: gather expert outputs back per token."""
    E_C = eo.shape[0] * eo.shape[1]
    out_flat = jnp.concatenate([eo.reshape(E_C, D),
                                jnp.zeros((1, D), dtype)])
    gathered = out_flat[jnp.where(keep, dest, E_C)]               # (T·K, D)
    weighted = gathered * top_p.reshape(-1)[:, None]
    return jnp.zeros((T, D), dtype).at[tok_idx].add(weighted)


def _expert_ffn(eb, p, cfg, dtype):
    """(E, C, D) → (E, C, D) through per-expert (optionally gated) MLPs.

    gelu in compute dtype: the (E,C,F) expert activation chain dominated
    dbrx's memory roofline when upcast to f32 (§Perf it4)."""
    if cfg.gated_mlp:
        g = jnp.einsum("ecd,edf->ecf", eb, p["w_gate"]).astype(dtype)
        u = jnp.einsum("ecd,edf->ecf", eb, p["w_up"]).astype(dtype)
        h = jax.nn.gelu(g) * u
    else:
        h = jax.nn.gelu(
            jnp.einsum("ecd,edf->ecf", eb, p["w_up"]).astype(dtype))
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"]).astype(dtype)


def _moe_block_global(x: jnp.ndarray, p: Params, cfg) -> jnp.ndarray:
    """Single-device / no-mesh MoE path (global dispatch)."""
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    eb, top_p, keep, dest, tok_idx, C = _moe_dispatch(
        xt, p["w_router"], cfg.n_experts, cfg.top_k, cfg.capacity_factor,
        x.dtype)
    eb = maybe_shard(eb, P("model", None, None))
    eo = _expert_ffn(eb, p, cfg, x.dtype)
    eo = maybe_shard(eo, P("model", None, None))
    y = _moe_combine(eo, top_p, keep, dest, tok_idx, T, D, x.dtype)
    return y.reshape(B, S, D)


def _moe_block_ep(x: jnp.ndarray, p: Params, cfg, mesh, baxes) -> jnp.ndarray:
    """Expert-parallel MoE via shard_map (the §Perf fix for MoE cells).

    The global-scatter path cannot be data-parallelised by SPMD (the
    argsort/scatter force a global token ordering, so every device
    re-dispatches ALL tokens and the expert einsums only parallelise over
    the "model" axis — a ~data×-inflation of expert FLOPs, plus an
    all-reduce of the whole (E·C·D) buffer per layer).  Here each device
    routes only its local token slice, exchanges capacity blocks with an
    all_to_all over "model", computes its resident experts, and reverses
    the exchange — per-device expert FLOPs = global/(data·model) and the
    only collectives are two a2a's + one output all-gather per layer.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    M = mesh.shape["model"]
    E_loc = E // M

    w_gate = p.get("w_gate")
    gated = w_gate is not None
    # mirrors spec_for_param's expert-parallel assignment of these leaves
    wspec = P("model", None, None)

    def ep_body(xl, wr, wu, wd, wg):
        B_loc = xl.shape[0]
        T_loc = B_loc * S
        xt = xl.reshape(T_loc, D)
        # each model-rank routes a disjoint 1/M slice of the local tokens
        # (the slice is padded so T_loc need not divide M)
        Ts = -(-T_loc // M)
        pad = Ts * M - T_loc
        if pad:
            xt = jnp.concatenate([xt, jnp.zeros((pad, D), xt.dtype)])
        mi = jax.lax.axis_index("model")
        xs = jax.lax.dynamic_slice_in_dim(xt, mi * Ts, Ts, axis=0)
        eb, top_p, keep, dest, tok_idx, C = _moe_dispatch(
            xs, wr, E, K, cfg.capacity_factor, xl.dtype)

        # exchange capacity blocks: dim0 of the result = source rank
        ex = jax.lax.all_to_all(
            eb.reshape(M, E_loc, C, D), "model", 0, 0)    # (M, E_loc, C, D)
        ex = ex.transpose(1, 0, 2, 3).reshape(E_loc, M * C, D)

        lp = {"w_up": wu, "w_down": wd}
        if gated:
            lp["w_gate"] = wg
        eo = _expert_ffn(ex, lp, cfg, xl.dtype)           # (E_loc, M·C, D)

        eo = eo.reshape(E_loc, M, C, D).transpose(1, 0, 2, 3)
        eo = jax.lax.all_to_all(eo, "model", 0, 0)        # back to sources
        eo = eo.reshape(E, C, D)

        ys = _moe_combine(eo, top_p, keep, dest, tok_idx, Ts, D, xl.dtype)
        # reassemble the full local token set on every model rank
        yt = jax.lax.all_gather(ys, "model", axis=0, tiled=True)
        if pad:
            yt = yt[:T_loc]
        return yt.reshape(B_loc, S, D)

    gate_arg = w_gate if gated else jnp.zeros((), x.dtype)
    gate_spec = wspec if gated else P()
    return jax.shard_map(
        ep_body, mesh=mesh,
        in_specs=(P(baxes, None, None), P(None, None),
                  wspec, wspec, gate_spec),
        out_specs=P(baxes, None, None),
        check_vma=False,
    )(x, p["w_router"], p["w_up"], p["w_down"], gate_arg)


def moe_block(x: jnp.ndarray, p: Params, cfg) -> jnp.ndarray:
    """Capacity-based MoE.  Dispatches to the shard_map expert-parallel
    path on a mesh with a "model" axis that divides the expert count;
    falls back to the global-dispatch path otherwise (single device /
    smoke tests)."""
    mesh = jax.sharding.get_abstract_mesh()
    baxes = batch_axes(mesh)
    if (divides(cfg.n_experts, mesh)
            and divides(x.shape[0], mesh, baxes)):
        return _moe_block_ep(x, p, cfg, mesh, baxes)
    return _moe_block_global(x, p, cfg)


# ---------------------------------------------------------------------------
# Mamba-2 SSD mixer (chunked state-space duality) + single-step decode
# ---------------------------------------------------------------------------

def _ssd_chunked(xh, dt, A, Bm, Cm, Q):
    """Chunked SSD (Dao & Gu 2024, alg. of §6): intra-chunk quadratic
    term + inter-chunk state recurrence.

    xh: (B,S,H,Pd); dt: (B,S,H) >0; A: (H,) <0; Bm/Cm: (B,S,N).
    Returns y: (B,S,H,Pd) and final state (B,H,Pd,N).
    """
    Bsz, S, H, Pd = xh.shape
    N = Bm.shape[-1]
    nc = S // Q
    xq = xh.reshape(Bsz, nc, Q, H, Pd)
    dtq = dt.reshape(Bsz, nc, Q, H)
    Bq = Bm.reshape(Bsz, nc, Q, N)
    Cq = Cm.reshape(Bsz, nc, Q, N)

    loga = dtq * A[None, None, None, :]                # (B,nc,Q,H) ≤ 0
    cum = jnp.cumsum(loga, axis=2)                     # within-chunk cumsum
    total = cum[:, :, -1, :]                           # (B,nc,H)

    # intra-chunk: scores[i,j] = C_i·B_j · exp(cum_i - cum_j) for j ≤ i.
    # The (B,nc,Q,Q,H) tensors dominate SSD memory traffic — materialise
    # the masked scores directly in the compute dtype (bf16): halves the
    # bytes of the largest tensor chain with f32 kept only inside exp/cum.
    cb = jnp.einsum("bcqn,bckn->bcqk", Cq, Bq,
                    preferred_element_type=jnp.float32)  # (B,nc,Q,Q)
    # mask before the exp: above the diagonal cum_i - cum_j ≥ 0 grows
    # with Q and overflows f32, and a where() after the exp would pass
    # 0·inf = NaN back through the gradient
    tri = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(
        tri, cum[:, :, :, None, :] - cum[:, :, None, :, :], -jnp.inf))
    scores = (cb[..., None] * decay).astype(xh.dtype)  # (B,nc,Q,Q,H)
    xdt = xq * dtq[..., None]                           # (B,nc,Q,H,Pd)
    y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", scores, xdt,
                         preferred_element_type=jnp.float32)

    # chunk states: S_c = Σ_j exp(total - cum_j) · B_j ⊗ (x_j·dt_j)
    w = jnp.exp(total[:, :, None, :] - cum)             # (B,nc,Q,H)
    Sc = jnp.einsum("bcqn,bcqh,bcqhp->bchpn", Bq, w.astype(xh.dtype), xdt,
                    preferred_element_type=jnp.float32)  # (B,nc,H,Pd,N)

    # inter-chunk recurrence: h_c = exp(total_c)·h_{c-1} + S_c
    def scan_fn(h_prev, inp):
        tot_c, S_c = inp
        h_new = h_prev * jnp.exp(tot_c)[:, :, None, None] + S_c
        return h_new, h_prev

    h0 = jnp.zeros((Bsz, H, Pd, N), jnp.float32)
    hT, h_prevs = jax.lax.scan(
        scan_fn, h0,
        (total.transpose(1, 0, 2), Sc.transpose(1, 0, 2, 3, 4)),
        unroll=min(nc, _CHUNK_UNROLL))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)          # (B,nc,H,Pd,N)

    # inter-chunk output: y_i += C_i · h_{c-1} · exp(cum_i)
    y_inter = jnp.einsum("bcqn,bchpn->bcqhp", Cq, h_prevs.astype(xh.dtype),
                         preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(cum)[..., None]
    y = (y_intra + y_inter).reshape(Bsz, S, H, Pd)
    return y.astype(xh.dtype), hT


def ssm_block(
    x: jnp.ndarray, p: Params, cfg, *,
    state: Optional[jnp.ndarray] = None,
    conv_state: Optional[jnp.ndarray] = None,
    chunk: Optional[int] = None,
):
    """Mamba-2 mixer.  Training/prefill: chunked SSD over the sequence.
    Decode (S==1 with ``state``): single-step recurrence.

    Layout: in_proj → [z (din), xs (din), B (N), C (N), dt (H)];
    4-tap depthwise causal conv on xs; SSD; gated output (z); out_proj.
    Returns (y, new_state, new_conv_state).
    """
    B, S, D = x.shape
    if chunk is None:
        chunk = getattr(cfg, "ssm_chunk", 256)
    din = cfg.ssm_inner(D)
    N, H = cfg.ssm_state, cfg.ssm_heads
    Pd = din // H
    proj = jnp.einsum("bsd,de->bse", x, p["w_in"]).astype(x.dtype)
    z, xs, Bm, Cm, dt_raw = jnp.split(
        proj, [din, 2 * din, 2 * din + N, 2 * din + 2 * N], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                     # (H,) < 0

    # depthwise causal conv (kernel 4) on xs
    kern = p["conv_w"]                                                # (4, din)
    if state is None or S > 1:
        xpad = jnp.pad(xs, ((0, 0), (3, 0), (0, 0)))
        xc = sum(xpad[:, i:i + S, :] * kern[3 - i] for i in range(4))
        new_conv = xpad[:, -3:, :]
    else:
        hist = jnp.concatenate([conv_state, xs], axis=1)              # (B,4,din)
        xc = (hist * kern[::-1].T[None].transpose(0, 2, 1)).sum(axis=1,
                                                                keepdims=True)
        new_conv = hist[:, 1:, :]
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(x.dtype)
    xh = xc.reshape(B, S, H, Pd)

    if state is None or S > 1:
        pad = (-S) % chunk
        if pad:
            xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
            Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        y, hT = _ssd_chunked(xh, dt, A, Bm, Cm, min(chunk, xh.shape[1]))
        y = y[:, :S]
    else:
        # single-step: h' = exp(dt·A)·h + dt·(B ⊗ x);  y = C·h'
        a = jnp.exp(dt[:, 0, :] * A[None, :])                        # (B,H)
        upd = jnp.einsum("bn,bhp->bhpn", Bm[:, 0], xh[:, 0] * dt[:, 0, :, None])
        hT = state * a[:, :, None, None] + upd
        y = jnp.einsum("bn,bhpn->bhp", Cm[:, 0], hT)[:, None]        # (B,1,H,Pd)
    y = y + xh[:, :S] * p["D_skip"][None, None, :, None]
    y = y.reshape(B, S, din) * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"]).astype(x.dtype)
    return out, hT, new_conv
