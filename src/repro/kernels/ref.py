"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each kernel's test sweeps
shapes/dtypes and asserts allclose against the function here.  They are
also the CPU fallback used by :mod:`repro.kernels.ops` outside TPU.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "block_sparse_matmul_ref",
    "intrablock_gather_matmul_ref",
    "block_importance_ref",
    "bitserial_zero_profile_ref",
    "flash_attention_ref",
    "decode_attention_ref",
]


def flash_attention_ref(
    q: jnp.ndarray,          # (BH, Sq, hd)
    k: jnp.ndarray,          # (BH, Skv, hd)
    v: jnp.ndarray,          # (BH, Skv, hd)
    *,
    causal: bool = True,
    window=None,
) -> jnp.ndarray:
    """Dense softmax attention with causal/sliding-window masking."""
    hd = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(hd))
    qi = jnp.arange(q.shape[1])[:, None]
    ki = jnp.arange(k.shape[1])[None, :]
    ok = jnp.ones(s.shape[1:], bool)
    if causal:
        ok &= ki <= qi
    if window is not None:
        ok &= ki > qi - window
    s = jnp.where(ok[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def decode_attention_ref(q, k, v, layer, lengths, window=None, *,
                         attn_cap: float = 0.0) -> jnp.ndarray:
    """Single-query decode attention as the model's XLA path runs it:
    this layer's whole (B, Smax, Hkv, hd) block out of the stacked
    (L, B, Smax, Hkv, hd) cache, then ``chunked_attention`` in ONE chunk
    spanning the cache, with positions from each slot's length on (and
    before its window) masked.  q: (B, Hq, hd); lengths: (B,).

    One chunk keeps the scores (B, H, 1, Smax) small, and XLA shards the
    sequence dim cleanly (flash-decode: partial softmax per shard + small
    cross-shard reductions), whereas a chunk scan would fight the
    sequence sharding and replicate compute.
    """
    # models import this package, so bind the model's attention late
    from ..models.layers import chunked_attention
    k = jax.lax.dynamic_index_in_dim(k, layer, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
    out = chunked_attention(q[:, None], k, v, causal=True, window=window,
                            q_offset=jnp.asarray(lengths) - 1,
                            attn_cap=attn_cap, chunk=k.shape[1])
    return out[:, 0]


def block_sparse_matmul_ref(
    x: jnp.ndarray,          # (B, K)
    w_comp: jnp.ndarray,     # (Gn, L, bm, bn) gathered non-zero K-blocks
    idx: jnp.ndarray,        # (Gn, L) int32 K-block indices, -1 = padding
) -> jnp.ndarray:
    """y[b, j*bn:(j+1)*bn] = Σ_l x[b, idx[j,l]*bm : +bm] @ w_comp[j, l].

    The FullBlock-compressed matmul: only surviving (bm × bn) weight
    blocks are stored; the block index directs which input slice feeds
    each block — the TPU analogue of CIM block-index input routing.
    """
    Gn, L, bm, bn = w_comp.shape
    B, K = x.shape

    def per_ncol(w_j, idx_j):
        def body(carry, li):
            acc = carry
            i = idx_j[li]
            valid = i >= 0
            start = jnp.maximum(i, 0) * bm
            xb = jax.lax.dynamic_slice(x, (0, start), (B, bm))
            part = jnp.dot(xb, w_j[li], preferred_element_type=jnp.float32)
            return acc + jnp.where(valid, part, 0.0), None

        acc0 = jnp.zeros((B, bn), jnp.float32)
        acc, _ = jax.lax.scan(body, acc0, jnp.arange(L))
        return acc

    out = jax.vmap(per_ncol, in_axes=(0, 0), out_axes=1)(w_comp, idx)  # (B, Gn, bn)
    return out.reshape(B, Gn * bn).astype(x.dtype)


def intrablock_gather_matmul_ref(
    x: jnp.ndarray,          # (B, K)
    w_comp: jnp.ndarray,     # (Kc, N) column-compressed weights
    row_idx: jnp.ndarray,    # (Kc,) int32: original K row of each compressed row
) -> jnp.ndarray:
    """y = x[:, row_idx] @ w_comp — the IntraBlock N:M column-sparse
    matmul; the row gather is the mux-based input selection (§IV-C ③)."""
    xg = jnp.take(x, row_idx, axis=1)
    return jnp.dot(xg, w_comp, preferred_element_type=jnp.float32).astype(x.dtype)


def block_importance_ref(
    w: jnp.ndarray, bm: int, bn: int, criterion: str = "l1"
) -> jnp.ndarray:
    """Eq. 1 block losses: (M/m, N/n) sums of ρ(w) per block."""
    M, N = w.shape
    assert M % bm == 0 and N % bn == 0, "ref expects whole blocks"
    rho = jnp.abs(w) if criterion == "l1" else jnp.square(w)
    rho = rho.astype(jnp.float32)
    return rho.reshape(M // bm, bm, N // bn, bn).sum(axis=(1, 3))


def bitserial_zero_profile_ref(
    q: jnp.ndarray, group_rows: int, n_bits: int = 8
) -> jnp.ndarray:
    """Count of all-zero (vector × group × bit) slots, as int32 scalar,
    plus the total slot count: returns (skippable, total).

    ``q`` int8 of shape (V, K); K padded up to a multiple of group_rows
    with zeros (paddings are genuinely skippable slots in hardware, and
    both the kernel and oracle count them identically).
    """
    V, K = q.shape
    pad = (-K) % group_rows
    mag = jnp.abs(q.astype(jnp.int32))
    if pad:
        mag = jnp.pad(mag, ((0, 0), (0, pad)))
    G = mag.shape[1] // group_rows
    grouped = mag.reshape(V, G, group_rows)
    skippable = jnp.int32(0)
    for b in range(n_bits):
        plane = (grouped >> b) & 1
        group_or = plane.max(axis=-1)
        skippable += jnp.sum(group_or == 0, dtype=jnp.int32)
    total = jnp.int32(V * G * n_bits)
    return jnp.stack([skippable, total])
