"""Statically tiled attention path: exactness against a dense oracle
across mask configurations (causal / sliding window / prefix-LM /
non-divisible chunks) and on the generic scan path."""
from __future__ import annotations

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models.layers import chunked_attention


def ref_attn(q, k, v, causal=True, window=None, prefix=0):
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    s = np.einsum("bqhd,bkhd->bhqk", q.astype(np.float32),
                  np.repeat(k.astype(np.float32), G, axis=2)) / math.sqrt(hd)
    qi = np.arange(Sq)[:, None]
    ki = np.arange(Skv)[None, :]
    ok = np.ones((Sq, Skv), bool)
    if causal:
        cm = ki <= qi
        if prefix > 0:
            cm |= (qi < prefix) & (ki < prefix)
        ok &= cm
    if window is not None:
        ok &= ki > qi - window
    s = np.where(ok[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p,
                     np.repeat(v.astype(np.float32), G, axis=2))


def _qkv(B=2, S=512, Hq=4, Hkv=2, hd=16, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, S, Hq, hd)).astype(np.float32)
    k = rng.standard_normal((B, S, Hkv, hd)).astype(np.float32)
    v = rng.standard_normal((B, S, Hkv, hd)).astype(np.float32)
    return q, k, v


@pytest.mark.parametrize("window,prefix,chunk", [
    (None, 0, 128),      # causal triangular tiling
    (64, 0, 128),        # static window skip
    (200, 0, 96),        # window not a chunk multiple
    (None, 100, 128),    # prefix-LM bidirectional prefix
    (None, 0, 512),      # single tile (Sq == chunk boundary)
])
def test_tiled_matches_dense(window, prefix, chunk):
    q, k, v = _qkv()
    out = np.asarray(chunked_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        causal=True, window=window, prefix=prefix, chunk=chunk))
    ref = ref_attn(q, k, v, True, window, prefix)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=2e-4)


def test_generic_scan_path_matches_dense():
    # Sq != Skv via kv_len/cache shape forces the scan path
    q, k, v = _qkv(S=256)
    out = np.asarray(chunked_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        causal=False, chunk=64))
    s = np.einsum("bqhd,bkhd->bhqk",
                  q.astype(np.float32),
                  np.repeat(k.astype(np.float32), 2, axis=2)) / 4.0
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", p,
                    np.repeat(v.astype(np.float32), 2, axis=2))
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=2e-4)

