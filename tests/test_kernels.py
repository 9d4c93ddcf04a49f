"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.flexblock import IntraBlock
from repro.core.pruning import intrablock_mask
from repro.kernels import (bitserial_zero_profile, block_importance,
                           block_sparse_matmul, compress_fullblock,
                           compress_intrablock, decompress_intrablock,
                           intrablock_gather_matmul)
from repro.kernels import ref as R

RNG = np.random.default_rng(7)


def _random_fullblock(K, N, bm, bn, keep_frac=0.5):
    w = RNG.normal(size=(K, N)).astype(np.float32)
    keep = RNG.random((K // bm, N // bn)) < keep_frac
    keep[0, :] = True  # at least one block per column group
    mask = np.repeat(np.repeat(keep, bm, 0), bn, 1)
    return w, keep, (w * mask)


@pytest.mark.parametrize("K,N,bm,bn,B", [
    (128, 64, 32, 32, 8),
    (256, 128, 64, 64, 32),
    (512, 256, 128, 128, 16),
    (384, 128, 128, 64, 5),     # B not a multiple of the tile
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_block_sparse_matmul_sweep(K, N, bm, bn, B, dtype):
    w, keep, wm = _random_fullblock(K, N, bm, bn)
    w = np.asarray(jnp.asarray(w, dtype))
    wm = np.asarray(jnp.asarray(wm, dtype=jnp.float32))
    wc, idx = compress_fullblock(np.asarray(jnp.asarray(w, dtype)), keep, bm, bn)
    x = jnp.asarray(RNG.normal(size=(B, K)), dtype)
    dense = np.asarray(jnp.asarray(x, jnp.float32)) @ (
        np.asarray(jnp.asarray(w, jnp.float32))
        * np.repeat(np.repeat(keep, bm, 0), bn, 1))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    y_ref = R.block_sparse_matmul_ref(x, jnp.asarray(wc), jnp.asarray(idx))
    y_pal = block_sparse_matmul(x, jnp.asarray(wc), jnp.asarray(idx),
                                impl="pallas_interpret", tile_b=8)
    scale = max(np.abs(dense).max(), 1.0)
    np.testing.assert_allclose(np.asarray(y_ref, np.float32) / scale,
                               dense / scale, atol=tol)
    np.testing.assert_allclose(np.asarray(y_pal, np.float32) / scale,
                               dense / scale, atol=tol)


@pytest.mark.parametrize("K,N,m,B", [
    (64, 32, 2, 8), (128, 64, 4, 16), (256, 128, 8, 7),
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_intrablock_gather_matmul_sweep(K, N, m, B, dtype):
    w = RNG.normal(size=(K, N)).astype(np.float32)
    ratio = (m - 1) / m
    mask = intrablock_mask(jnp.asarray(w), IntraBlock(m, 1, ratio),
                           align_cols=True)
    wc, ridx = compress_intrablock(w, mask, m)
    x = jnp.asarray(RNG.normal(size=(B, K)), dtype)
    dense = np.asarray(jnp.asarray(x, jnp.float32)) @ (w * mask)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    y_ref = R.intrablock_gather_matmul_ref(
        x, jnp.asarray(wc, dtype), jnp.asarray(ridx))
    y_pal = intrablock_gather_matmul(
        x, jnp.asarray(wc, dtype), jnp.asarray(ridx),
        impl="pallas_interpret", tile_b=8, tile_n=32)
    scale = max(np.abs(dense).max(), 1.0)
    np.testing.assert_allclose(np.asarray(y_ref, np.float32) / scale,
                               dense / scale, atol=tol)
    np.testing.assert_allclose(np.asarray(y_pal, np.float32) / scale,
                               dense / scale, atol=tol)


def test_compress_intrablock_rejects_unaligned():
    w = RNG.normal(size=(8, 4)).astype(np.float32)
    mask = intrablock_mask(jnp.asarray(w), IntraBlock(2, 1, 0.5))
    if np.all(mask.reshape(4, 2, 4) == mask.reshape(4, 2, 4)[:, :, :1]):
        pytest.skip("mask happened to be aligned")
    with pytest.raises(ValueError):
        compress_intrablock(w, mask, 2)
    # general path: masked-dense decompression is exact
    np.testing.assert_array_equal(decompress_intrablock(w, mask), w * mask)


@pytest.mark.parametrize("M,N,bm,bn", [
    (64, 64, 8, 8), (128, 256, 32, 16), (256, 128, 64, 128),
])
@pytest.mark.parametrize("crit", ["l1", "l2"])
def test_block_importance_sweep(M, N, bm, bn, crit):
    w = jnp.asarray(RNG.normal(size=(M, N)), jnp.float32)
    ref = R.block_importance_ref(w, bm, bn, crit)
    pal = block_importance(w, bm, bn, crit, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), rtol=1e-5)


@pytest.mark.parametrize("V,K,g", [(16, 64, 16), (100, 96, 32), (128, 256, 64)])
def test_bitserial_profile_sweep(V, K, g):
    q = jnp.asarray(RNG.integers(-40, 40, size=(V, K)), jnp.int8)
    ref = np.asarray(R.bitserial_zero_profile_ref(q, g))
    pal = np.asarray(bitserial_zero_profile(q, g, impl="pallas_interpret"))
    np.testing.assert_array_equal(pal, ref)
    skippable, total = ref
    assert 0 <= skippable <= total


def test_bitserial_all_zero_input():
    q = jnp.zeros((8, 32), jnp.int8)
    s, t = np.asarray(R.bitserial_zero_profile_ref(q, 8))
    assert s == t  # everything skippable


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Sq,Skv,hd,tq,tk,causal,window", [
    (128, 128, 32, 32, 32, True, None),    # causal triangular skip
    (256, 256, 64, 64, 64, True, 64),      # sliding-window skip
    (128, 256, 32, 32, 64, False, None),   # cross/bidirectional
    (256, 256, 16, 128, 128, True, None),  # MXU-sized q tiles
    (128, 128, 32, 32, 32, False, 64),     # non-causal + window: completes
    (128, 128, 32, 32, 32, True, 64),      # the causal × window × dtype
                                           # regression cross vs ref.py
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_sweep(Sq, Skv, hd, tq, tk, causal, window, dtype):
    from repro.kernels import flash_attention
    from repro.kernels.ref import flash_attention_ref
    B, Hq, Hkv = 2, 4, 2
    q = jnp.asarray(RNG.normal(size=(B, Sq, Hq, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Skv, Hkv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Skv, Hkv, hd)), dtype)
    y = flash_attention(q, k, v, causal=causal, window=window,
                        impl="pallas_interpret", tile_q=tq, tile_k=tk)
    G = Hq // Hkv
    kf = jnp.repeat(k, G, axis=2).transpose(0, 2, 1, 3).reshape(B * Hq, Skv, hd)
    vf = jnp.repeat(v, G, axis=2).transpose(0, 2, 1, 3).reshape(B * Hq, Skv, hd)
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, hd)
    ref = flash_attention_ref(qf, kf, vf, causal=causal, window=window)
    ref = ref.reshape(B, Hq, Sq, hd).transpose(0, 2, 1, 3)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_attention_matches_model_attention():
    """Kernel ≡ the execution-plane chunked_attention (same math)."""
    from repro.kernels import flash_attention
    from repro.models.layers import chunked_attention
    B, S, Hq, Hkv, hd = 2, 256, 4, 2, 32
    q = jnp.asarray(RNG.normal(size=(B, S, Hq, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)), jnp.float32)
    y_kernel = flash_attention(q, k, v, causal=True, window=64,
                               impl="pallas_interpret", tile_q=64, tile_k=64)
    y_model = chunked_attention(q, k, v, causal=True, window=64, chunk=64)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               atol=5e-5)


# ---------------------------------------------------------------------------
# ragged decode attention kernel
# ---------------------------------------------------------------------------

_BIG = 1 << 30


@pytest.mark.parametrize("layer,G,window,cap,dtype", [
    (0, 4, None, 0.0, jnp.bfloat16),
    (1, 4, 100, 0.0, jnp.bfloat16),        # window binds
    (2, 4, _BIG, 50.0, jnp.bfloat16),      # window does not bind
    (2, 1, None, 50.0, np.float32),
    (0, 1, 100, 0.0, np.float32),
    (1, 4, _BIG, 50.0, np.float32),
    (1, 1, 100, 50.0, jnp.bfloat16),
], ids=["first-g4-bf16", "mid-g4-window", "last-g4-wide-cap",
        "last-g1-cap-f32", "first-g1-window-f32", "mid-g4-wide-cap-f32",
        "mid-g1-window-cap-bf16"])
@pytest.mark.parametrize("lengths", [(0, 1, 127, 128, 129, 256), 200],
                         ids=["per-slot", "scalar-pos"])
def test_decode_attention_sweep(layer, G, window, cap, dtype, lengths):
    """Kernel (interpret mode) ≡ the XLA decode path (this layer's block
    of the stacks + chunked_attention), with the window traced as the
    layer scan passes it; slots of length 0 are left out."""
    import jax
    from repro.kernels import ops
    L, B, Smax, Hkv, hd = 3, 6, 256, 2, 128
    q = jnp.asarray(RNG.normal(size=(B, G * Hkv, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(L, B, Smax, Hkv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(L, B, Smax, Hkv, hd)), dtype)
    n = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))

    def attend(impl):
        fn = jax.jit(lambda q, k, v, layer, n, w: ops.decode_attention(
            q, k, v, layer, n, None if window is None else w,
            attn_cap=cap, impl=impl))
        return np.asarray(fn(q, k, v, jnp.int32(layer), n,
                             jnp.int32(window or 0)), np.float32)

    y, ref = attend("pallas_interpret"), attend("ref")
    live = np.asarray(n) > 0
    assert np.all(np.isfinite(y))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(y[live], ref[live], atol=tol)
