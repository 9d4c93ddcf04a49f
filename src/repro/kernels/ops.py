"""Public kernel API: layout builders + impl dispatch.

``impl`` semantics for every op:

* ``"auto"``   — compiled Pallas on TPU, jnp oracle elsewhere (CPU tests
  exercise the kernels explicitly via ``impl="pallas_interpret"``).
* ``"pallas"`` — compiled Pallas (TPU target).
* ``"pallas_interpret"`` — Pallas kernel body interpreted in Python on
  CPU; the correctness-validation path in this container.
* ``"ref"``    — the pure-jnp oracle.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import ref as _ref
from .bitserial_profile import bitserial_zero_profile_pallas
from .block_importance import block_importance_pallas
from .block_sparse_matmul import block_sparse_matmul_pallas
from .decode_attention import block_size as decode_attention_block
from .decode_attention import decode_attention_pallas
from .decode_attention import fits as _decode_fits
from .flash_attention import flash_attention_pallas
from .intrablock_matmul import intrablock_gather_matmul_pallas

__all__ = [
    "compress_fullblock",
    "compress_intrablock",
    "block_sparse_matmul",
    "intrablock_gather_matmul",
    "block_importance",
    "bitserial_zero_profile",
    "flash_attention",
    "decode_attention",
    "decode_attention_block",
    "resolve_decode_attention",
]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    return impl


# ---------------------------------------------------------------------------
# Layout builders (host-side, run once at deployment/pruning time)
# ---------------------------------------------------------------------------

def compress_fullblock(
    w: np.ndarray, keep: np.ndarray, bm: int, bn: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Pack a FullBlock-pruned matrix into the kernel layout.

    ``keep``: (K/bm, N/bn) bool block keep-grid.
    Returns ``w_comp`` (Gn, L, bm, bn) and ``idx`` (Gn, L) int32 with -1
    padding, where L = max surviving K-blocks over output-column groups
    (the ragged-compression shape the paper's rearrangement step
    equalises; L is the post-'pad'-rearrangement extent).
    """
    K, N = w.shape
    gk, gn = keep.shape
    if gk * bm != K or gn * bn != N:
        raise ValueError(f"keep grid {keep.shape} mismatches {w.shape}/({bm},{bn})")
    L = max(1, int(keep.sum(axis=0).max()))
    w_comp = np.zeros((gn, L, bm, bn), dtype=w.dtype)
    idx = np.full((gn, L), -1, dtype=np.int32)
    for j in range(gn):
        ks = np.nonzero(keep[:, j])[0]
        for l, kblk in enumerate(ks):
            w_comp[j, l] = w[kblk * bm:(kblk + 1) * bm, j * bn:(j + 1) * bn]
            idx[j, l] = kblk
    return w_comp, idx


def compress_intrablock(
    w: np.ndarray, mask: np.ndarray, m: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Pack a *row-aligned* IntraBlock(m,1)-pruned matrix.

    Hardware-adaptation note (see DESIGN.md §2.2): CIM muxes select a
    different surviving row per (block, column) cell; the TPU MXU has no
    sub-tile gather, so per-column N:M sparsity yields **no MXU FLOP
    saving** — the general case runs as masked-dense
    (:func:`decompress_intrablock`).  When survivor positions are shared
    across columns (row-aligned IntraBlock, produced by
    ``intrablock_mask(..., align_cols=True)``), compression is a pure
    row-subset selection that maps to an input row-gather + dense
    matmul, keeping both the storage and the compute saving.

    Returns ``w_comp`` (Kc, N) = ``w[row_idx]`` and ``row_idx`` (Kc,).
    Raises if the mask is not row-aligned or survivor counts are not
    uniform per block.
    """
    K, N = w.shape
    if K % m:
        raise ValueError(f"K={K} not a multiple of intra block m={m}")
    nblocks = K // m
    mb = mask.reshape(nblocks, m, N).astype(bool)
    if not np.all(mb == mb[:, :, :1]):
        raise ValueError(
            "mask is not row-aligned across columns; per-column IntraBlock "
            "has no TPU gather layout — use decompress_intrablock()")
    pattern = mb[:, :, 0]                       # (nblocks, m)
    counts = pattern.sum(axis=1)
    phi = int(counts.max())
    if phi == 0:
        raise ValueError("mask keeps nothing")
    if not np.all(counts == phi):
        raise ValueError(f"non-uniform survivors per block: {set(counts.tolist())}")
    row_idx = np.nonzero(pattern.reshape(-1))[0].astype(np.int32)   # (nblocks*phi,)
    w_comp = np.ascontiguousarray(w[row_idx, :])
    return w_comp, row_idx


def decompress_intrablock(w: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """General per-column IntraBlock fallback: masked-dense weights.

    On TPU the MXU processes the zeros anyway; the benefit of per-column
    N:M is storage/accuracy (and CIM rows), not MXU FLOPs.
    """
    return np.asarray(w) * np.asarray(mask, dtype=w.dtype)


# ---------------------------------------------------------------------------
# Dispatch wrappers
# ---------------------------------------------------------------------------

def block_sparse_matmul(x, w_comp, idx, *, impl: str = "auto", tile_b: int = 128):
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.block_sparse_matmul_ref(x, w_comp, idx)
    return block_sparse_matmul_pallas(
        x, w_comp, idx, tile_b=tile_b,
        interpret=(impl == "pallas_interpret"))


def intrablock_gather_matmul(x, w_comp, row_idx, *, impl: str = "auto",
                             tile_b: int = 128, tile_n: int = 128):
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.intrablock_gather_matmul_ref(x, w_comp, row_idx)
    return intrablock_gather_matmul_pallas(
        x, w_comp, row_idx, tile_b=tile_b, tile_n=tile_n,
        interpret=(impl == "pallas_interpret"))


def block_importance(w, bm: int, bn: int, criterion: str = "l1", *,
                     impl: str = "auto", tile_n: int = 0):
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.block_importance_ref(w, bm, bn, criterion)
    return block_importance_pallas(
        w, bm, bn, criterion, tile_n=tile_n,
        interpret=(impl == "pallas_interpret"))


def bitserial_zero_profile(q, group_rows: int, n_bits: int = 8, *,
                           impl: str = "auto", tile_v: int = 128):
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.bitserial_zero_profile_ref(q, group_rows, n_bits)
    return bitserial_zero_profile_pallas(
        q, group_rows, n_bits, tile_v=tile_v,
        interpret=(impl == "pallas_interpret"))


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, impl: str = "auto",
                    tile_q: int = 128, tile_k: int = 128):
    """Fused attention over (B, S, H, hd) tensors with GQA broadcast.

    q: (B, Sq, Hq, hd); k/v: (B, Skv, Hkv, hd) with Hq % Hkv == 0.
    """
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hq, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hq, Skv, hd)
    impl = _resolve(impl)
    if impl == "ref":
        of = _ref.flash_attention_ref(qf, kf, vf, causal=causal,
                                      window=window)
    else:
        of = flash_attention_pallas(
            qf, kf, vf, causal=causal, window=window, tile_q=tile_q,
            tile_k=tile_k, interpret=(impl == "pallas_interpret"))
    return of.reshape(B, Hq, Sq, hd).transpose(0, 2, 1, 3)


def resolve_decode_attention(impl: str, k) -> str:
    """``impl`` for :func:`decode_attention` over stacks like ``k``.
    ``"auto"`` takes the kernel on a TPU, outside a multi-device mesh
    (the kernel reads each slot whole from one device's HBM, so a
    sharded cache stays on the XLA path) and where the kernel tiles the
    stacks' head layout (``decode_attention.fits``)."""
    if impl != "auto":
        return impl
    mesh = jax.sharding.get_abstract_mesh()
    one_device = mesh.empty or mesh.size == 1
    ok = _on_tpu() and one_device and _decode_fits(k.shape, k.dtype)
    return "pallas" if ok else "ref"


def decode_attention(q, k, v, layer, lengths, window=None, *,
                     attn_cap: float = 0.0, impl: str = "auto"):
    """Single-query decode attention over the stacked serving cache.

    q: (B, Hq, hd); k/v: every layer's (L, B, Smax, Hkv, hd) stack;
    ``layer``: this layer's index; ``lengths``: (B,) valid positions per
    slot, the new row included (0 = a free slot, which reads nothing and
    returns zeros); ``window``: None, int or traced scalar.  The kernel
    fetches only each slot's live blocks; ``"ref"`` is the XLA path.
    """
    impl = resolve_decode_attention(impl, k)
    if impl == "ref":
        return _ref.decode_attention_ref(q, k, v, layer, lengths, window,
                                         attn_cap=attn_cap)
    return decode_attention_pallas(
        q, k, v, layer, lengths, window, attn_cap=attn_cap,
        interpret=(impl == "pallas_interpret"))
