"""Flash attention Pallas TPU kernel with causal/window tile skipping.

The execution-plane hot-spot: §Perf showed score-tile materialisation
dominating every attention-bearing cell's memory roofline term.  The
fused kernel keeps score tiles in VMEM (they never reach HBM) and skips
kv tiles that are fully masked — causal-triangular and sliding-window
skipping, i.e. the paper's FullBlock block-skip idea applied to the
attention score matrix.

Layout / grid:

* q: (BH, Sq, hd), k/v: (BH, Skv, hd) — GQA group broadcast happens in
  the ops.py wrapper.
* grid = (BH, Sq/TQ): each program owns one query tile and runs the
  online-softmax loop over its *live* kv tiles only:
  ``lo = (q_lo − window + 1) // TK`` (window) .. ``hi = q_hi // TK``
  (causal) — a dynamic fori_loop range from the program id.
* BlockSpec keeps the q tile + the running (m, l, acc) in VMEM; kv rows
  stream tile-by-tile via ``pl.ds`` ref slices.  TQ/TK default to the
  MXU-aligned 128; hd is the lane dimension.  The BH grid dimension is
  squeezed out of every block (``None`` block dims) so refs are plain
  2-D (rows, hd) tiles.
* The whole K/V sequence of one BH row is one block, so VMEM bounds the
  context: on a v5e chip at hd=128 in bf16 it compiles at Skv=8192 and
  runs out of VMEM from Skv=16384.

Validated in interpret mode against the pure-jnp oracle
(:func:`repro.kernels.ref.flash_attention_ref`) across shapes, dtypes,
windows and masks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention_pallas"]

_NEG = -1e30  # finite -inf stand-in: keeps exp()/max() NaN-free in bf16


def _kernel(q_ref, k_ref, v_ref, o_ref, *, causal, window, tile_k,
            seq_kv, scale):
    TQ, hd = q_ref.shape
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale            # (TQ, hd)
    q_lo = qi * TQ
    q_idx = q_lo + jax.lax.iota(jnp.int32, TQ)

    n_tiles = seq_kv // tile_k
    if causal:
        hi = jnp.minimum((q_lo + TQ - 1) // tile_k + 1, n_tiles)
    else:
        hi = jnp.int32(n_tiles)
    if window is not None:
        lo = jnp.maximum((q_lo - window + 1) // tile_k, 0)
    else:
        lo = jnp.int32(0)

    def body(ki, carry):
        m_prev, l_prev, acc_prev = carry
        start = ki * tile_k
        kt = k_ref[pl.ds(start, tile_k), :]
        vt = v_ref[pl.ds(start, tile_k), :]
        k_idx = start + jax.lax.iota(jnp.int32, tile_k)
        s = jnp.dot(q, kt.astype(jnp.float32).T,
                    preferred_element_type=jnp.float32)   # (TQ, TK)
        ok = jnp.ones((TQ, tile_k), bool)
        if causal:
            ok &= k_idx[None, :] <= q_idx[:, None]
        if window is not None:
            ok &= k_idx[None, :] > q_idx[:, None] - window
        s = jnp.where(ok, s, _NEG)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        corr = jnp.exp(m_prev - m_cur)
        l_cur = l_prev * corr + p.sum(axis=-1)
        pv = jnp.dot(p.astype(vt.dtype), vt,
                     preferred_element_type=jnp.float32)
        acc_cur = acc_prev * corr[:, None] + pv
        return m_cur, l_cur, acc_cur

    m0 = jnp.full((TQ,), _NEG, jnp.float32)
    l0 = jnp.zeros((TQ,), jnp.float32)
    a0 = jnp.zeros((TQ, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "tile_q", "tile_k", "interpret"))
def flash_attention_pallas(
    q: jnp.ndarray,        # (BH, Sq, hd)
    k: jnp.ndarray,        # (BH, Skv, hd)
    v: jnp.ndarray,        # (BH, Skv, hd)
    *,
    causal: bool = True,
    window: int | None = None,
    tile_q: int = 128,
    tile_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    if Sq % tile_q or Skv % tile_k:
        raise ValueError(f"Sq={Sq}/Skv={Skv} must tile by {tile_q}/{tile_k}")
    scale = 1.0 / math.sqrt(hd)
    grid = (BH, Sq // tile_q)
    return pl.pallas_call(
        functools.partial(_kernel, causal=causal, window=window,
                          tile_k=tile_k, seq_kv=Skv, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, tile_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Skv, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Skv, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, tile_q, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
