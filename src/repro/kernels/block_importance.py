"""Blockwise importance reduction Pallas TPU kernel (pruning Eq. 1).

Computes ``L_FB[i, j] = Σ ρ(W[i·bm:(i+1)·bm, j·bn:(j+1)·bn])`` for the
FullBlock pruning workflow, tiled so one program owns one block-row
strip: grid = (M/bm,), block = (bm, N) in VMEM, output row (1, N/bn).

For very wide matrices the strip splits along N as well (tile_n), with
the partial block sums remaining exact because bn divides tile_n.

The output is laid out (M/bm, 1, N/bn) so that the last two dimensions
of each output block, (1, tile_n/bn), satisfy the TPU block rule: each
is either the array's own extent or (with tile_n < N) a multiple of 128.
On TPU ``bn`` must be a multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["block_importance_pallas"]


def _make_kernel(bm: int, bn: int, criterion: str):
    def _kernel(w_ref, o_ref):
        w = w_ref[...].astype(jnp.float32)
        rho = jnp.abs(w) if criterion == "l1" else jnp.square(w)
        TN = w.shape[1]
        o_ref[...] = rho.reshape(bm, TN // bn, bn).sum(axis=(0, 2))[None, :]

    return _kernel


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "criterion", "tile_n",
                                    "interpret"))
def block_importance_pallas(
    w: jnp.ndarray,
    bm: int,
    bn: int,
    criterion: str = "l1",
    *,
    tile_n: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    M, N = w.shape
    if M % bm or N % bn:
        raise ValueError(f"matrix {w.shape} not divisible by block ({bm},{bn})")
    TN = tile_n or N
    if TN % bn or N % TN:
        raise ValueError(f"tile_n={TN} must tile N={N} in whole blocks of {bn}")
    out = pl.pallas_call(
        _make_kernel(bm, bn, criterion),
        grid=(M // bm, N // TN),
        in_specs=[pl.BlockSpec((bm, TN), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((None, 1, TN // bn), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((M // bm, 1, N // bn), jnp.float32),
        interpret=interpret,
    )(w)
    return out.reshape(M // bm, N // bn)
