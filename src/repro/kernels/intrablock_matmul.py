"""IntraBlock (N:M column-sparse) matmul Pallas TPU kernel.

IntraBlock(m, 1) pruning keeps φ of every m consecutive K-rows; column-
wise compression stacks the survivors into a uniform (Kc = K·φ/m, N)
matrix.  At execution each compressed row must receive the input element
of its *original* row — in CIM hardware this is the mux-based indexing
unit between the pre-processor and the array (§IV-C ③); on TPU it is an
input gather feeding a dense MXU matmul.

The gather runs once, in XLA, before the kernel: it moves B×Kc input
elements, against the Kc×N weights the matmul reads, and the TPU has no
lane gather inside a kernel.  The kernel is the dense matmul over the
compressed weights.  Grid: (B/TB, N/TN); on TPU ``tile_b`` must be a
multiple of 8 and ``tile_n`` of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["intrablock_gather_matmul_pallas"]


def _make_kernel(cast_f32: bool):
    def _kernel(xg_ref, w_ref, o_ref):
        # xg_ref: (TB, Kc) gathered input; w_ref: (Kc, TN); o_ref: (TB, TN)
        xg, w = xg_ref[...], w_ref[...]
        if cast_f32:
            # interpret-mode CPU thunks lack bf16×bf16→f32 dot support;
            # the TPU path keeps bf16 operands for native MXU accumulation
            xg, w = xg.astype(jnp.float32), w.astype(jnp.float32)
        o_ref[...] = jnp.dot(
            xg, w, preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)

    return _kernel


@functools.partial(jax.jit, static_argnames=("tile_b", "tile_n", "interpret"))
def intrablock_gather_matmul_pallas(
    x: jnp.ndarray,        # (B, K)
    w_comp: jnp.ndarray,   # (Kc, N)
    row_idx: jnp.ndarray,  # (Kc,) int32
    *,
    tile_b: int = 128,
    tile_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B = x.shape[0]
    Kc, N = w_comp.shape
    TB, TN = min(tile_b, B), min(tile_n, N)
    xg = jnp.take(x, row_idx.astype(jnp.int32), axis=1)       # (B, Kc)
    pad_b, pad_n = (-B) % TB, (-N) % TN
    if pad_b:
        xg = jnp.pad(xg, ((0, pad_b), (0, 0)))
    if pad_n:
        w_comp = jnp.pad(w_comp, ((0, 0), (0, pad_n)))
    Bp, Np = xg.shape[0], w_comp.shape[1]
    out = pl.pallas_call(
        _make_kernel(cast_f32=interpret and x.dtype == jnp.bfloat16),
        grid=(Bp // TB, Np // TN),
        in_specs=[
            pl.BlockSpec((TB, Kc), lambda b, j: (b, 0)),
            pl.BlockSpec((Kc, TN), lambda b, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((TB, TN), lambda b, j: (b, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), x.dtype),
        interpret=interpret,
    )(xg, w_comp)
    return out[:B, :N]
