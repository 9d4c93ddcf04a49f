"""FullBlock block-sparse matmul Pallas TPU kernel.

TPU-native adaptation of the paper's CIM weight-sparsity execution
(§III-B): FullBlock-pruned weights are stored *densely* as the gathered
list of surviving (bm × bn) blocks per output-column group, plus a block
index that routes the right input slice to each block — the analogue of
the CIM accelerator's block-index memory directing inputs to array rows.

Layout (built by :func:`repro.kernels.ops.compress_fullblock`):

* ``w_comp``: (Gn, L, bm, bn) — for each of Gn output-column groups, its
  L surviving K-blocks (L = max over groups, padded).
* ``idx``:    (Gn, L) int32 — source K-block index per slot, -1 padding.

Grid: (B/TB, Gn, L).  ``idx`` is prefetched into SMEM as a scalar
operand, and the input BlockSpec's index map reads it, so each step
DMAs exactly the (TB, bm) input slice its weight block needs.  The L
axis is the reduction: an f32 VMEM accumulator collects the partial
products and is written out at the last slot; padding slots skip the
matmul.  On TPU ``bm``/``bn`` must be multiples of 128 (the input slice
is a lane block); interpret-mode tests exercise smaller shapes too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_sparse_matmul_pallas"]


def _kernel(idx_ref, x_ref, w_ref, o_ref, acc_ref, *, n_slots):
    j, l = pl.program_id(1), pl.program_id(2)

    @pl.when(l == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(idx_ref[j * n_slots + l] >= 0)
    def _():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(l == n_slots - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def block_sparse_matmul_pallas(
    x: jnp.ndarray,        # (B, K)
    w_comp: jnp.ndarray,   # (Gn, L, bm, bn)
    idx: jnp.ndarray,      # (Gn, L) int32
    *,
    tile_b: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, K = x.shape
    Gn, L, bm, bn = w_comp.shape
    if K % bm:
        raise ValueError(f"K={K} not a multiple of block rows {bm}")
    TB = min(tile_b, B)
    pad_b = (-B) % TB
    if pad_b:
        x = jnp.pad(x, ((0, pad_b), (0, 0)))
    Bp = x.shape[0]

    def x_map(b, j, l, idx_ref):
        # a padding slot re-reads block 0; its product is skipped
        return b, jnp.maximum(idx_ref[j * L + l], 0)

    out = pl.pallas_call(
        functools.partial(_kernel, n_slots=L),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Bp // TB, Gn, L),
            in_specs=[
                pl.BlockSpec((TB, bm), x_map),
                pl.BlockSpec((None, None, bm, bn),
                             lambda b, j, l, idx_ref: (j, l, 0, 0)),
            ],
            out_specs=pl.BlockSpec((TB, bn), lambda b, j, l, idx_ref: (b, j)),
            scratch_shapes=[pltpu.VMEM((TB, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Bp, Gn * bn), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(idx.reshape(-1).astype(jnp.int32), x, w_comp)
    return out[:B]
