"""Bit-serial zero-plane profiling Pallas TPU kernel (§IV-B).

Digital CIM pre-processors detect, per bit position, whether every input
broadcast to an array's activated rows is zero (an OR-tree across the
group) and skip that bit-serial cycle.  CIMinus profiles activations to
estimate the skippable ratio; this kernel performs the bit-plane
group-OR reduction over int8 activation samples.

Grid: (V/TV,).  The wrapper hands the kernel the samples transposed,
(K, V), so a group of ``group_rows`` inputs splits the sublane axis and
the vectors run along the lanes.  Each program reduces its vector tile
to a partial count of skippable (vector × group × bit) slots and writes
it into a lane-dense (8, 128) output block; the wrapper sums partials.
On TPU ``group_rows`` must be a multiple of 8 and ``tile_v`` of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["bitserial_zero_profile_pallas"]


def _make_kernel(group_rows: int, n_bits: int):
    def _kernel(q_ref, o_ref):
        mag = jnp.abs(q_ref[...].astype(jnp.int32))      # (Kp, TV)
        Kp, TV = mag.shape
        grouped = mag.reshape(Kp // group_rows, group_rows, TV)
        count = jnp.zeros((), jnp.int32)
        for b in range(n_bits):
            plane = (grouped >> b) & 1
            group_or = plane.max(axis=1)
            count += jnp.sum(group_or == 0, dtype=jnp.int32)
        o_ref[...] = jnp.full(o_ref.shape, count, jnp.int32)

    return _kernel


@functools.partial(jax.jit,
                   static_argnames=("group_rows", "n_bits", "tile_v",
                                    "interpret"))
def bitserial_zero_profile_pallas(
    q: jnp.ndarray,          # (V, K) int8
    group_rows: int,
    n_bits: int = 8,
    *,
    tile_v: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns jnp.int32 [skippable, total] — identical contract to
    :func:`repro.kernels.ref.bitserial_zero_profile_ref`."""
    V, K = q.shape
    pad_k = (-K) % group_rows
    if pad_k:
        q = jnp.pad(q, ((0, 0), (0, pad_k)))
    TV = min(tile_v, V)
    pad_v = (-V) % TV
    if pad_v:
        # pad vectors with ones: a non-zero pad never counts as skippable,
        # so padded rows contribute zero to the count and we subtract their
        # group totals from `total` below by just not counting them.
        q = jnp.pad(q, ((0, pad_v), (0, 0)), constant_values=1)
    Vp, Kp = q.shape
    G = Kp // group_rows
    partials = pl.pallas_call(
        _make_kernel(group_rows, n_bits),
        grid=(Vp // TV,),
        in_specs=[pl.BlockSpec((Kp, TV), lambda i: (0, i))],
        out_specs=pl.BlockSpec((None, 8, 128), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Vp // TV, 8, 128), jnp.int32),
        interpret=interpret,
    )(q.T)
    skippable = partials[:, 0, 0].sum()
    # padded rows contain a 1-bit in plane 0 → bits 1..7 of an all-ones pad
    # row are zero and would inflate the count; remove their contribution.
    if pad_v:
        pad_contrib = pad_v * G * (n_bits - 1)
        skippable = skippable - jnp.int32(pad_contrib)
    total = jnp.int32(V * G * n_bits)
    return jnp.stack([skippable.astype(jnp.int32), total])
