"""Ragged single-query decode attention over the stacked serving cache.

The serving decode keeps every layer's K and V stacked as (L, B, Smax,
Hkv, hd).  The XLA path takes this layer's whole (B, Smax, Hkv, hd)
block out of the stack and masks what it does not need: every slot's
positions past its length, and free slots whole.  This kernel leaves
the stacks in HBM and fetches only each slot's live blocks.

Layout / loop:

* q: (B, Hq, hd) in VMEM; K/V: the whole stacks in HBM (``pl.ANY``);
  scalar-prefetched ``layer``, per-slot ``lengths`` (B,) and ``window``
  (may differ per layer: gemma2 passes a traced one).
* one program per call.  A while loop walks the (slot, block) items:
  slot b's blocks are ``[max(0, len_b − window) // blk, ceil(len_b /
  blk))``, so a slot of length 0 has none and costs no copy.  Each
  item's K and V block, (blk, Hkv, hd) and contiguous in HBM, is copied
  into a ring of three VMEM buffers, two items ahead of the one
  attended, across slot boundaries.  On a v5e at qwen3-4b's (128, 8,
  128) bf16 blocks this reads at about 730 GB/s where two buffers read
  at about 590 (one copy in flight does not hide a copy's start-up),
  and blocks of 128 beat 64 and 256 at chat lengths.
* per item the block is viewed as (blk·Hkv, hd) rows, one per (position,
  kv head).  All Hq query heads score all rows in one MXU product; a
  row counts only for the G = Hq / Hkv query heads of its kv head, at a
  position inside ``[len_b − window, len_b)``.  The softmax runs online
  in f32, with the softcap applied to the scaled scores, and the
  probabilities meet V in V's dtype: the XLA path's mathematics at its
  precision.  Scoring every head against every row costs Hkv× the MXU
  work the heads need, which at decode's single query is still below
  the copy time of the block.
* a slot with no blocks returns zeros, as the masked XLA path does.

Validated in interpret mode against the XLA path
(:func:`repro.kernels.ref.decode_attention_ref`) across lengths,
layers, GQA groups, windows, softcaps and dtypes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_size", "fits", "decode_attention_pallas"]

_NEG = -1e30  # finite -inf stand-in: keeps exp()/max() NaN-free
_NO_WINDOW = 1 << 30
_MAX_BLOCK = 128
_BUFFERS = 3


def block_size(max_len: int) -> int:
    """Positions per fetched block: 128 where ``max_len`` allows (a bf16
    (128, 8, 128) K block is 256 KiB, one contiguous copy)."""
    return math.gcd(max_len, _MAX_BLOCK)


def fits(shape, dtype) -> bool:
    """Whether the kernel serves a (L, B, Smax, Hkv, hd) stack on a TPU:
    Smax in whole blocks of 128 positions, hd in whole 128-lane rows,
    and Hkv rows that fill the sublane packing (two bf16 rows a 32-bit
    word) the way XLA tiles the stack.  Checked for a described v5e:
    bf16 Hkv 2/4/8/16 and f32 Hkv 1/2/4/8/16 at hd 128 and 256 compile;
    hd 64, bf16 Hkv 1, and Hkv 3/5/6/12 do not all."""
    *_, smax, hkv, hd = shape
    packing = 4 // jnp.dtype(dtype).itemsize
    words = hkv // packing
    return (smax % _MAX_BLOCK == 0 and hd % 128 == 0
            and hkv % packing == 0
            and (words in (1, 2, 4, 8) or words % 8 == 0))


def _kernel(layer_ref, len_ref, win_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sem, nxt_ref, bias_ref, m_ref, l_ref, acc_ref, *,
            blk, scale, attn_cap):
    B, Hq, hd = q_ref.shape
    nbuf, _, Hkv, _ = k_buf.shape
    rows = blk * Hkv
    layer, window = layer_ref[0], win_ref[0]

    def live_blocks(b):
        n = len_ref[jnp.minimum(b, B - 1)]
        return jnp.maximum(n - window, 0) // blk, (n + blk - 1) // blk

    # nxt[b]: the first slot from b on with a block to read (B if none)
    nxt_ref[B] = B

    def fill_nxt(i, _):
        b = B - 1 - i
        lo, hi = live_blocks(b)
        nxt_ref[b] = jnp.where(hi > lo, b, nxt_ref[b + 1])
        return 0

    jax.lax.fori_loop(0, B, fill_nxt, 0)

    def advance(b, j):
        """The (slot, block) item after (b, j); slot B once none is left."""
        last = j + 1 >= live_blocks(b)[1]
        nb = jnp.where(last, nxt_ref[jnp.minimum(b + 1, B)], b)
        return nb, jnp.where(last, live_blocks(nb)[0], j + 1)

    def copies(b, j, buf):
        start = pl.multiple_of(j * blk, blk)
        return (pltpu.make_async_copy(k_hbm.at[layer, b, pl.ds(start, blk)],
                                      k_buf.at[buf], sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, b, pl.ds(start, blk)],
                                      v_buf.at[buf], sem.at[1, buf]))

    def fetch(b, j, buf):
        @pl.when(b < B)
        def _():
            for c in copies(b, j, buf):
                c.start()

    def reset():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def attend(b, j, buf):
        q = q_ref[b]                                          # (Hq, hd)
        k = k_buf.at[buf].reshape(rows, hd)[...]
        v = v_buf.at[buf].reshape(rows, hd)[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if attn_cap > 0:
            s = attn_cap * jnp.tanh(s / attn_cap)
        # rows of other kv heads are out; so are positions outside the
        # slot's [len - window, len), which only its end blocks hold
        col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        pos = j * blk + col // Hkv
        n = len_ref[b]
        s = jnp.where((pos < n) & (pos >= n - window), s + bias_ref[...],
                      _NEG)
        m_prev = m_ref[...][:, :1]                            # (Hq, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)
        corr = jnp.exp(m_prev - m_cur)
        l_cur = l_ref[...][:, :1] * corr + p.sum(axis=1, keepdims=True)
        pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    first = nxt_ref[0]

    @pl.when(first < B)
    def _():
        # row r of the block is kv head r % Hkv, which serves query heads
        # [h * G, (h + 1) * G)
        head = jax.lax.broadcasted_iota(jnp.int32, bias_ref.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, bias_ref.shape, 1)
        bias_ref[...] = jnp.where(col % Hkv == head // (Hq // Hkv), 0.0,
                                  _NEG)
        reset()
        # keep nbuf - 1 items in flight ahead of the one attended
        ahead = (first, live_blocks(first)[0])
        for buf in range(nbuf - 1):
            fetch(*ahead, buf)
            ahead = advance(*ahead)

        def item(carry):
            b, j, fb, fj, buf = carry
            fetch(fb, fj, (buf + nbuf - 1) % nbuf)
            for c in copies(b, j, buf):
                c.wait()
            attend(b, j, buf)
            nb, nj = advance(b, j)

            @pl.when(nb != b)
            def _():
                l = jnp.maximum(l_ref[...][:, :1], 1e-20)
                o_ref[b] = (acc_ref[...] / l).astype(o_ref.dtype)
                reset()

            return (nb, nj, *advance(fb, fj), (buf + 1) % nbuf)

        jax.lax.while_loop(lambda c: c[0] < B, item,
                           (first, live_blocks(first)[0], *ahead, 0))


@functools.partial(jax.jit, static_argnames=("attn_cap", "interpret"))
def decode_attention_pallas(
    q: jnp.ndarray,        # (B, Hq, hd)
    k: jnp.ndarray,        # (L, B, Smax, Hkv, hd)
    v: jnp.ndarray,        # (L, B, Smax, Hkv, hd)
    layer: jnp.ndarray,    # () int: this layer's index into the stacks
    lengths: jnp.ndarray,  # (B,) int: valid positions per slot, 0 = free
    window=None,           # None, int or traced scalar
    *,
    attn_cap: float = 0.0,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, hd = q.shape
    _, _, Smax, Hkv, _ = k.shape
    blk = block_size(Smax)
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} must be a multiple of Hkv={Hkv}")
    dtype = q.dtype
    q = q.astype(jnp.promote_types(q.dtype, k.dtype))
    scalars = (jnp.reshape(layer, (1,)).astype(jnp.int32),
               jnp.asarray(lengths, jnp.int32),
               jnp.reshape(_NO_WINDOW if window is None else window,
                           (1,)).astype(jnp.int32))
    whole = pl.BlockSpec((B, Hq, hd), lambda i, *_: (0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, blk=blk, scale=1.0 / math.sqrt(hd),
                          attn_cap=attn_cap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(1,),
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((_BUFFERS, blk, Hkv, hd), k.dtype),
                pltpu.VMEM((_BUFFERS, blk, Hkv, hd), v.dtype),
                pltpu.SemaphoreType.DMA((2, _BUFFERS)),
                pltpu.SMEM((B + 1,), jnp.int32),        # next live slot
                pltpu.VMEM((Hq, blk * Hkv), jnp.float32),  # head mask
                pltpu.VMEM((Hq, 128), jnp.float32),     # running max
                pltpu.VMEM((Hq, 128), jnp.float32),     # running sum
                pltpu.VMEM((Hq, hd), jnp.float32),      # running output
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hq, hd), dtype),
        interpret=interpret,
        name="decode_attention",
    )(*scalars, q, k, v)
    return out
