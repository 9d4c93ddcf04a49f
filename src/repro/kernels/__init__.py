"""Pallas TPU kernels for CIMinus compute hot-spots.

Each kernel ships three artefacts:

* ``<name>.py`` — the ``pl.pallas_call`` + BlockSpec kernel (TPU target);
* ``ops.py``    — jit'd dispatch wrappers + compressed-layout builders;
* ``ref.py``    — pure-jnp oracles (semantic ground truth + CPU path).

Validated in interpret mode on CPU; see tests/test_kernels.py.
"""
from .ops import (bitserial_zero_profile, block_importance,
                  block_sparse_matmul, compress_fullblock,
                  compress_intrablock, decode_attention,
                  decompress_intrablock, flash_attention,
                  intrablock_gather_matmul)

__all__ = [
    "bitserial_zero_profile", "block_importance", "block_sparse_matmul",
    "compress_fullblock", "compress_intrablock", "decode_attention",
    "decompress_intrablock", "flash_attention", "intrablock_gather_matmul",
]
