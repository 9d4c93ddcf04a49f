"""Operations and bytes the algorithms need, computed from shapes.

Each function counts the work the mathematics asks for, not what an
implementation happens to do: causal attention counts the lower
triangle, and a decode step reads the cache only over each slot's
valid positions.  So a count reads the same whatever computes it, and a
share of a roofline or a peak built on it can only fall when work is
wasted.

``a`` is the ``arch`` dict of a configuration file.  A multiply-add
counts as two operations.
"""
from __future__ import annotations

from typing import Dict, Iterable


def head_dim(a: Dict) -> int:
    return a.get("head_dim") or a["d_model"] // a["n_heads"]


def attn_params(a: Dict) -> int:
    """q, k, v and o projections of one layer (norms not counted)."""
    d, hd = a["d_model"], head_dim(a)
    return d * hd * (2 * a["n_heads"] + 2 * a["n_kv_heads"])


def mlp_params(a: Dict) -> int:
    """One dense MLP."""
    return (3 if a.get("gated_mlp", True) else 2) * a["d_model"] * a["d_ff"]


def norm_params(a: Dict) -> int:
    n = 2 * a["d_model"]
    if a.get("qk_norm"):
        n += 2 * head_dim(a)
    return n


def layer_params(a: Dict) -> int:
    """Every weight of one dense attention layer."""
    return attn_params(a) + norm_params(a) + mlp_params(a)


def active_layer_params(a: Dict) -> int:
    """The weights of one layer that one token multiplies by."""
    return attn_params(a) + mlp_params(a)


def embed_params(a: Dict) -> int:
    return a["vocab_size"] * a["d_model"]


def weight_bytes(a: Dict, bytes_per: int = 2) -> int:
    """All weights a forward pass over the whole vocabulary reads once:
    every layer, the final norm and the (tied) embedding matrix."""
    n = a["n_layers"] * layer_params(a) + a["d_model"] + embed_params(a)
    if not a.get("tie_embeddings"):
        n += embed_params(a)
    return n * bytes_per


def attention_flops(a: Dict, queries: int, context: int) -> float:
    """Scores and weighted values of one layer for ``queries`` new
    positions, the last of which sees ``context`` positions (causal)."""
    first = context - queries + 1
    seen = (first + context) * queries / 2          # sum of visible keys
    return 4.0 * a["n_heads"] * head_dim(a) * seen


def decode_flops(a: Dict, contexts: Iterable[int]) -> float:
    """One decode step: one token per active slot, each seeing
    ``context`` positions, its own included; logits over the vocabulary."""
    per_token = 2.0 * (a["n_layers"] * active_layer_params(a)
                       + embed_params(a))
    total = 0.0
    for ctx in contexts:
        total += per_token + a["n_layers"] * attention_flops(a, 1, ctx)
    return total


def kv_bytes_per_position(a: Dict, bytes_per: int = 2) -> int:
    return 2 * a["n_layers"] * a["n_kv_heads"] * head_dim(a) * bytes_per


def decode_bytes(a: Dict, contexts: Iterable[int], kv_bytes: int = 2,
                 weight_bytes_per: int = 2) -> float:
    """One decode step: every weight once, and each active slot's keys
    and values over its valid positions (read) plus the new one
    (written)."""
    kv = sum(contexts) * kv_bytes_per_position(a, kv_bytes)
    return weight_bytes(a, weight_bytes_per) + kv


def prefill_flops(a: Dict, prompt: int, logits_rows: int = 1) -> float:
    """A causal prefill of ``prompt`` tokens that returns the logits of
    ``logits_rows`` positions."""
    return (2.0 * a["n_layers"] * active_layer_params(a) * prompt
            + a["n_layers"] * attention_flops(a, prompt, prompt)
            + 2.0 * embed_params(a) * logits_rows)

