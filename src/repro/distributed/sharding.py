"""Sharding rules for the production mesh.

Mesh axes: single-pod ``("data", "model")`` = (16, 16); multi-pod
``("pod", "data", "model")`` = (2, 16, 16).  Batch shards over
("pod","data"); weights tensor-parallel over "model"
(column-parallel qkv/up, row-parallel o/down ⇒ one all-reduce per pair);
embeddings vocab-sharded; MoE experts expert-parallel on "model";
optimizer state additionally ZeRO-1 sharded over "data".

Everything here degrades gracefully off-mesh: ``maybe_shard`` is a no-op
when no mesh is active and silently drops axes the active mesh lacks, so
the same model code runs on 1 CPU device (smoke tests) and on the
512-chip dry-run mesh.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["maybe_shard", "batch_axes", "spec_for_param", "tree_specs",
           "tree_shardings", "batch_spec", "cache_specs", "logits_spec",
           "filter_spec", "ShardOpts", "get_options", "set_options",
           "options"]


# ---------------------------------------------------------------------------
# Tunable sharding strategy (the §Perf hillclimb knobs)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardOpts:
    """Global sharding strategy knobs.

    fsdp            — additionally shard weights over the "data" axis on a
                      second (divisible) dimension; layers gather them
                      per-use (FSDP/ZeRO-3 style).  Optimizer m/v always
                      use the fsdp=True specs when ``zero1`` (ZeRO-1).
    attn_kv_fallback— what to do when head counts don't divide the model
                      axis: "replicate" (no collectives in the score
                      einsums) or "head_dim" (legacy; shards the score
                      CONTRACTION dim ⇒ pathological all-reduces).
    ep_shardmap     — dispatch MoE via shard_map expert parallelism
                      (local per-shard routing + all_to_all) instead of
                      the global-scatter path that SPMD cannot data-
                      parallelise.
    """
    fsdp: bool = False
    # ZeRO-1 only pays off when params share the fsdp layout: GSPMD
    # reshards mismatched (model)↔(data,model) layouts via full
    # replication (§Perf llama it1 lesson) — so it defaults off and is
    # enabled together with fsdp.
    zero1: bool = False
    attn_kv_fallback: str = "replicate"
    ep_shardmap: bool = True


_OPTS = ShardOpts()


def get_options() -> ShardOpts:
    return _OPTS


def set_options(**kw) -> ShardOpts:
    global _OPTS
    _OPTS = dataclasses.replace(_OPTS, **kw)
    return _OPTS


@contextlib.contextmanager
def options(**kw):
    global _OPTS
    prev = _OPTS
    _OPTS = dataclasses.replace(_OPTS, **kw)
    try:
        yield _OPTS
    finally:
        _OPTS = prev


def _mesh_axis_names() -> Tuple[str, ...]:
    mesh = jax.sharding.get_abstract_mesh()
    return tuple(mesh.axis_names) if not mesh.empty else ()


def filter_spec(spec: P) -> Optional[P]:
    """Drop axes absent from the active mesh; None when no mesh."""
    names = _mesh_axis_names()
    if not names:
        return None
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in names else None)
    return P(*out)


def maybe_shard(x: jnp.ndarray, spec: P) -> jnp.ndarray:
    """with_sharding_constraint when a mesh is active; identity otherwise."""
    f = filter_spec(spec)
    if f is None:
        return x
    return jax.lax.with_sharding_constraint(x, f)


def batch_axes() -> Any:
    """The mesh axes a global batch dimension shards over."""
    names = _mesh_axis_names()
    axes = tuple(a for a in ("pod", "data") if a in names)
    return axes if axes else None


# ---------------------------------------------------------------------------
# Spec assignment: per-leaf, driven by (trailing key name, leaf shape).
# Divisibility-aware: an axis only shards if its extent divides the mesh
# axis size (e.g. hymba's 25 q-heads fall back to head_dim sharding; odd
# vocabs fall back to d_model sharding).
# ---------------------------------------------------------------------------

_MODEL = 16  # production "model" axis size


def _b(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)


def _div(n: int) -> bool:
    return n % _MODEL == 0


_DATA = 16   # production "data" axis size (per pod)


def _fsdp_augment(spec_entries, shape) -> P:
    """Add "data" sharding on the largest still-unsharded divisible axis
    (FSDP / ZeRO second-axis sharding)."""
    entries = list(spec_entries)
    best, best_ax = 0, -1
    for ax, (e, n) in enumerate(zip(entries, shape)):
        if ax == 0 and len(shape) >= 3:
            continue   # never shard the layer-scan axis
        if e is None and n % _DATA == 0 and n > best:
            best, best_ax = n, ax
    if best_ax >= 0:
        entries[best_ax] = "data"
    return P(*entries)


def spec_for_param(key: str, shape: Tuple[int, ...],
                   fsdp: Optional[bool] = None) -> P:
    nd = len(shape)
    fsdp = _OPTS.fsdp if fsdp is None else fsdp

    def out(*entries):
        if fsdp:
            return _fsdp_augment(entries, shape)
        return P(*entries)

    if key == "embed":
        if _div(shape[0]):
            return out("model", None)
        return out(None, "model")
    if key == "lm_head":
        if _div(shape[1]):
            return out(None, "model")
        return out("model", None)
    if key in ("wq", "wo") and nd == 4:
        # (L, D, Hq, hd) / (L, Hq, hd, D): shard heads when divisible.
        # With the "replicate" fallback, NEVER shard q's head_dim — it is
        # the contraction dim of the score einsum, and a double-sharded
        # contraction (q AND k on hd) forces score-matrix all-reduces.
        h_ax = 2 if key == "wq" else 1
        spec = [None] * nd
        if _div(shape[h_ax]):
            spec[h_ax] = "model"
        elif _OPTS.attn_kv_fallback == "head_dim":
            spec[3 if key == "wq" else 2] = "model"
        return out(*spec)
    if key in ("wk", "wv") and nd == 4:
        # (L, D, Hkv, hd): shard kv heads when divisible.  Otherwise hd-
        # sharding is safe ONLY when q is head-sharded (XLA then inserts a
        # cheap k/v all-gather while keeping the 16× projection sharding);
        # when q-heads are ALSO non-divisible (hymba 25H/5kv) both sides of
        # the score contraction would be hd-sharded ⇒ score all-reduces —
        # replicate instead.  Whether q-heads divide is tree context,
        # provided by tree_specs/tree_shardings via _QHEADS_DIVISIBLE.
        spec = [None] * nd
        if _div(shape[2]):
            spec[2] = "model"
        elif _OPTS.attn_kv_fallback == "head_dim":   # legacy pathological
            spec[3] = "model"
        # else: replicate.  Measured (llama3 L=1/2 A/B): replicated k/v
        # projections cost LESS than hd-sharded ones once SPMD's
        # "involuntary full rematerialization" resharding copies are
        # counted (6.51e12 vs 7.47e12 flops/layer, bytes equal).
        return out(*spec)
    if key in ("w_gate", "w_up") and nd == 4:      # (L, E, D, F) experts
        return out(None, "model", None, None)
    if key == "w_down" and nd == 4:                # (L, E, F, D)
        return out(None, "model", None, None)
    if key in ("w_gate", "w_up") and nd == 3:      # (L, D, F)
        return out(None, None, "model")
    if key == "w_down" and nd == 3:                # (L, F, D)
        return out(None, "model", None)
    if key == "w_in" and nd == 3:                  # (L, D, e)
        return out(None, None, "model") if _div(shape[2]) else out(*([None] * nd))
    if key == "w_out" and nd == 3:                 # (L, din, D)
        return out(None, "model", None) if _div(shape[1]) else out(*([None] * nd))
    if key == "conv_w":                            # (L, 4, din)
        return out(None, None, "model") if _div(shape[2]) else out(*([None] * nd))
    if key == "w_router":                          # (L, D, E)
        return out(None, None, "model") if _div(shape[2]) else out(None, None, None)
    return P(*([None] * nd))                       # norms, biases, dynamics


def _leaf_key(path) -> str:
    for p in reversed(path):
        if hasattr(p, "key"):
            return str(p.key)
    return ""




def tree_specs(template, fsdp: Optional[bool] = None) -> Any:
    """PartitionSpec tree matching an arbitrary params/opt-state tree."""
    def assign(path, leaf):
        return spec_for_param(_leaf_key(path), tuple(leaf.shape), fsdp=fsdp)

    return jax.tree_util.tree_map_with_path(assign, template)


def tree_shardings(mesh, template, fsdp: Optional[bool] = None) -> Any:
    """NamedSharding tree for ``jax.jit`` in_shardings."""
    from jax.sharding import NamedSharding

    def assign(path, leaf):
        spec = spec_for_param(_leaf_key(path), tuple(leaf.shape), fsdp=fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(assign, template)


def batch_spec(*, multi_pod: bool = False) -> P:
    return P(_b(multi_pod), None)


def logits_spec(*, multi_pod: bool = False) -> P:
    return P(_b(multi_pod), None, "model")


def cache_specs(cfg, cell, *, multi_pod: bool = False) -> Dict[str, Any]:
    """KV/SSM cache shardings for serving.

    decode_32k (large batch): batch over ("pod","data"), kv-heads over
    "model" when divisible else sequence over "model".
    long_500k (batch=1): sequence over every mesh axis (sequence
    parallelism); SSM state replicated (it is small and seq-free).
    """
    b = _b(multi_pod)
    data_size = 16 * (2 if multi_pod else 1)
    batched = cell.global_batch >= data_size
    if batched:
        if cfg.n_kv_heads % _MODEL == 0:
            kv = P(None, b, None, "model", None)
        else:
            kv = P(None, b, "model", None, None)
    else:
        kv = P(None, None, b + ("model",), None, None)
    specs: Dict[str, Any] = {"pos": P()}
    if cfg.attention != "none":
        specs["k"] = specs["v"] = kv
    if cfg.ssm_state > 0:
        # state (L, B, H, Pd, N), conv (L, B, 3, din)
        if batched:
            nspec = "model" if _div(cfg.ssm_state) else None
            specs["ssm"] = P(None, b, None, None, nspec)
            din = cfg.ssm_inner()
            specs["conv"] = P(None, b, None, "model" if _div(din) else None)
        else:
            specs["ssm"] = P(None, None, None, None, None)
            specs["conv"] = P(None, None, None, None)
    if cfg.enc_dec:
        hspec = "model" if _div(cfg.n_kv_heads) else None
        cb = b if batched else None
        specs["cross_k"] = P(None, cb, None, hspec, None)
        specs["cross_v"] = P(None, cb, None, hspec, None)
    return specs
