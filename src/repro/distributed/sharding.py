"""Sharding rules: where every leaf lives, as a function of (leaf name,
shape, mesh).

Batch shards over whichever of ("pod", "data") the mesh has; weights are
tensor-parallel over "model" (column-parallel qkv/up, row-parallel
o/down ⇒ one all-reduce per pair); embeddings vocab-sharded; MoE experts
expert-parallel on "model".  An axis only shards a dimension its size
divides (:func:`divides`), and ``models.layers`` asks the same helper
when it picks the sequence-parallel attention and expert-parallel MoE
paths, so placement and the model step agree on every mesh.

Everything here degrades gracefully off-mesh: ``maybe_shard`` is a no-op
when no mesh is active and silently drops axes the active mesh lacks, so
the same model code runs on 1 CPU device (smoke tests) and on the
512-chip dry-run mesh.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["maybe_shard", "batch_axes", "divides", "spec_for_param",
           "tree_specs", "tree_shardings", "batch_spec", "cache_specs",
           "logits_spec", "filter_spec"]


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


def batch_axes(mesh=None) -> Tuple[str, ...]:
    """The axes of ``mesh`` (default: the active mesh) a global batch
    dimension shards over: whichever of ("pod", "data") it has.  As a
    ``PartitionSpec`` entry, ``()`` means replicated."""
    names = _mesh_axis_names() if mesh is None else tuple(mesh.axis_names)
    return tuple(a for a in ("pod", "data") if a in names)


# ---------------------------------------------------------------------------
# Spec assignment: per-leaf, driven by (trailing key name, leaf shape, mesh).
# ``mesh`` is a ``Mesh`` or an ``AbstractMesh``; only its axis names and
# sizes are read.  A dimension shards over an axis only if the axis size
# divides it (hymba's 25 q-heads replicate; odd vocabs fall back to d_model
# sharding).
# ---------------------------------------------------------------------------

def divides(n: int, mesh, axes: Union[str, Sequence[str]] = "model") -> bool:
    """Whether extent ``n`` splits evenly over ``axes`` of ``mesh`` (False
    when the mesh lacks one of them)."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    if not all(a in mesh.axis_names for a in axes):
        return False
    return n % math.prod(mesh.shape[a] for a in axes) == 0


def spec_for_param(key: str, shape: Tuple[int, ...], mesh) -> P:
    nd = len(shape)

    def div(n):
        return divides(n, mesh)

    if key == "embed":
        return P("model", None) if div(shape[0]) else P(None, "model")
    if key == "lm_head":
        return P(None, "model") if div(shape[1]) else P("model", None)
    if key in ("wq", "wo", "wk", "wv") and nd == 4:
        # (L, D, H, hd) / (L, H, hd, D): shard heads when divisible, else
        # replicate.  Never shard head_dim: it is the contraction dim of
        # the score einsum, so sharding it forces score all-reduces.
        # Measured (llama3 L=1/2 A/B): replicated k/v projections cost
        # LESS than hd-sharded ones once SPMD's resharding copies are
        # counted (6.51e12 vs 7.47e12 flops/layer, bytes equal).
        h_ax = 1 if key == "wo" else 2
        spec = [None] * nd
        if div(shape[h_ax]):
            spec[h_ax] = "model"
        return P(*spec)
    if key in ("w_gate", "w_up", "w_down") and nd == 4:   # (L, E, ·, ·)
        return P(None, "model", None, None)
    if key in ("w_gate", "w_up") and nd == 3:      # (L, D, F)
        return P(None, None, "model")
    if key == "w_down" and nd == 3:                # (L, F, D)
        return P(None, "model", None)
    if key == "w_in" and nd == 3:                  # (L, D, e)
        return P(None, None, "model") if div(shape[2]) else P(None, None, None)
    if key == "w_out" and nd == 3:                 # (L, din, D)
        return P(None, "model", None) if div(shape[1]) else P(None, None, None)
    if key in ("conv_w", "w_router"):              # (L, 4, din) / (L, D, E)
        return P(None, None, "model") if div(shape[2]) else P(None, None, None)
    return P(*([None] * nd))                       # norms, biases, dynamics


def _leaf_key(path) -> str:
    for p in reversed(path):
        if hasattr(p, "key"):
            return str(p.key)
    return ""


def tree_specs(template, mesh) -> Any:
    """PartitionSpec tree matching an arbitrary params/opt-state tree."""
    def assign(path, leaf):
        return spec_for_param(_leaf_key(path), tuple(leaf.shape), mesh)

    return jax.tree_util.tree_map_with_path(assign, template)


def tree_shardings(mesh, template) -> Any:
    """NamedSharding tree for ``jax.jit`` in_shardings."""
    from jax.sharding import NamedSharding
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        tree_specs(template, mesh),
                        is_leaf=lambda x: isinstance(x, P))


def batch_spec(mesh) -> P:
    return P(batch_axes(mesh), None)


def logits_spec(mesh) -> P:
    return P(batch_axes(mesh), None, "model")


def cache_specs(cfg, cell, mesh) -> Dict[str, Any]:
    """KV/SSM cache shardings for serving.

    A batch the batch axes divide (decode_32k) shards over them, with
    kv-heads over "model" when divisible else sequence over "model".
    Otherwise (long_500k, batch=1) the sequence shards over every mesh
    axis (sequence parallelism) and SSM state is replicated (it is small
    and seq-free).
    """
    b = batch_axes(mesh)
    batched = divides(cell.global_batch, mesh, b)
    if batched:
        if divides(cfg.n_kv_heads, mesh):
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
            nspec = "model" if divides(cfg.ssm_state, mesh) else None
            specs["ssm"] = P(None, b, None, None, nspec)
            din = cfg.ssm_inner()
            specs["conv"] = P(None, b, None,
                              "model" if divides(din, mesh) else None)
        else:
            specs["ssm"] = P(None, None, None, None, None)
            specs["conv"] = P(None, None, None, None)
    if cfg.enc_dec:
        hspec = "model" if divides(cfg.n_kv_heads, mesh) else None
        cb = b if batched else None
        specs["cross_k"] = P(None, cb, None, hspec, None)
        specs["cross_v"] = P(None, cb, None, hspec, None)
    return specs
