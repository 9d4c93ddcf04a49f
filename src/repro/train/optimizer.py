"""AdamW with global-norm clipping, implemented directly on pytrees.

Written in-tree (no optax dependency) so the optimizer state layout is
fully under our control: ``m``/``v`` mirror the parameter tree in f32
and take the parameters' PartitionSpecs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["AdamWConfig", "adamw_init", "adamw_update", "global_norm"]


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


def adamw_init(params) -> Dict[str, Any]:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {
        "step": jnp.zeros((), jnp.int32),
        "m": zeros,
        "v": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
    }


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def _schedule(cfg: AdamWConfig, step: jnp.ndarray) -> jnp.ndarray:
    """Linear warmup → cosine decay to min_lr_ratio."""
    step_f = step.astype(jnp.float32)
    warm = jnp.minimum(step_f / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step_f - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    decay = cfg.min_lr_ratio + (1.0 - cfg.min_lr_ratio) * cos
    return cfg.lr * warm * decay


def adamw_update(
    grads, opt_state: Dict[str, Any], params, cfg: AdamWConfig,
) -> Tuple[Any, Dict[str, Any], Dict[str, jnp.ndarray]]:
    """One AdamW step.  Returns (new_params, new_state, metrics)."""
    step = opt_state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9)) \
        if cfg.clip_norm > 0 else 1.0
    lr = _schedule(cfg, step)
    b1, b2 = cfg.beta1, cfg.beta2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m_new = b1 * m + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m_new / bc1
        vhat = v_new / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        p32 = p.astype(jnp.float32)
        p_new = p32 - lr * (delta + cfg.weight_decay * p32)
        return p_new.astype(p.dtype), m_new, v_new

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(opt_state["m"])
    flat_v = jax.tree.leaves(opt_state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, {"step": step, "m": new_m, "v": new_v}, metrics
