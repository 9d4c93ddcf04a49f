"""Multi-device distributed paths (4 virtual CPU devices, subprocess):

* EP shard_map MoE ≡ the global-dispatch oracle (dropless capacity),
  gradients finite;
* sequence-parallel SWA attention ≡ the fallback path incl. gradients.

Each test runs in its own interpreter because jax locks the device
count at first init (the main pytest process runs with 1 device).
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            PYTHONPATH="src")


def _run(script: str, timeout: int = 480):
    r = subprocess.run([sys.executable, "-c", script], env=_ENV,
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


EP_MOE = """
import dataclasses, numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models.layers import _moe_block_global, moe_block
from repro.launch.mesh import make_mesh
cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b").reduced(),
                          n_experts=8, top_k=2, capacity_factor=8.0)
D, E, F = cfg.d_model, cfg.n_experts, cfg.d_ff
ks = [jax.random.PRNGKey(i) for i in range(5)]
p = {"w_router": jax.random.normal(ks[0], (D, E)) * 0.1,
     "w_up": jax.random.normal(ks[1], (E, D, F)) * 0.05,
     "w_gate": jax.random.normal(ks[2], (E, D, F)) * 0.05,
     "w_down": jax.random.normal(ks[3], (E, F, D)) * 0.05}
x = jax.random.normal(ks[4], (4, 16, D))
mesh = make_mesh((2, 2), ("data", "model"))
with jax.set_mesh(mesh):
    y_ep = jax.jit(lambda x, p: moe_block(x, p, cfg))(x, p)
    g = jax.jit(jax.grad(lambda p, x: moe_block(x, p, cfg).sum()))(p, x)
y_ref = _moe_block_global(x, p, cfg)
assert float(jnp.abs(y_ep - y_ref).max()) < 2e-4
assert all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(g))
print("EP-MOE-OK")
"""

SWA_SEQPAR = """
import dataclasses, numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models.layers import attention_block
from repro.launch.mesh import make_mesh
cfg = dataclasses.replace(get_config("hymba-1.5b"), d_model=80, n_heads=5,
                          n_kv_heads=5, head_dim=16, window=64)
D, Hq, hd = 80, 5, 16
p = {k: jax.random.normal(jax.random.PRNGKey(i), s) * 0.1
     for i, (k, s) in enumerate({"wq": (D, Hq, hd), "wk": (D, Hq, hd),
                                 "wv": (D, Hq, hd), "wo": (Hq, hd, D)}.items())}
B, S = 2, 2048
x = jax.random.normal(jax.random.PRNGKey(9), (B, S, D))
pos = jnp.broadcast_to(jnp.arange(S), (B, S))
f = lambda x, p: attention_block(x, p, cfg, positions=pos, causal=True,
                                 window=cfg.window)
y_ref, (k_ref, v_ref) = f(x, p)
g_ref = jax.grad(lambda p, x: f(x, p)[0].sum())(p, x)
mesh = make_mesh((2, 2), ("data", "model"))
with jax.set_mesh(mesh):
    y_sp, (k_sp, v_sp) = jax.jit(f)(x, p)
    g_sp = jax.jit(jax.grad(lambda p, x: f(x, p)[0].sum()))(p, x)
assert float(jnp.abs(y_sp - y_ref).max()) < 2e-5
assert float(jnp.abs(k_sp - k_ref).max()) < 2e-5
for k in g_ref:
    assert float(jnp.abs(g_sp[k] - g_ref[k]).max()) < 2e-3, k
print("SWA-SEQPAR-OK")
"""


@pytest.mark.slow
def test_ep_moe_matches_global_dispatch():
    assert "EP-MOE-OK" in _run(EP_MOE)


@pytest.mark.slow
def test_swa_seqpar_matches_fallback():
    assert "SWA-SEQPAR-OK" in _run(SWA_SEQPAR)
