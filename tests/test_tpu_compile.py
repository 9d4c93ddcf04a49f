"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

Interpret mode runs a Pallas kernel body in Python and so accepts block
layouts the TPU compiler refuses (unaligned blocks, scalar stores to
VMEM, in-kernel lane gathers).  These tests hand each kernel, and a
two-layer qwen3-4b ``decode_step``, to the TPU compiler at qwen3-4b
widths and check that a Mosaic kernel (``tpu_custom_call``) comes out.

The topology is described inside a fixture, never at import: loading
the TPU library holds a process-wide lock, and every xdist worker
imports every test module.
"""
import dataclasses
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config, list_archs
from repro.kernels import ops

K, N = 2560, 9728          # qwen3-4b d_model, d_ff
HQ, HKV, HD = 32, 8, 128   # qwen3-4b heads, kv heads, head_dim


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("skv", [2048, 8192])
def test_flash_attention_compiles(one_chip, skv):
    q = _spec(one_chip, (1, skv, HQ, HD), jnp.bfloat16)
    kv = _spec(one_chip, (1, skv, HKV, HD), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            impl="pallas"), q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", [a for a in list_archs()
                                  if get_config(a).attention != "none"])
def test_decode_attention_compiles_where_it_fits(one_chip, arch,
                                                 monkeypatch):
    """On a TPU, every config whose bf16 K/V head layout ``fits`` the
    decode kernel takes it, and the TPU compiler takes the kernel at the
    config's published heads, head dim and softcap, with a traced
    window; the rest stay on the XLA path.  qwen3-4b must fit."""
    from repro.kernels.decode_attention import fits
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_config(arch)
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    L, B, S = 2, 4, 1024
    stack = _spec(one_chip, (L, B, S, Hkv, hd), jnp.bfloat16)
    fit = fits(stack.shape, stack.dtype)
    assert fit or arch != "qwen3-4b"
    assert ops.resolve_decode_attention("auto", stack) == \
        ("pallas" if fit else "ref")
    if not fit:
        return
    compiled = _compile(
        lambda q, k, v, layer, n, w: ops.decode_attention(
            q, k, v, layer, n, w, attn_cap=cfg.attn_softcap, impl="pallas"),
        _spec(one_chip, (B, Hq, hd), jnp.bfloat16), stack, stack,
        _spec(one_chip, (), jnp.int32), _spec(one_chip, (B,), jnp.int32),
        _spec(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def _block_sparse(s):
    gn, L = N // 128, 10
    return (lambda x, w, i: ops.block_sparse_matmul(x, w, i, impl="pallas"),
            _spec(s, (256, K), jnp.bfloat16),
            _spec(s, (gn, L, 128, 128), jnp.bfloat16),
            _spec(s, (gn, L), jnp.int32))


def _intrablock(s):
    return (lambda x, w, i: ops.intrablock_gather_matmul(x, w, i,
                                                         impl="pallas"),
            _spec(s, (256, K), jnp.bfloat16),
            _spec(s, (K // 2, N), jnp.bfloat16),
            _spec(s, (K // 2,), jnp.int32))


def _block_importance(s):
    return (lambda w: ops.block_importance(w, 128, 128, impl="pallas"),
            _spec(s, (K, N), jnp.bfloat16))


def _bitserial(s):
    return (lambda q: ops.bitserial_zero_profile(q, 128, impl="pallas"),
            _spec(s, (1024, K), jnp.int8))


@pytest.mark.parametrize("build", [_block_sparse, _intrablock,
                                   _block_importance, _bitserial],
                         ids=["block_sparse_matmul", "intrablock_gather",
                              "block_importance", "bitserial_profile"])
def test_sparse_kernel_compiles(one_chip, build):
    fn, *args = build(one_chip)
    assert "tpu_custom_call" in _compile(fn, *args).as_text()


def test_qwen3_4b_decode_step_compiles(one_chip, monkeypatch):
    """The two-layer qwen3-4b decode step at the chat cell's shapes (16
    slots x 1024, bf16 cache), on the path a TPU takes, runs attention
    in the decode kernel (a ``tpu_custom_call``) and slices no layer's
    whole (16, 1024, 8, 128) K/V block out of the stacks."""
    from repro.models.transformer import decode_step, init_cache, init_params
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("qwen3-4b"), n_layers=2)
    slots, max_len = 16, 1024

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(
        lambda: init_cache(cfg, slots, max_len, dtype=jnp.bfloat16)))
    cache["pos"] = _spec(one_chip, (slots,), jnp.int32)
    tokens = _spec(one_chip, (slots,), jnp.int32)
    step = jax.jit(lambda p, t, c: decode_step(p, t, cfg, c))
    lowered = step.lower(params, tokens, cache)
    logits, new_cache = lowered.out_info
    assert logits.shape == (slots, cfg.vocab_size)
    assert new_cache["k"].shape == (2, slots, max_len, HKV, HD)
    compiled = lowered.compile()
    assert compiled.memory_analysis() is not None
    hlo = compiled.as_text()
    assert re.search(r"%decode_attention[\w.]* = \S+ custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"', hlo)
    block = f"{slots},{max_len},{HKV},{HD}]"
    assert not [line for line in hlo.splitlines()
                if "dynamic-slice(" in line and block in line.split("=")[1]
                .split("dynamic-slice(")[0]]


_INSTR = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\((.*)$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def _producers(hlo, shape):
    """(opcode, root opcode of the fused computation or None) of every
    instruction in ``hlo`` whose result is an array of ``shape``."""
    roots, found, comp = {}, [], None
    for line in hlo.splitlines():
        if line.endswith("{") and " = " not in line:
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        root, _, result, opcode, rest = m.groups()
        if root:
            roots[comp] = opcode
        if result.split("{")[0] == shape:
            calls = _CALLS.search(rest)
            found.append((opcode, calls.group(1) if calls else None))
    return [(op, roots.get(c) if c else None) for op, c in found]


def test_engine_decode_writes_cache_in_place(one_chip, monkeypatch):
    """The engine's decode program at qwen3-4b widths (16 slots x 1024,
    bf16 cache) aliases the donated K/V stacks to its output, needs no
    temporary the size of a layer's block, and writes the stacks only by
    scattering each slot's new row: no copy of a stack, and no
    dynamic-update-slice of a whole layer block."""
    from repro.models.transformer import init_cache, init_params
    from repro.serve.engine import ServeEngine
    L, slots, max_len = 2, 16, 1024
    cfg = dataclasses.replace(get_config("qwen3-4b"), n_layers=L)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # the chip's path

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(
        lambda: init_cache(cfg, slots, max_len, dtype=jnp.bfloat16)))
    cache["pos"] = _spec(one_chip, (slots,), jnp.int32)
    tokens = _spec(one_chip, (slots,), jnp.int32)
    engine = ServeEngine(cfg, params, slots=slots, max_len=max_len,
                         dtype=jnp.bfloat16)
    compiled = engine._decode.lower(params, tokens, cache).compile()
    mem = compiled.memory_analysis()
    stack = cache["k"]
    stack_bytes = stack.size * stack.dtype.itemsize
    assert mem.alias_size_in_bytes >= 2 * stack_bytes
    assert mem.temp_size_in_bytes < stack_bytes // L
    shape = f"bf16[{','.join(map(str, stack.shape))}]"
    made = _producers(compiled.as_text(), shape)
    assert ("fusion", "scatter") in made
    assert set(made) <= {("parameter", None), ("get-tuple-element", None),
                         ("scatter", None), ("fusion", "scatter")}, made
