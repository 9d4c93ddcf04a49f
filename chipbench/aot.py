#!/usr/bin/env python3
"""Compile the chat cell's decode program at its real size for a
described TPU v5e, without a chip, and print what ``memory_analysis()``
says.

    JAX_PLATFORMS=cpu python3 chipbench/aot.py

qwen3-4b ``decode_step`` at 16 slots x 1024 (the chat cell) and
4 x 4096, bfloat16 cache.  A compile that passes is not a chip run: it
gives bytes, never a time.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _mem(tag, compiled):
    m = compiled.memory_analysis()
    gib = 1 << 30
    print(f"{tag}: arguments {m.argument_size_in_bytes / gib:.3f} GiB, "
          f"outputs {m.output_size_in_bytes / gib:.3f} GiB, "
          f"temporaries {m.temp_size_in_bytes / gib:.3f} GiB, "
          f"aliased {m.alias_size_in_bytes / gib:.3f} GiB", flush=True)


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import bench
    from repro.models.transformer import decode_step, init_cache, init_params

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    a = bench.arch_config(bench.load_json(bench.HERE / "configs"
                                          / "qwen3-4b.json"))
    params = on(jax.eval_shape(lambda k: init_params(a, k),
                               jax.random.PRNGKey(0)))
    for slots, max_len in ((16, 1024), (4, 4096)):
        cache = on(jax.eval_shape(
            lambda: init_cache(a, slots, max_len, dtype=jnp.bfloat16)))
        cache["pos"] = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
        tok = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
        c = jax.jit(lambda p, t, c: decode_step(p, t, a, c)).lower(
            params, tok, cache).compile()
        _mem(f"qwen3-4b decode_step {slots}x{max_len}", c)
    return 0


if __name__ == "__main__":
    sys.exit(main())
