"""Launch-layer units that don't need the 512-device dry-run process:
collective-byte HLO parsing, input spec shapes, mesh construction on the
local device, roofline math."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import cells_for, get_config, list_archs
from repro.configs.base import SHAPE_CELLS


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[256]{0} all-reduce(%y), to_apply=%sum
  %rs = f32[4,4]{1,0} reduce-scatter(%z), dimensions={0}
  %a2a = (f32[16]{0}, f32[16]{0}) all-to-all(%p, %q)
  %cp = u32[10]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %other = f32[999]{0} add(%a, %b)
  %ags = bf16[64]{0} all-gather-start(%v)
  %agd = bf16[64]{0} all-gather-done(%ags)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 8 * 128 * 2 + 64 * 2   # ag + ag-start
    assert out["all-reduce"] == 256 * 4
    assert out["reduce-scatter"] == 16 * 4
    assert out["all-to-all"] == 32 * 4
    assert out["collective-permute"] == 10 * 4
    assert out["count"] == 6


def test_input_specs_shapes():
    from repro.launch.dryrun import input_specs
    cfg = get_config("llama3-8b")
    cell = SHAPE_CELLS["train_4k"]
    specs = input_specs(cfg, cell)
    assert specs["batch"]["tokens"].shape == (256, 4096)
    cell_d = SHAPE_CELLS["decode_32k"]
    sd = input_specs(cfg, cell_d)
    assert sd["tokens"].shape == (128,)
    assert sd["cache"]["k"].shape == (32, 128, 32768, 8, 128)

    wcfg = get_config("whisper-medium")
    sw = input_specs(wcfg, SHAPE_CELLS["prefill_32k"])
    assert sw["enc_embed"].shape == (32, 1500, 1024)

    pcfg = get_config("paligemma-3b")
    sp = input_specs(pcfg, SHAPE_CELLS["train_4k"])
    assert sp["batch"]["prefix_embed"].shape == (256, 256, 2048)


def _abstract_mesh(shape, axes):
    from jax.sharding import AbstractMesh, AxisType
    return AbstractMesh(tuple(shape), tuple(axes),
                        axis_types=(AxisType.Auto,) * len(axes))


_PRODUCTION_MESHES = {"single": ((16, 16), ("data", "model")),
                      "multi": ((2, 16, 16), ("pod", "data", "model"))}


def test_spec_for_param_divisibility_fallbacks():
    from repro.distributed.sharding import spec_for_param
    from jax.sharding import PartitionSpec as P
    mesh = _abstract_mesh(*_PRODUCTION_MESHES["single"])
    # hymba: 25 q heads don't divide 16 → REPLICATE (never shard the
    # score-contraction head_dim — §Perf it1: hd-sharding on both sides
    # of the contraction forces score-matrix all-reduces)
    assert spec_for_param("wq", (32, 1600, 25, 64), mesh) == \
        P(None, None, None, None)
    assert spec_for_param("wq", (32, 4096, 32, 128), mesh) == \
        P(None, None, "model", None)
    # odd vocab → d_model sharding
    assert spec_for_param("embed", (51865, 1024), mesh) == P(None, "model")
    assert spec_for_param("embed", (128256, 4096), mesh) == P("model", None)
    # MoE experts expert-sharded
    assert spec_for_param("w_up", (40, 16, 6144, 10752), mesh) == \
        P(None, "model", None, None)


def _spec_json(spec):
    return [e if e is None or isinstance(e, str) else list(e) for e in spec]


def _production_record(cfg, mesh):
    """Every spec the dry-run places on ``mesh``, in the fixture's form."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import (batch_spec, cache_specs,
                                            logits_spec, tree_specs)
    from repro.launch.dryrun import param_struct
    flat = jax.tree_util.tree_flatten_with_path(
        tree_specs(param_struct(cfg), mesh),
        is_leaf=lambda x: isinstance(x, P))[0]
    return {
        "params": {jax.tree_util.keystr(p): _spec_json(s) for p, s in flat},
        "cache": {name: {k: _spec_json(v)
                         for k, v in cache_specs(cfg, cell, mesh).items()}
                  for name, cell in cells_for(cfg).items()
                  if cell.kind == "decode"},
        "batch": _spec_json(batch_spec(mesh)),
        "logits": _spec_json(logits_spec(mesh)),
    }


@pytest.fixture(scope="module")
def production_specs():
    import json
    from pathlib import Path
    path = (Path(__file__).parent / "fixtures" / "sharding"
            / "production_specs.json")
    return json.loads(path.read_text())


@pytest.mark.parametrize("mesh_kind", sorted(_PRODUCTION_MESHES))
@pytest.mark.parametrize("arch", sorted(list_archs()))
def test_production_specs_unchanged(production_specs, arch, mesh_kind):
    """On the dry-run's two production meshes every spec is the one the
    fixture recorded when the rule still assumed a 16×16 mesh."""
    mesh = _abstract_mesh(*_PRODUCTION_MESHES[mesh_kind])
    assert _production_record(get_config(arch), mesh) == \
        production_specs[arch][mesh_kind]


def test_spec_for_param_reads_the_mesh():
    """On a four-chip (1, 4) mesh placement follows the mesh, and
    attention_block's sequence-parallel choice agrees with the spec rule."""
    import dataclasses
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import ShapeCell
    from repro.distributed.sharding import cache_specs, divides, tree_specs
    from repro.launch.dryrun import param_struct
    from repro.models.layers import attention_block

    mesh = _abstract_mesh((1, 4), ("data", "model"))
    moe = get_config("qwen3-moe-30b-a3b")
    assert moe.n_kv_heads == 4
    layers = tree_specs(param_struct(moe), mesh)["layers"]
    assert layers["wk"] == layers["wv"] == P(None, None, "model", None)
    cache = cache_specs(moe, ShapeCell("four", 512, 4, "decode"), mesh)
    assert cache["k"] == cache["v"] == P(None, "data", None, "model", None)

    hymba = get_config("hymba-1.5b")
    assert hymba.n_heads == 25
    wq = tree_specs(param_struct(hymba), mesh)["layers"]["wq"]
    assert wq == P(None, None, None, None)

    sds = jax.ShapeDtypeStruct
    S, D = 4 * 1024, 64
    for cfg in (hymba, moe):
        cfg = dataclasses.replace(cfg, qk_norm=False)
        Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        p = {"wq": sds((D, Hq, hd), jnp.float32),
             "wk": sds((D, Hkv, hd), jnp.float32),
             "wv": sds((D, Hkv, hd), jnp.float32),
             "wo": sds((Hq, hd, D), jnp.float32)}
        with jax.sharding.use_abstract_mesh(mesh):
            jaxpr = jax.make_jaxpr(
                lambda x, p, pos, cfg=cfg: attention_block(
                    x, p, cfg, positions=pos, window=64))(
                sds((1, S, D), jnp.float32), p, sds((1, S), jnp.int32))
        seqpar = "shard_map" in str(jaxpr)
        heads_sharded = "model" in tree_specs(
            param_struct(cfg), mesh)["layers"]["wq"]
        assert seqpar == (not divides(Hq, mesh)) == (not heads_sharded), \
            cfg.name


def test_cells_for_skips():
    assert "long_500k" not in cells_for(get_config("llama3-8b"))
    assert "long_500k" in cells_for(get_config("mamba2-130m"))


def test_roofline_math():
    from repro.launch.roofline import analyze
    rec = {
        "arch": "x", "cell": "train_4k", "mesh": "single", "tag": "",
        "chips": 256, "kind": "train", "seq_len": 4096, "global_batch": 256,
        "flops": 1.97e14, "bytes_accessed": 8.19e11,
        "collective_bytes": {"all-reduce": 5e10, "count": 3},
        "peak_bytes": 2 ** 30, "params": 8e9, "active_params": 8e9,
    }
    a = analyze(rec)
    assert abs(a["t_compute_s"] - 1.0) < 1e-6
    assert abs(a["t_memory_s"] - 1.0) < 1e-6
    assert abs(a["t_collective_s"] - 1.0) < 1e-6
    assert a["model_flops"] == 6 * 8e9 * 4096 * 256
    assert a["dominant"] in ("compute", "memory", "collective")


def test_make_local_mesh():
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh()
    assert set(mesh.axis_names) == {"data", "model"}


def test_tiny_lower_on_local_mesh():
    """End-to-end lower+compile of a reduced arch on the local 1-device
    mesh — the same code path the 512-device dry-run exercises."""
    from repro.launch.mesh import make_local_mesh
    from repro.train.optimizer import AdamWConfig, adamw_init
    from repro.train.step import make_train_step
    from repro.models.transformer import init_params

    cfg = get_config("qwen3-4b").reduced()
    mesh = make_local_mesh()
    params = jax.eval_shape(
        lambda k: init_params(cfg, k, dtype=jnp.float32),
        jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw_init, params)
    batch = {
        "tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32),
        "labels": jax.ShapeDtypeStruct((4, 16), jnp.int32),
    }
    step = make_train_step(cfg, AdamWConfig())
    with jax.set_mesh(mesh):
        lowered = jax.jit(step).lower(params, opt, batch)
    compiled = lowered.compile()
    assert compiled.cost_analysis() is not None


def test_timed_execute_refeeds_donated_args():
    """`dryrun --execute` timing helper: donated args are re-fed from the
    step's outputs between repeats, warmup is excluded from the stats."""
    from repro.launch.dryrun import _timed_execute

    calls = []

    def fake_compiled(params, opt, batch):
        calls.append((params, opt, batch))
        return (params + 1, opt + 10, {"loss": 0.0})

    out = _timed_execute(fake_compiled, [0, 0, "batch"], repeats=3,
                         refeed=((0, 0), (1, 1)), block=lambda o: None)
    assert out["execute_repeats"] == 3
    assert out["time_s"] > 0.0
    assert out["time_s_median"] >= out["time_s"]
    # warmup + 3 timed calls; params/opt chain through the outputs
    assert [(c[0], c[1]) for c in calls] == [(0, 0), (1, 10), (2, 20), (3, 30)]
    assert all(c[2] == "batch" for c in calls)   # non-donated arg untouched


def test_timed_execute_zeros_materialisation_local():
    """_zeros_like_structs + _timed_execute against a real compiled fn on
    the local device — the --execute path minus the 512-device mesh."""
    from repro.launch.dryrun import _timed_execute, _zeros_like_structs

    def f(x, y):
        return (x @ y, x.sum())

    structs = (jax.ShapeDtypeStruct((8, 8), jnp.float32),
               jax.ShapeDtypeStruct((8, 8), jnp.float32))
    compiled = jax.jit(f).lower(*structs).compile()
    args = _zeros_like_structs(structs, compiled.input_shardings[0])
    assert args[0].shape == (8, 8)
    out = _timed_execute(compiled, args, repeats=2)
    assert out["execute_repeats"] == 2 and out["time_s"] > 0.0


# ---------------------------------------------------------------------------
# Mesh helper: Auto axes, discovery through jax.set_mesh, shard_map
# ---------------------------------------------------------------------------

def _local_mesh():
    from repro.launch.mesh import make_local_mesh
    return make_local_mesh()


def test_no_mesh_is_empty():
    m = jax.sharding.get_abstract_mesh()
    assert m.empty
    assert tuple(m.axis_names) == ()


def test_set_mesh_discovery_and_restore():
    mesh = _local_mesh()
    assert jax.sharding.get_abstract_mesh().empty
    with jax.set_mesh(mesh):
        active = jax.sharding.get_abstract_mesh()
        assert not active.empty
        assert tuple(active.axis_names) == ("data", "model")
        assert active.shape["model"] == 1
        assert active.shape["data"] == jax.device_count()
    assert jax.sharding.get_abstract_mesh().empty


def test_set_mesh_restores_on_exception():
    mesh = _local_mesh()
    with pytest.raises(RuntimeError, match="boom"):
        with jax.set_mesh(mesh):
            raise RuntimeError("boom")
    assert jax.sharding.get_abstract_mesh().empty


def test_set_mesh_nesting():
    from repro.launch.mesh import make_mesh
    m1 = _local_mesh()
    m2 = make_mesh((1, jax.device_count()), ("pod", "model"))
    with jax.set_mesh(m1):
        with jax.set_mesh(m2):
            assert tuple(jax.sharding.get_abstract_mesh().axis_names) == \
                ("pod", "model")
        assert tuple(jax.sharding.get_abstract_mesh().axis_names) == \
            ("data", "model")


def test_make_mesh_axes_are_auto():
    from jax.sharding import AxisType
    from repro.launch.mesh import make_mesh
    assert _local_mesh().axis_types == (AxisType.Auto, AxisType.Auto)
    mesh = make_mesh((1, 1, jax.device_count()), ("pod", "data", "model"))
    assert mesh.axis_types == (AxisType.Auto,) * 3


def test_filter_spec_tracks_active_mesh():
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import filter_spec
    spec = P(("pod", "data"), None, "model")
    assert filter_spec(spec) is None              # no mesh → no-op marker
    with jax.set_mesh(_local_mesh()):
        assert filter_spec(spec) == P(("data",), None, "model")


def test_maybe_shard_inside_jit_under_mesh():
    """with_sharding_constraint with a bare PartitionSpec resolves
    against the mesh that jax.set_mesh activated."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import maybe_shard

    x = jnp.arange(8.0).reshape(4, 2)
    f = jax.jit(lambda x: maybe_shard(x * 2, P("data", None)))
    with jax.set_mesh(_local_mesh()):
        y = f(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x) * 2)
    # and off-mesh it is an identity wrapper
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda x: maybe_shard(x, P("data", None)))(x)),
        np.asarray(x))


def test_tree_shardings_lower_with_in_shardings():
    from jax.sharding import NamedSharding
    from repro.distributed.sharding import tree_shardings
    mesh = _local_mesh()
    tree = {"wq": jnp.zeros((2, 8, 16, 8)), "b": jnp.zeros((3,))}
    shardings = tree_shardings(mesh, tree)
    assert all(isinstance(s, NamedSharding)
               for s in jax.tree.leaves(shardings))
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            lambda t: jax.tree.map(lambda l: l + 1, t),
            in_shardings=(shardings,),
        ).lower(tree)
    assert lowered.compile() is not None


def test_shard_map_runs_on_installed_jax():
    from jax.sharding import PartitionSpec as P
    mesh = _local_mesh()
    n = jax.device_count()
    x = jnp.arange(4 * n, dtype=jnp.float32).reshape(n, 4)

    def body(xl):
        i = jax.lax.axis_index("data")
        return xl + i.astype(jnp.float32)

    with jax.set_mesh(mesh):
        y = jax.shard_map(
            body, mesh=jax.sharding.get_abstract_mesh(),
            in_specs=(P("data", None),), out_specs=P("data", None),
            check_vma=False,
        )(x)
    expect = np.asarray(x) + np.arange(n)[:, None]
    np.testing.assert_array_equal(np.asarray(y), expect)


def test_shard_map_collective():
    from jax.sharding import PartitionSpec as P
    mesh = _local_mesh()
    n = jax.device_count()
    x = jnp.ones((n, 2), jnp.float32)

    def body(xl):
        return jax.lax.psum(xl, "data")

    with jax.set_mesh(mesh):
        y = jax.shard_map(
            body, mesh=jax.sharding.get_abstract_mesh(),
            in_specs=(P("data", None),), out_specs=P("data", None),
            check_vma=False,
        )(x)
    np.testing.assert_array_equal(np.asarray(y), np.full((n, 2), n))


def test_use_compile_cache_respects_environment(monkeypatch):
    from repro import runtime
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert runtime.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None   # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = runtime.use_compile_cache()
        assert path == str(runtime.CHECKOUT_CACHE_DIR)
        assert path.endswith(".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
