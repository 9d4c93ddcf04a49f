"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this lowers the appropriate step function (train_step /
prefill / serve_step) against ShapeDtypeStruct inputs on the production
mesh, compiles it, and records:

* ``compiled.memory_analysis()``  — bytes per device (proves it fits);
* ``compiled.cost_analysis()``    — HLO FLOPs / bytes for §Roofline;
* collective bytes by op kind     — parsed from the optimized HLO.

With ``--execute N`` the compiled cell is additionally *run* N times on
zero-filled sharded inputs (donated buffers are re-fed from the step's
own outputs) and the best wall-clock lands in the record as ``time_s``
— turning the characterisation ledger into calibration samples that
``python -m repro.calibrate collect/fit`` harvests as ``step:<kind>``
op classes, so production-scale runs feed the roofline fit, not just
microbenchmarks and fixtures.  Execution allocates the cell's real
footprint; keep it for hardware runs.

Results append to a JSONL ledger (``--out``), one record per cell, so an
interrupted matrix run resumes where it stopped (``--skip-done``).

:func:`main` gives the CPU backend 512 devices before any backend starts
(``jax_num_cpu_devices``); importing this module changes no flag, so a
process on the chip can use :func:`lower_cell` and friends as they are.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --cell train_4k
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun.jsonl
  python -m repro.launch.dryrun --arch llama3-8b --cell train_4k \
      --execute 5 --tag calib
"""
import argparse
import functools
import json
import os
import re
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..configs import all_configs, cells_for, get_config
from ..configs.base import ArchConfig, ShapeCell, SHAPE_CELLS
from ..distributed.sharding import (batch_axes, cache_specs, divides,
                                    tree_shardings)
from ..models.transformer import decode_step, forward, init_cache, init_params, prefill
from ..runtime import use_compile_cache
from ..train.optimizer import AdamWConfig, adamw_init
from ..train.step import make_train_step

__all__ = ["input_specs", "lower_cell", "run_cell", "main"]


# ---------------------------------------------------------------------------
# ShapeDtypeStruct inputs (weak-type-correct, shardable, zero allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def param_struct(cfg: ArchConfig, dtype=jnp.bfloat16):
    return jax.eval_shape(
        functools.partial(init_params, cfg, dtype=dtype),
        jax.random.PRNGKey(0))


def input_specs(cfg: ArchConfig, cell: ShapeCell) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    B, S = cell.global_batch, cell.seq_len
    d = cfg.d_model
    if cell.kind == "train":
        batch = {"tokens": _sds((B, S), jnp.int32),
                 "labels": _sds((B, S), jnp.int32)}
        if cfg.prefix_len:
            batch["prefix_embed"] = _sds((B, cfg.prefix_len, d), jnp.bfloat16)
        if cfg.enc_dec:
            batch["enc_embed"] = _sds((B, cfg.enc_seq, d), jnp.bfloat16)
        return {"batch": batch}
    if cell.kind == "prefill":
        out = {"tokens": _sds((B, S), jnp.int32)}
        if cfg.prefix_len:
            out["prefix_embed"] = _sds((B, cfg.prefix_len, d), jnp.bfloat16)
        if cfg.enc_dec:
            out["enc_embed"] = _sds((B, cfg.enc_seq, d), jnp.bfloat16)
        return out
    # decode: one new token against a cache of length S
    cache = jax.eval_shape(
        functools.partial(init_cache, cfg, B, S, dtype=jnp.bfloat16))
    return {"tokens": _sds((B,), jnp.int32), "cache": cache}


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def lower_cell(cfg: ArchConfig, cell: ShapeCell, mesh, *,
               remat: bool = True, microbatches: int = 1,
               remat_policy: str = "minimal"):
    """Lower one cell on ``mesh``; returns the jax Lowered object."""
    params_t = param_struct(cfg)
    p_shard = tree_shardings(mesh, params_t)
    baxes = batch_axes(mesh)

    if cell.kind == "train":
        opt_t = jax.eval_shape(adamw_init, params_t)
        o_shard = tree_shardings(mesh, opt_t)
        specs = input_specs(cfg, cell)
        bshard = {}
        for k, v in specs["batch"].items():
            nd = len(v.shape)
            bshard[k] = NamedSharding(mesh, P(*((baxes,) + (None,) * (nd - 1))))
        step = make_train_step(cfg, AdamWConfig(), remat=remat,
                               microbatches=microbatches,
                               remat_policy=remat_policy)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, o_shard, bshard),
                donate_argnums=(0, 1),
            ).lower(params_t, opt_t, specs["batch"])
        return lowered

    if cell.kind == "prefill":
        specs = input_specs(cfg, cell)
        arg_shards = {}
        for k, v in specs.items():
            nd = len(v.shape)
            arg_shards[k] = NamedSharding(mesh, P(*((baxes,) + (None,) * (nd - 1))))

        def prefill_fn(params, inputs):
            kw = {k: v for k, v in inputs.items() if k != "tokens"}
            return prefill(params, inputs["tokens"], cfg, **kw)

        with jax.set_mesh(mesh):
            lowered = jax.jit(
                prefill_fn, in_shardings=(p_shard, arg_shards),
            ).lower(params_t, specs)
        return lowered

    # decode
    specs = input_specs(cfg, cell)
    c_specs = cache_specs(cfg, cell, mesh)
    cache_t = specs["cache"]
    c_shard = {}
    for k, v in cache_t.items():
        c_shard[k] = NamedSharding(mesh, c_specs.get(k, P()))
    tok_spec = P(baxes) if divides(cell.global_batch, mesh, baxes) else P(None)
    tok_shard = NamedSharding(mesh, tok_spec)

    def serve_step(params, tokens, cache):
        return decode_step(params, tokens, cfg, cache)

    with jax.set_mesh(mesh):
        lowered = jax.jit(
            serve_step,
            in_shardings=(p_shard, tok_shard, c_shard),
            donate_argnums=(2,),
        ).lower(params_t, specs["tokens"], cache_t)
    return lowered


# ---------------------------------------------------------------------------
# Collective-byte extraction from optimized HLO
# ---------------------------------------------------------------------------

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(sig: str) -> int:
    """Sum byte sizes of every typed shape in an HLO result signature."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind result bytes from an (optimized) HLO dump."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT )?[%\w.\-]+ = (.+?) (\S+)\(", ls)
        if not m:
            continue
        sig, opname = m.group(1), m.group(2)
        for kind in _COLLECTIVES:
            if opname == kind or opname.startswith(kind + "-start") \
               or opname == kind + "-done":
                if opname.endswith("-done"):
                    break  # counted at -start
                out[kind] += _shape_bytes(sig)
                out["count"] += 1
                break
    return out


# ---------------------------------------------------------------------------
# Cell execution + ledger
# ---------------------------------------------------------------------------

def _cost_of(compiled) -> Tuple[float, float, Dict[str, int]]:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def _timed_execute(compiled, args, *, repeats: int = 3,
                   refeed: Tuple[Tuple[int, int], ...] = (),
                   block=None, clock=time.perf_counter) -> Dict[str, float]:
    """Run ``compiled(*args)`` ``repeats`` times and report wall seconds.

    ``refeed`` maps output positions back onto donated argument slots
    (``(arg_idx, out_idx)``) — donated buffers are invalidated by the
    call, so repeats re-feed the step's own outputs (params/opt for
    train, the KV cache for decode), which is also what a real training
    loop does.  One extra warmup call absorbs transfer/dispatch warmup
    and is excluded from the stats.
    """
    if block is None:
        block = jax.block_until_ready
    args = list(args)
    times = []
    for _ in range(max(1, repeats) + 1):
        t0 = clock()
        out = compiled(*args)
        block(out)
        times.append(clock() - t0)
        for arg_idx, out_idx in refeed:
            args[arg_idx] = out[out_idx]
    timed = times[1:]
    timed_sorted = sorted(timed)
    mid = len(timed_sorted) // 2
    median = (timed_sorted[mid] if len(timed_sorted) % 2
              else 0.5 * (timed_sorted[mid - 1] + timed_sorted[mid]))
    return {"time_s": min(timed), "time_s_median": median,
            "execute_repeats": len(timed)}


# donated arg slot <- output position, per cell kind (train donates
# params+opt and returns them first; decode donates and returns the cache)
_REFEED = {"train": ((0, 0), (1, 1)), "prefill": (), "decode": ((2, 1),)}


def _zeros_like_structs(structs, shardings):
    """Materialise zero-filled device arrays for a struct tree, placed on
    the compiled executable's input shardings."""
    flat, treedef = jax.tree.flatten(structs)
    flat_sh = list(shardings)
    if len(flat_sh) != len(flat):       # some jax versions return a pytree
        flat_sh = jax.tree.flatten(shardings)[0]
    out = []
    for s, sh in zip(flat, flat_sh):
        out.append(jax.device_put(jnp.zeros(s.shape, s.dtype), sh))
    return jax.tree.unflatten(treedef, out)


def _execute_cell(compiled, structs, kind: str, repeats: int) -> Dict[str, float]:
    """Execute a compiled cell on zero inputs; returns timing fields."""
    args = _zeros_like_structs(structs, compiled.input_shardings[0])
    return _timed_execute(compiled, args, repeats=repeats,
                          refeed=_REFEED.get(kind, ()))


def run_cell(arch: str, cell_name: str, mesh_kind: str, *,
             remat: bool = True, microbatches: int = 1,
             extra_tag: str = "", remat_policy: str = "minimal",
             ffn_compress: float = 0.0, execute: int = 0) -> Dict[str, Any]:
    """Lower+compile one cell, plus the L=1/L=2 unrolled variants used to
    extrapolate exact per-layer FLOPs / bytes / collective traffic (XLA
    cost analysis counts a rolled scan body once, so the full-L program's
    raw numbers undercount by ~L×)."""
    import dataclasses as _dc

    from ..models import transformer as _tf

    cfg = get_config(arch)
    if ffn_compress > 0:
        # FullBlock row-compressed FFN execution: pruned rows of w_up/
        # w_gate (and cols of w_down) are removed entirely — on TPU the
        # static block indices fold into the weight layout at compile
        # time, so compressed execution IS a smaller dense matmul (the
        # alignment argument of paper §III-D).
        keep = 1.0 - ffn_compress
        cfg = _dc.replace(
            cfg, d_ff=max(256, int(round(cfg.d_ff * keep / 256)) * 256))
    cell = SHAPE_CELLS[cell_name]
    from .mesh import make_production_mesh
    mesh = make_production_mesh(multi_pod=mesh_kind == "multi")

    # --- full-size compile: THE dry-run proof + memory analysis -----------
    # (obs spans are no-ops unless recording is enabled; the ledger's
    # lower_s/compile_s fields below stay the source of truth)
    t0 = time.time()
    with obs.span("dryrun.lower", arch=arch, cell=cell_name, mesh=mesh_kind):
        lowered = lower_cell(cfg, cell, mesh, remat=remat,
                             microbatches=microbatches,
                             remat_policy=remat_policy)
    t_lower = time.time() - t0
    t0 = time.time()
    with obs.span("dryrun.compile", arch=arch, cell=cell_name,
                  mesh=mesh_kind):
        compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    flops_raw, bytes_raw, coll_raw = _cost_of(compiled)

    # --- optional real execution: wall-clock for the calibration loop ------
    timing: Dict[str, float] = {}
    if execute > 0:
        params_t = param_struct(cfg)
        specs = input_specs(cfg, cell)
        if cell.kind == "train":
            opt_t = jax.eval_shape(adamw_init, params_t)
            structs = (params_t, opt_t, specs["batch"])
        elif cell.kind == "prefill":
            structs = (params_t, specs)
        else:
            structs = (params_t, specs["tokens"], specs["cache"])
        with obs.span("dryrun.execute", arch=arch, cell=cell_name,
                      mesh=mesh_kind, repeats=execute):
            timing = _execute_cell(compiled, structs, cell.kind, execute)

    # --- per-layer extrapolation via unrolled L=1 / L=2 variants -----------
    from ..models import layers as _ly

    def measure(n_layers: int):
        kw = dict(n_layers=n_layers)
        if cfg.enc_dec:
            kw["enc_layers"] = n_layers
        cfg_l = _dc.replace(cfg, **kw)
        with _tf.scan_unroll(max(2, n_layers)), _ly.chunk_unroll(8):
            low = lower_cell(cfg_l, cell, mesh, remat=remat,
                             microbatches=microbatches,
                             remat_policy=remat_policy)
            return _cost_of(low.compile())

    L = cfg.n_layers
    f1, b1, c1 = measure(1)
    f2, b2, c2 = measure(2)
    flops = f1 + (L - 1) * max(f2 - f1, 0.0)
    bytes_acc = b1 + (L - 1) * max(b2 - b1, 0.0)
    coll = {}
    for k in set(c1) | set(c2):
        coll[k] = int(c1.get(k, 0) + (L - 1) * max(c2.get(k, 0) - c1.get(k, 0), 0))

    rec = {
        "arch": arch,
        "cell": cell_name,
        "mesh": mesh_kind,
        "tag": extra_tag,
        "chips": mesh.size,
        "kind": cell.kind,
        "seq_len": cell.seq_len,
        "global_batch": cell.global_batch,
        # per-device program totals (extrapolated to all L layers)
        "flops": flops,
        "bytes_accessed": bytes_acc,
        "collective_bytes": coll,
        # raw rolled-scan numbers kept for reference
        "flops_raw": flops_raw,
        "bytes_raw": bytes_raw,
        "collective_raw": {k: int(v) for k, v in coll_raw.items()},
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "peak_bytes": int(getattr(mem, "peak_memory_in_bytes",
                                  getattr(mem, "temp_size_in_bytes", 0))),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    if timing:
        rec["executed"] = True
        rec.update(timing)      # time_s / time_s_median / execute_repeats
    return rec


def _emit_trace(arch: str, cell: ShapeCell, out: str) -> Dict[str, Any]:
    """Capture the modeling-plane traced DAG for this cell and save it
    next to the ledger (``<out dir>/trace/<arch>_<cell>.json``).

    The returned fields join the measured HLO row to its modeling-plane
    sibling by content: the TraceGraph digest keys explore-cache entries
    for ``--workload traced:<arch>`` sweeps, and the lowered MVM totals
    are the analytic counterpart of the record's XLA ``flops``.
    """
    from ..trace import lower_graph, trace_model
    from ..trace.diff import summarize

    step = {"train": "forward"}.get(cell.kind, cell.kind)
    graph = trace_model(get_config(arch), step=step, seq_len=cell.seq_len,
                        batch=cell.global_batch)
    tdir = os.path.join(os.path.dirname(out) or ".", "trace")
    os.makedirs(tdir, exist_ok=True)
    path = os.path.join(tdir, f"{arch}_{cell.name}.json")
    graph.save(path)
    wl = lower_graph(graph)
    # strict pre-flight: a broken lowered DAG fails this cell's record
    # (the per-cell try/except upstream turns it into a failure row)
    from ..analysis import preflight
    preflight(wl, strict=True, where="dryrun.emit_trace")
    s = summarize(wl)
    return {"trace_path": path, "trace_digest": graph.digest(),
            "trace_ops": len(wl), "trace_mvm_macs": s["mvm_macs"],
            "trace_mvm_weights": s["mvm_weights"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id")
    ap.add_argument("--cell", default=None, choices=list(SHAPE_CELLS))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="run the full arch × cell matrix")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--execute", type=int, default=0, metavar="N",
                    help="additionally RUN each compiled cell N times on "
                         "zero inputs and record best wall-clock as "
                         "time_s (allocates the real footprint; feeds "
                         "repro.calibrate)")
    ap.add_argument("--emit-trace", action="store_true",
                    help="also capture the modeling-plane traced DAG "
                         "(repro.trace) per cell, save the graph JSON "
                         "under <out dir>/trace/, and stamp its content "
                         "digest + MVM totals into the ledger record")
    ap.add_argument("--tag", default="")
    ap.add_argument("--remat-policy", default="minimal",
                    choices=["minimal", "dots", "nothing"],
                    help="activation-checkpoint policy for train cells")
    ap.add_argument("--ffn-compress", type=float, default=0.0,
                    help="execute with FullBlock row-compressed FFN at "
                         "this sparsity ratio (the paper's technique in "
                         "the execution plane): d_ff → (1-r)·d_ff")
    args = ap.parse_args(argv)
    jax.config.update("jax_num_cpu_devices", 512)
    use_compile_cache()

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = set()
    if args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r["arch"], r["cell"], r["mesh"], r.get("tag", "")))
                except json.JSONDecodeError:
                    pass

    jobs = []
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        for arch, cfg in all_configs().items():
            for cell_name in cells_for(cfg):
                for mk in meshes:
                    jobs.append((arch, cell_name, mk))
    else:
        if not args.arch or not args.cell:
            ap.error("--arch and --cell required unless --all")
        cfg = get_config(args.arch)
        if args.cell not in cells_for(cfg):
            print(f"SKIP {args.arch}/{args.cell}: long_500k needs "
                  "sub-quadratic attention (see DESIGN.md §3.2)")
            return 0
        jobs = [(args.arch, args.cell, mk) for mk in meshes]

    failures = 0
    for arch, cell_name, mk in jobs:
        if (arch, cell_name, mk, args.tag) in done:
            print(f"skip (done): {arch} {cell_name} {mk}")
            continue
        print(f"=== {arch} × {cell_name} × {mk} ===", flush=True)
        try:
            rec = run_cell(arch, cell_name, mk, remat=not args.no_remat,
                           extra_tag=args.tag, remat_policy=args.remat_policy,
                           ffn_compress=args.ffn_compress,
                           execute=args.execute)
            if args.emit_trace:
                rec.update(_emit_trace(arch, SHAPE_CELLS[cell_name], args.out))
                print(f"    trace: {rec['trace_path']} "
                      f"digest={rec['trace_digest'][:16]} "
                      f"mvm_macs={rec['trace_mvm_macs']:.3e}", flush=True)
            timed = (f" time={rec['time_s']:.3f}s" if "time_s" in rec else "")
            print(f"    flops={rec['flops']:.3e} bytes={rec['bytes_accessed']:.3e} "
                  f"coll={sum(v for k, v in rec['collective_bytes'].items() if k != 'count'):.3e} "
                  f"peak/device={rec['peak_bytes']/2**30:.2f} GiB "
                  f"compile={rec['compile_s']}s{timed}", flush=True)
        except Exception as e:  # noqa: BLE001 — ledger records failures
            rec = {"arch": arch, "cell": cell_name, "mesh": mk,
                   "tag": args.tag, "error": f"{type(e).__name__}: {e}"}
            failures += 1
            print(f"    FAILED: {rec['error'][:300]}", flush=True)
        obs.event("dryrun.cell.done", arch=arch, cell=cell_name, mesh=mk,
                  ok="error" not in rec,
                  compile_s=rec.get("compile_s"), time_s=rec.get("time_s"))
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
