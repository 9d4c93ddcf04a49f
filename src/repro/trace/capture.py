"""Capture a jaxpr into the jax-free :class:`~repro.trace.ir.TraceGraph`.

The only module in :mod:`repro.trace` that imports jax — and it does so
lazily, so ``repro.trace.lower`` / fixture replay keep working in the
no-jax CI job.  ``jax.make_jaxpr`` runs on ``ShapeDtypeStruct`` inputs:
capture is abstract interpretation, no device, no compilation.

Three front doors:

* :func:`capture` — any callable + example (abstract) args, with the
  argument positions holding model parameters named so the lowerer can
  attribute weight storage.
* :func:`trace_model` — an LM :class:`~repro.configs.base.ArchConfig`
  plus a step kind (``forward`` / ``prefill`` / ``decode``), traced from
  the shape-faithful reference programs (default) or the real
  :mod:`repro.models.transformer` (``source="model"``, best-effort: the
  execution plane's flash-attention tiling and MoE capacity dispatch are
  *not* MAC-identical to the hand DAGs, see ``docs/tracing.md``).
* :func:`traced_workload` — config (or name) → lowered :class:`Workload`,
  the entry point the explore CLI's ``--workload traced:…`` uses.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from ..core.workload import Workload
from .ir import TraceEqn, TraceGraph, TraceVar
from .lower import lower_graph

__all__ = ["capture", "trace_model", "traced_workload", "traced_cnn",
           "TRACE_STEPS"]

TRACE_STEPS = ("forward", "prefill", "decode")

_KEY_RE = re.compile(r"[^A-Za-z0-9_]+")


def _path_name(path) -> str:
    """``(DictKey('layers'), DictKey('wq'))`` → ``"layers/wq"``."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(_KEY_RE.sub("_", str(p)).strip("_"))
    return "/".join(parts)


def _json_safe(v):
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if hasattr(v, "_asdict"):                     # namedtuple (Gather/Conv/
        return {k: _json_safe(x) for k, x in v._asdict().items()}  # Scatter DNs)
    if isinstance(v, (tuple, list)):
        return [_json_safe(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _json_safe(x) for k, x in v.items()}
    if hasattr(v, "name") and not callable(v):    # enums (Precision, …)
        return str(v.name)
    return str(v)                                 # dtypes, everything else


def _deep_eqn_count(jaxpr) -> int:
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = len(jaxpr.eqns)
    for e in jaxpr.eqns:
        for val in e.params.values():
            inner = getattr(val, "jaxpr", val)
            if hasattr(inner, "eqns"):
                n += _deep_eqn_count(inner)
    return n


def _convert_jaxpr(closed, name: str) -> TraceGraph:
    """Recursively convert a (Closed)Jaxpr into a TraceGraph."""
    from jax.extend import core

    jaxpr = getattr(closed, "jaxpr", closed)
    ids: Dict[object, str] = {}
    vars_table: Dict[str, TraceVar] = {}
    consts = []

    def vid(v) -> str:
        if isinstance(v, core.Literal):
            vn = f"c{len(consts)}"
            consts.append(vn)
            vars_table[vn] = TraceVar(tuple(getattr(v.aval, "shape", ())),
                                      str(getattr(v.aval, "dtype", "?")))
            return vn
        if v not in ids:
            vn = f"v{len(ids)}"
            ids[v] = vn
            vars_table[vn] = TraceVar(tuple(getattr(v.aval, "shape", ())),
                                      str(getattr(v.aval, "dtype", "?")))
        return ids[v]

    invars = [vid(v) for v in jaxpr.invars]
    for cv in jaxpr.constvars:
        consts.append(vid(cv))

    eqns = []
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        params: Dict[str, object] = {}
        body: Optional[TraceGraph] = None
        if prim == "while":
            body = _convert_jaxpr(eqn.params["body_jaxpr"], f"{prim}:body")
            for k in ("cond_nconsts", "body_nconsts"):
                params[k] = int(eqn.params.get(k, 0))
        elif prim == "cond":
            # data-dependent branch: keep the deepest one (upper bound on
            # the work a branch can do; documented in docs/tracing.md)
            branches = eqn.params["branches"]
            body = _convert_jaxpr(max(branches, key=_deep_eqn_count),
                                  f"{prim}:branch")
        else:
            for k, val in eqn.params.items():
                if isinstance(val, (core.Jaxpr, core.ClosedJaxpr)):
                    if body is None:
                        body = _convert_jaxpr(val, f"{prim}:{k}")
                    continue
                params[k] = _json_safe(val)
        eqns.append(TraceEqn(prim=prim,
                             invars=[vid(v) for v in eqn.invars],
                             outvars=[vid(v) for v in eqn.outvars],
                             params=params, body=body))

    # literal outvars become const vars so positional body-output
    # alignment in the lowerer is preserved
    return TraceGraph(name=name, invars=invars,
                      outvars=[vid(v) for v in jaxpr.outvars],
                      vars=vars_table, eqns=eqns, consts=consts)


def capture(fn, *example_args, param_argnums: Tuple[int, ...] = (0,),
            name: str = "traced", meta: Optional[dict] = None) -> TraceGraph:
    """Trace ``fn`` abstractly and convert its jaxpr to a TraceGraph.

    ``example_args`` may be (pytrees of) ``jax.ShapeDtypeStruct`` — no
    real data is needed.  Leaves of the arguments whose positions are in
    ``param_argnums`` are recorded as model parameters, named by their
    pytree path (``layers/wq``); the lowerer turns those names into
    weight attribution on the MVM nodes.
    """
    import jax

    closed = jax.make_jaxpr(fn)(*example_args)
    graph = _convert_jaxpr(closed, name)

    pos = 0
    weights: Dict[str, str] = {}
    for ai, arg in enumerate(example_args):
        leaves = jax.tree_util.tree_flatten_with_path(arg)[0]
        for path, _leaf in leaves:
            if ai in param_argnums:
                weights[graph.invars[pos]] = _path_name(path) or f"arg{ai}"
            pos += 1
    if pos != len(graph.invars):
        raise AssertionError(
            f"flattened args ({pos}) != jaxpr invars ({len(graph.invars)})")
    graph.weights = weights
    graph.meta = dict(meta or {})
    return graph


# ---------------------------------------------------------------------------
# Model-level capture.
# ---------------------------------------------------------------------------

def _model_program(cfg, step: str, seq_len: int, batch: int):
    """Abstract (fn, params, args) for the real execution-plane model."""
    import jax
    import jax.numpy as jnp

    from ..models import transformer

    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
    if step == "forward":
        return (lambda p, t: transformer.forward(p, t, cfg)), params, (toks,)
    if step == "prefill":
        return (lambda p, t: transformer.prefill(p, t, cfg)), params, (toks,)
    if step == "decode":
        cache = jax.eval_shape(
            lambda: transformer.init_cache(cfg, batch, seq_len))
        tok1 = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        return ((lambda p, t, c: transformer.decode_step(p, t, cfg, c)),
                params, (tok1, cache))
    raise ValueError(f"unknown step {step!r}; choose from {TRACE_STEPS}")


def trace_model(cfg, *, step: str = "forward", seq_len: int = 128,
                batch: int = 1, source: str = "reference") -> TraceGraph:
    """Trace one step of an LM config into a TraceGraph."""
    if step not in TRACE_STEPS:
        raise ValueError(f"unknown step {step!r}; choose from {TRACE_STEPS}")
    if source == "reference":
        from .reference import reference_program
        fn, params, args = reference_program(cfg, step=step,
                                             seq_len=seq_len, batch=batch)
    elif source == "model":
        fn, params, args = _model_program(cfg, step, seq_len, batch)
    else:
        raise ValueError(f"unknown source {source!r} "
                         "(choose 'reference' or 'model')")
    return capture(fn, params, *args,
                   name=f"{cfg.name}:{step}",
                   meta={"config": cfg.name, "step": step,
                         "seq_len": seq_len, "batch": batch,
                         "source": source,
                         "workload_name": f"traced-{cfg.name}-{step}"})


def traced_workload(cfg, *, step: str = "forward", seq_len: int = 128,
                    batch: int = 1, source: str = "reference") -> Workload:
    """Config (or config name) → auto-lowered :class:`Workload`.

    The traced sibling of :func:`repro.core.workload.lm_workload`: same
    DAG machinery downstream (schedulers, cost model, explore cache —
    keyed by the jaxpr digest via ``Workload.source_digest``), but the
    op list comes out of the program instead of out of a hand model.
    """
    if isinstance(cfg, str):
        from ..configs import get_config
        cfg = get_config(cfg)
    graph = trace_model(cfg, step=step, seq_len=seq_len, batch=batch,
                        source=source)
    return lower_graph(graph)


def traced_cnn(model: str = "resnet18", img: int = 32,
               num_classes: int = 100) -> Workload:
    """Traced sibling of the CNN builders (vgg16 / resnet18 / resnet50)."""
    from .reference import cnn_program
    fn, params, args = cnn_program(model, img=img, num_classes=num_classes)
    graph = capture(fn, params, *args, name=f"{model}-{img}",
                    meta={"model": model, "img": img,
                          "num_classes": num_classes,
                          "workload_name": f"traced-{model}-{img}"})
    return lower_graph(graph)
