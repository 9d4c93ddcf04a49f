"""HLO opcode byte/flop histogram — the dry-run "profiler".

There is no wall-clock profile on CPU, so §Perf iterations localise the
dominant roofline term by ranking compiled-HLO ops by result bytes
(the quantity XLA cost analysis accumulates into ``bytes accessed``).
Feeds the hypothesis step: "what IS the per-layer byte whale?"

Usage:
  python -m repro.launch.hlo_histogram --arch llama3-8b --cell train_4k

:func:`main` gives the CPU backend 256 devices before any backend starts;
importing this module changes no flag.
"""
import argparse
import collections
import re
from typing import Dict, Tuple

import jax

from ..configs import get_config
from ..configs.base import SHAPE_CELLS
from ..launch.dryrun import lower_cell, _shape_bytes
from ..launch.mesh import make_production_mesh

_OP_RE = re.compile(r"^\s*(?:ROOT )?[%\w.\-]+ = (.+?) ([\w\-]+)\(")

# Opcodes XLA:TPU fuses into neighbouring producers/consumers — their
# results never round-trip HBM on the target backend.  The CPU backend
# (which the dry-run compiles with) fuses far less, so raw ``bytes
# accessed`` over-counts them; excluding them gives a TPU-fusion-adjusted
# LOWER estimate of the memory term (the truth lies between).
_FUSIBLE = {
    "convert", "broadcast", "add", "subtract", "multiply", "divide",
    "select", "compare", "exponential", "tanh", "maximum", "minimum",
    "and", "or", "not", "negate", "abs", "rsqrt", "sqrt", "power",
    "iota", "bitcast", "copy", "reduce-precision", "constant",
    "reshape", "exponential-minus-one", "log", "sign", "clamp",
    "concatenate", "pad", "slice", "reverse",
}


def fused_bytes_estimate(hlo_text: str) -> Tuple[int, int]:
    """(raw result bytes, TPU-fusion-adjusted bytes) over the module."""
    raw = fused = 0
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        sig, op = m.groups()
        b = _shape_bytes(sig)
        raw += b
        if op not in _FUSIBLE:
            fused += b
    return raw, fused


def histogram(hlo_text: str, top: int = 25) -> Dict[str, Tuple[int, int]]:
    """opcode → (total result bytes, op count), descending by bytes."""
    agg: Dict[str, list] = collections.defaultdict(lambda: [0, 0])
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        sig, op = m.groups()
        b = _shape_bytes(sig)
        agg[op][0] += b
        agg[op][1] += 1
    ranked = sorted(agg.items(), key=lambda kv: -kv[1][0])[:top]
    return {k: (v[0], v[1]) for k, v in ranked}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--cell", required=True, choices=list(SHAPE_CELLS))
    ap.add_argument("--layers", type=int, default=2,
                    help="truncated layer count (keeps compiles fast)")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--remat-policy", default="dots")
    args = ap.parse_args(argv)
    jax.config.update("jax_num_cpu_devices", 256)

    import dataclasses
    from ..models import layers as _ly, transformer as _tf

    cfg = dataclasses.replace(get_config(args.arch), n_layers=args.layers)
    mesh = make_production_mesh()
    with _tf.scan_unroll(max(2, args.layers)), _ly.chunk_unroll(8):
        low = lower_cell(cfg, SHAPE_CELLS[args.cell], mesh, remat=True,
                         remat_policy=args.remat_policy)
        compiled = low.compile()
    hist = histogram(compiled.as_text(), args.top)
    total = sum(b for b, _ in hist.values())
    print(f"{args.arch} × {args.cell} (L={args.layers}) — "
          f"top {args.top} opcodes by result bytes:")
    for op, (b, n) in hist.items():
        print(f"  {op:28s} {b/1e9:10.2f} GB  ×{n:5d}  "
              f"({b / max(total, 1):5.1%})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
