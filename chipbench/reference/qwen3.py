"""Plain reference of the Qwen3 decoder as the program states it.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: no cache, no batching, no kernels, full causal
attention over the whole sequence.  It imports nothing of the program.
The weights are made from the seed by the program's documented scheme
(``init_params``: one key split four ways; the layer keys split over the
sorted weight names; normal draws scaled by 1/sqrt(fan-in), cast to the
served type; norm scales zero), so the reference computes the same model
without taking an array from the program.

Departure from the published Qwen3, followed here because the program
has it: the gated MLP uses GELU (tanh form) where Qwen3 uses SiLU.

``quant="fp8"`` rounds every weight and every activation that enters a
matrix product to float8 e4m3 (products still accumulate in float32):
the control, a step below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def layer_shapes(a: Dict) -> Dict[str, Tuple[int, ...]]:
    d, hd = a["d_model"], a.get("head_dim") or a["d_model"] // a["n_heads"]
    hq, hkv, ff = a["n_heads"], a["n_kv_heads"], a["d_ff"]
    s = {"ln1": (d,), "ln2": (d,), "wq": (d, hq, hd), "wk": (d, hkv, hd),
         "wv": (d, hkv, hd), "wo": (hq, hd, d)}
    if a.get("qk_norm"):
        s.update(q_norm=(hd,), k_norm=(hd,))
    e = a.get("n_experts", 1)
    if e > 1:
        s.update(w_router=(d, e), w_up=(e, d, ff), w_down=(e, ff, d),
                 w_gate=(e, d, ff))
    else:
        s.update(w_up=(d, ff), w_down=(ff, d), w_gate=(d, ff))
    return s


def _is_norm(name: str) -> bool:
    return name.startswith("ln") or name.endswith("_norm")


def _normal(key, shape, mul, div, dtype, sharding=None):
    """A normal draw times ``mul`` or over ``div``, as the scheme has it,
    made where ``sharding`` puts it (the draw does not depend on it)."""
    def draw(key):
        x = jax.random.normal(key, shape, jnp.float32)
        return (x * mul if div is None else x / div).astype(dtype)
    return jax.jit(draw, out_shardings=sharding)(key)


def weights(a: Dict, seed: int, dtype=jnp.bfloat16, place=None) -> Dict:
    """Every weight, stacked over layers.  ``place(name, shape)`` may give
    a sharding for a weight too large for one device; by default all
    lie on the default device."""
    place = place or (lambda name, shape: None)
    d, L = a["d_model"], a["n_layers"]
    k_emb, k_layers, _, k_head = jax.random.split(jax.random.PRNGKey(seed), 4)
    w = {"embed": _normal(k_emb, (a["vocab_size"], d), None, math.sqrt(d),
                          dtype),
         "final_norm": jnp.zeros((d,), dtype)}
    shapes = layer_shapes(a)
    keys = jax.random.split(k_layers, len(shapes))
    layers = {}
    for (name, shp), k in zip(sorted(shapes.items()), keys):
        if _is_norm(name):
            layers[name] = jnp.zeros((L,) + shp, dtype)
            continue
        fan_in = d if name in ("wq", "wk", "wv") else math.prod(shp[:-1])
        layers[name] = _normal(k, (L,) + shp, 1.0 / math.sqrt(max(fan_in, 1)),
                               None, dtype, place(name, (L,) + shp))
    w["layers"] = layers
    if not a.get("tie_embeddings"):
        w["lm_head"] = _normal(k_head, (d, a["vocab_size"]), None,
                               math.sqrt(d), dtype)
    return jax.block_until_ready(w)


# ---------------------------------------------------------------------------
# the layer equations
# ---------------------------------------------------------------------------

def _q(x, quant):
    if quant == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(spec, x, w, quant):
    return jnp.einsum(spec, _q(x, quant), _q(w, quant), precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs            # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def attention(x, w, a, quant):
    """Causal self-attention of one sequence (S, d), grouped KV heads.
    Returns the output and the keys (after norm and rotation) and values
    a cache would hold."""
    S = x.shape[0]
    hq, hkv = a["n_heads"], a["n_kv_heads"]
    hd = w["wq"].shape[-1]
    eps, theta = a["norm_eps"], a["rope_theta"]
    q = _mm("sd,dhk->shk", x, w["wq"], quant)
    k = _mm("sd,dhk->shk", x, w["wk"], quant)
    v = _mm("sd,dhk->shk", x, w["wv"], quant)
    if "q_norm" in w:
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    pos = jnp.arange(S)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    kv = (k, v)
    q = q.reshape(S, hkv, hq // hkv, hd)
    s = _mm("qhgd,khd->hgqk", q, k, quant) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("hgqk,khd->qhgd", p, v, quant).reshape(S, hq, hd)
    return _mm("shk,hkd->sd", o, w["wo"], quant), kv


def mlp(x, w, quant):
    h = gelu(_mm("sd,df->sf", x, w["w_gate"], quant)) \
        * _mm("sd,df->sf", x, w["w_up"], quant)
    return _mm("sf,fd->sd", h, w["w_down"], quant)


def route(x, w_router, a, quant):
    """Each token's top-k experts and their renormalised weights."""
    probs = jax.nn.softmax(_mm("sd,de->se", x, w_router, quant), -1)
    top_p, top_e = jax.lax.top_k(probs, a["top_k"])
    return top_p / top_p.sum(-1, keepdims=True), top_e


def dispatch_table(top_p: np.ndarray, top_e: np.ndarray, E: int):
    """For each expert, the tokens routed to it and their weights, padded
    with token S (a zero row) to one width: every token goes through
    exactly its k experts, none is dropped."""
    S, K = top_e.shape
    flat_e = top_e.ravel()
    order = np.argsort(flat_e, kind="stable")
    counts = np.bincount(flat_e, minlength=E)
    width = max(128, -(-int(counts.max()) // 128) * 128)
    idx = np.full((E, width), S, np.int32)
    wts = np.zeros((E, width), np.float32)
    start = 0
    for e in range(E):
        sel = order[start:start + counts[e]]
        idx[e, :counts[e]] = sel // K
        wts[e, :counts[e]] = top_p.ravel()[sel]
        start += counts[e]
    return idx, wts


@functools.partial(jax.jit, static_argnames=("quant",))
def _experts(h, idx, wts, w, *, quant):
    """Σ over each token's experts of weight · expert MLP(token)."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    xs = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])[idx]
    g = gelu(_mm("ecd,edf->ecf", xs, w["w_gate"], quant))
    y = _mm("ecf,efd->ecd", g * _mm("ecd,edf->ecf", xs, w["w_up"], quant),
            w["w_down"], quant) * wts[..., None]
    out = jnp.zeros((h.shape[0] + 1, h.shape[1]), h.dtype).at[idx].add(y)
    return out[:-1]


def layer_weights(layers: Dict, l: int) -> Dict:
    """Layer ``l``'s weights, all on the first device (the stacked
    weights may lie spread over several)."""
    dev = jax.devices()[0]
    return {k: jax.device_put(v[l], dev) for k, v in layers.items()}


_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


@functools.partial(jax.jit, static_argnames=("a_items", "quant"))
def _mixer(x, w, *, a_items, quant):
    """Attention and its residual; then the FFN's input, and for a mixture
    of experts each token's route (or, dense, the FFN and its residual)."""
    a = dict(a_items)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = a["norm_eps"]
    y, kv = attention(rms_norm(x, w["ln1"], eps), w, a, quant)
    x = x + y
    h = rms_norm(x, w["ln2"], eps)
    if a.get("n_experts", 1) > 1:
        return x, h, route(h, w["w_router"], a, quant), kv
    return x + mlp(h, w, quant), None, None, kv


def _layer(x, w, *, a_items, quant):
    """One decoder layer: (x, (keys, values))."""
    if "w_router" not in w:
        x, _, _, kv = _mixer(x, w, a_items=a_items, quant=quant)
        return x, kv
    rest = {k: v for k, v in w.items() if k not in _EXPERT_KEYS}
    x, h, routed, kv = _mixer(x, rest, a_items=a_items, quant=quant)
    idx, wts = dispatch_table(np.asarray(routed[0]), np.asarray(routed[1]),
                              dict(a_items)["n_experts"])
    experts = {k: w[k] for k in _EXPERT_KEYS}
    return x + _experts(h, jnp.asarray(idx), jnp.asarray(wts), experts,
                        quant=quant), kv


@functools.partial(jax.jit, static_argnames=("a_items", "quant"))
def _head(x, rows, final_norm, embed, *, a_items, quant):
    a = dict(a_items)
    h = rms_norm(x[rows], final_norm.astype(jnp.float32), a["norm_eps"])
    return _mm("sd,vd->sv", h, embed.astype(jnp.float32), quant)


def _items(a: Dict):
    keep = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
            "n_experts", "top_k", "qk_norm", "norm_eps", "rope_theta",
            "n_layers", "vocab_size", "tie_embeddings")
    return tuple((k, a[k]) for k in keep if k in a)


def logits(a: Dict, w: Dict, tokens: np.ndarray, rows: np.ndarray,
           quant: Optional[str] = None, pad_to: Optional[int] = None):
    """Logits (len(rows), V) at positions ``rows`` of one sequence.
    ``pad_to`` pads the sequence at its end (causal: no row sees the
    padding) so that sequences of many lengths share one program."""
    n = len(tokens)
    if pad_to and pad_to > n:
        tokens = np.concatenate([tokens, np.zeros(pad_to - n, np.int32)])
    items = _items(a)
    x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    for l in range(a["n_layers"]):
        x, _ = _layer(x, layer_weights(w["layers"], l), a_items=items,
                      quant=quant)
    head = w["embed"] if a.get("tie_embeddings") else w["lm_head"].T
    return _head(x, jnp.asarray(rows), w["final_norm"], head,
                 a_items=items, quant=quant)


def prefill(a: Dict, w: Dict, tokens: np.ndarray,
            quant: Optional[str] = None):
    """What a prefill of one prompt hands on: the last position's logits
    (V,) and each layer's keys and values, (L, S, Hkv, hd) each, as
    float32 NumPy arrays."""
    items = _items(a)
    x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    ks, vs = [], []
    for l in range(a["n_layers"]):
        x, (k, v) = _layer(x, layer_weights(w["layers"], l), a_items=items,
                           quant=quant)
        ks.append(np.asarray(k))
        vs.append(np.asarray(v))
    head = w["embed"] if a.get("tie_embeddings") else w["lm_head"].T
    last = _head(x, jnp.asarray([len(tokens) - 1]), w["final_norm"], head,
                 a_items=items, quant=quant)[0]
    return np.asarray(last), np.stack(ks), np.stack(vs)


@jax.jit
def _gaps(ref, tokens):
    """How far each token's logit lies below the row's best."""
    best = ref.max(-1)
    return best - jnp.take_along_axis(ref, tokens[:, None], -1)[:, 0]


def served_rows(prompt: np.ndarray, output: np.ndarray):
    """The sequence the reference reads (prompt and every served token
    but the last) and the rows whose logits chose each served token."""
    tokens = np.concatenate([prompt, output[:-1]]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(tokens), dtype=np.int32)
    return tokens, rows


def max_served_gap(a: Dict, seed: int,
                   sample: Sequence[Tuple[np.ndarray, np.ndarray]],
                   pad_to: Optional[int] = None) -> float:
    """The widest gap, over every served token of ``sample`` (pairs of
    prompt and served tokens), between the reference's best logit and
    the logit of the token the program served."""
    w = weights(a, seed)
    pad_to = pad_to or max(len(p) + len(o) for p, o in sample)
    worst = 0.0
    for prompt, output in sample:
        tokens, rows = served_rows(prompt, output)
        ref = logits(a, w, tokens, rows, pad_to=pad_to)
        worst = max(worst, float(_gaps(ref, jnp.asarray(output)).max()))
    return worst


def control_gaps(a: Dict, seed: int,
                 sample: Sequence[Tuple[np.ndarray, np.ndarray]],
                 quant: str = "fp8", pad_to: Optional[int] = None
                 ) -> List[float]:
    """The control: at each row of each sample, the token the reference
    computed at ``quant`` puts first, and how far its logit lies below
    the float32 reference's best.  Returns the widest gap per sample."""
    w = weights(a, seed)
    pad_to = pad_to or max(len(p) + len(o) for p, o in sample)
    out = []
    for prompt, output in sample:
        tokens, rows = served_rows(prompt, output)
        ref = logits(a, w, tokens, rows, pad_to=pad_to)
        low = logits(a, w, tokens, rows, quant=quant, pad_to=pad_to)
        out.append(float(_gaps(ref, low.argmax(-1)).max()))
    return out
