"""Seeded weights and the plain float32 references.

The benchmark makes the weights itself, from ``--seed``, on the device
in one jitted call, in the dtype each leaf is served in; the program is
handed them, and the reference makes them again the same way after the
program's state is freed.  The reference takes nothing the program
made: only the shapes of the program's parameter tree (so the leaves
land where the program reads them) and the rules below, which say what
each leaf is.

Each reference is the model's teacher-forced forward in straightforward
``jax.numpy`` at float32 with ``precision="highest"`` matmuls: no
kernels, no cache, no batching across requests.  ``gqa_glu`` is the
decoder as the program's configuration runs granite-3-2b (RMSNorm,
split-half RoPE, grouped-query causal attention, the relufied gated FFN
``relu(x Wg) * (x Wu) Wd``, tied output head).

``precision="fp8"`` is the control: every matmul's operands are rounded
to float8 e4m3 (scaled per row of activations and per output column of
weights) before an exact product, the step below the bfloat16 the
configuration serves in.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
ROPE_THETA = 10000.0
F8_MAX = 448.0

# -- weights -------------------------------------------------------------------


def _normal(scale):
    return lambda key, shape: jax.random.normal(key, shape) * scale


def _fan_in(key, shape):
    return jax.random.normal(key, shape) * shape[-2] ** -0.5


def _around(center, spread):
    return lambda key, shape: center + spread * jax.random.normal(key, shape)


# leaf path (without the leading stacked-layer axis) -> initialiser
RULES: Dict[str, Dict[str, Callable]] = {
    "gqa_glu": {
        "embed": _normal(0.02),
        "final_norm/scale": _around(1.0, 0.1),
        "layers/ln1/scale": _around(1.0, 0.1),
        "layers/ln2/scale": _around(1.0, 0.1),
        "layers/attn/wq": _fan_in, "layers/attn/wk": _fan_in,
        "layers/attn/wv": _fan_in, "layers/attn/wo": _fan_in,
        "layers/mlp/w_gate": _fan_in, "layers/mlp/w_up": _fan_in,
        "layers/mlp/w_down": _fan_in,
    },
}


def seed_key(seed: int) -> jax.Array:
    """A key from any whole seed, also past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


def _paths(tree) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def make_weights(kind: str, abstract, seed: int):
    """The parameter tree with ``abstract``'s structure, shapes and
    dtypes, every leaf drawn by its rule from the seed (leaf ``i`` in
    flattening order from ``fold_in(key, i)``), in one jitted call."""
    rules = RULES[kind]
    paths = _paths(abstract)
    missing = [p for p in paths if p not in rules]
    if missing:
        raise KeyError(f"no weight rule for {kind} leaves {missing}")
    leaves, treedef = jax.tree_util.tree_flatten(abstract)

    def gen(key):
        out = []
        for i, (p, a) in enumerate(zip(paths, leaves)):
            v = rules[p](jax.random.fold_in(key, i), a.shape)
            out.append(v.astype(a.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)
    return jax.jit(gen)(seed_key(seed))


# -- matmuls at the two precisions ------------------------------------------------

def _q8(a, axis):
    s = F8_MAX / jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True),
                             1e-30)
    return (a * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def mm(a, w, precision: str):
    """``a @ w`` in float32; ``fp8`` rounds both operands first."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        a, w = _q8(a, -1), _q8(w, -2)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# -- gqa_glu (granite-3-2b as configured) ----------------------------------------

def _rope(x, pos):
    d = x.shape[-1]
    inv = 1.0 / (ROPE_THETA ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gqa_layer(x, lp, precision, heads, kv_heads):
    S, d = x.shape
    D = lp["attn"]["wq"].shape[-1] // heads
    pos = jnp.arange(S)
    h = _rms(x, lp["ln1"]["scale"])
    q = mm(h, lp["attn"]["wq"], precision).reshape(S, heads, D)
    k = mm(h, lp["attn"]["wk"], precision).reshape(S, kv_heads, D)
    v = mm(h, lp["attn"]["wv"], precision).reshape(S, kv_heads, D)
    q, k = _rope(q, pos), _rope(k, pos)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)                     # head h -> h // G
    v = jnp.repeat(v, group, axis=1)
    if precision == "fp8":
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * D ** -0.5
    causal = pos[:, None] >= pos[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST)
    x = x + mm(o.reshape(S, heads * D), lp["attn"]["wo"], precision)
    h2 = _rms(x, lp["ln2"]["scale"])
    g = jax.nn.relu(mm(h2, lp["mlp"]["w_gate"], precision))
    u = mm(h2, lp["mlp"]["w_up"], precision)
    return x + mm(g * u, lp["mlp"]["w_down"], precision)


@partial(jax.jit, static_argnames=("precision", "heads", "kv_heads"))
def _gqa_logits(params, tokens, *, precision, heads, kv_heads):
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, lp):
        return _gqa_layer(x, _f32(lp), precision, heads, kv_heads), None
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"])
    return mm(x, params["embed"].T, precision)


# -- the readings -----------------------------------------------------------------------

def logits(kind: str, params, tokens, precision: str, arch: Dict):
    """(S, V) float32 logits of the teacher-forced forward over ``tokens``."""
    with jax.default_matmul_precision("highest"):
        if kind == "gqa_glu":
            return _gqa_logits(params, tokens, precision=precision,
                               heads=arch["num_attention_heads"],
                               kv_heads=arch["num_key_value_heads"])
    raise ValueError(f"unknown reference {kind!r}")


@jax.jit
def _gaps(ref, nxt, other):
    """Per position: how far below the reference's best logit lie the
    served next token and the other precision's first choice."""
    best = ref.max(-1)
    served = jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]
    pick = jnp.take_along_axis(ref, other.argmax(-1)[:, None], -1)[:, 0]
    return best - served, best - pick


def gaps(kind: str, params, seq: np.ndarray, pad_to: int, arch: Dict,
         control: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """Gaps at every position of ``seq`` (prompt + served tokens),
    padded on the right to ``pad_to`` so one program serves every
    sequence of a cell (a causal forward never reads the padding).
    -> (served token's gap at position p for token p + 1, the fp8
    control's gap at p; zeros unless ``control``)."""
    n = len(seq)
    toks = np.zeros((pad_to,), np.int32)
    toks[:n] = seq
    toks = jnp.asarray(toks)
    ref = logits(kind, params, toks, "f32", arch)
    other = logits(kind, params, toks, "fp8", arch) if control else ref
    nxt = jnp.concatenate([toks[1:], toks[:1]])
    g_served, g_ctl = (np.asarray(a)[:n - 1] for a in _gaps(ref, nxt, other))
    return g_served, (g_ctl if control else np.zeros_like(g_ctl))
