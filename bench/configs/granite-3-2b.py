"""granite-3-2b: weights from the seed, and the plain float32 reference.

Imports nothing of the program under test.  The weights are made here,
in one jitted call, in the layout the serving engine takes (stacked
layers under ``layers``) and in the type they are served in: bfloat16
matrices, float32 norm scales.  The reference makes them again from the
same seed; it never reads the program's copy.

The reference is the published dense GQA decoder as this configuration
runs it (``granite-3-2b.json``; the departures from Granite are listed
there): pre-norm RMSNorm blocks, rotary positions on the two halves of
each head, causal softmax attention with 8 key/value heads shared by
groups of 4 query heads, a SwiGLU MLP, a final RMSNorm and the tied
embedding as the output layer.  It runs in float32 with every matrix
product at ``highest`` precision, over whole sequences at once, one
layer at a time under a scan.

``precision="int8"`` is the control: the same forward with every weight
matrix quantized per output channel and every input of a linear layer
quantized per token to int8 (symmetric, round to nearest), the
arithmetic otherwise float32.  It is the step below the served
bfloat16 that a later change could be tempted to take.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EMBED_STD = 0.02


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "d": d,
        "h": h,
        "kv": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or d // h,
        "ff": cfg["intermediate_size"],
        "v": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
        "eps": cfg["rms_norm_eps"],
        "theta": cfg["rope_theta"],
    }


def _leaves(m: dict) -> dict:
    """Path -> (shape, std) of every weight matrix; std 0 = ones (norms)."""
    d, h, kv, hd, ff, v, n = (m[x] for x in ("d", "h", "kv", "hd", "ff", "v",
                                            "layers"))
    return {
        ("embed", "table"): ((v, d), EMBED_STD),
        ("final_norm", "scale"): ((d,), 0.0),
        ("layers", "attn", "wq"): ((n, d, h, hd), 1 / math.sqrt(d)),
        ("layers", "attn", "wk"): ((n, d, kv, hd), 1 / math.sqrt(d)),
        ("layers", "attn", "wv"): ((n, d, kv, hd), 1 / math.sqrt(d)),
        ("layers", "attn", "wo"): ((n, h, hd, d), 1 / math.sqrt(h * hd)),
        ("layers", "mlp", "w_gate"): ((n, d, ff), 1 / math.sqrt(d)),
        ("layers", "mlp", "w_up"): ((n, d, ff), 1 / math.sqrt(d)),
        ("layers", "mlp", "w_down"): ((n, ff, d), 1 / math.sqrt(ff)),
        ("layers", "ln1", "scale"): ((n, d), 0.0),
        ("layers", "ln2", "scale"): ((n, d), 0.0),
    }


def param_count(cfg: dict) -> int:
    """Parameters held (norm scales included)."""
    return sum(math.prod(s) for s, _ in _leaves(dims(cfg)).values())


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _make_weights(key, *, m, dtype):
    tree: dict = {}
    for i, (path, (shape, std)) in enumerate(_leaves(dict(m)).items()):
        if std:
            x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            x = (x * std).astype(dtype)
        else:
            x = jnp.ones(shape, jnp.float32)
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = x
    return tree


def make_weights(cfg: dict, seed_word: int):
    """All weights from one 32-bit seed word, on the device, in one call."""
    return _make_weights(jax.random.key(seed_word),
                         m=tuple(sorted(dims(cfg).items())),
                         dtype=cfg["torch_dtype"])


# ---------------------------------------------------------------------------
# reference forward
# ---------------------------------------------------------------------------


def _quant(x, axes):
    """Symmetric int8 round trip, one scale per slice over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, cos, sin):
    """x: (n, L, heads, hd); rotate the two halves of each head."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "int8", "kv"))
def _forward(params, tokens, *, m, int8, kv=False):
    """Logits ``(n, L, vocab)`` of every position of ``tokens (n, L)``, or
    with ``kv`` every layer's rotated keys and values ``(layers, n, L,
    kv_heads, head_dim)``."""
    m = dict(m)
    f32 = jnp.float32
    act = (lambda a: _quant(a, (-1,))) if int8 else (lambda a: a)
    w_in = (lambda w, axes: _quant(w, axes)) if int8 else (lambda w, axes: w)

    table = params["embed"]["table"].astype(f32)
    table = w_in(table, (1,))
    n, length = tokens.shape
    hd = m["hd"]
    inv = 1.0 / (m["theta"] ** (jnp.arange(0, hd, 2, dtype=f32) / hd))
    ang = jnp.outer(jnp.arange(length, dtype=f32), inv)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    causal = jnp.tril(jnp.ones((length, length), bool))
    g = m["h"] // m["kv"]

    def layer(x, lp):
        a, mlp = lp["attn"], lp["mlp"]
        h = act(_rms(x, lp["ln1"]["scale"], m["eps"]))
        q = jnp.einsum("nld,dhk->nlhk", h, w_in(a["wq"].astype(f32), (0,)))
        k = jnp.einsum("nld,dhk->nlhk", h, w_in(a["wk"].astype(f32), (0,)))
        v = jnp.einsum("nld,dhk->nlhk", h, w_in(a["wv"].astype(f32), (0,)))
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        cached = (k, v) if kv else None
        q = q.reshape(n, length, m["kv"], g, hd) / math.sqrt(hd)
        s = jnp.einsum("nqcgd,nkcd->ncgqk", q, k)
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("ncgqk,nkcd->nqcgd", p, v).reshape(n, length, m["h"], hd)
        wo = w_in(a["wo"].astype(f32), (0, 1))
        x = x + jnp.einsum("nlhk,hkd->nld", act(o), wo)
        h = act(_rms(x, lp["ln2"]["scale"], m["eps"]))
        gate = jnp.einsum("nld,df->nlf", h, w_in(mlp["w_gate"].astype(f32), (0,)))
        up = jnp.einsum("nld,df->nlf", h, w_in(mlp["w_up"].astype(f32), (0,)))
        down = w_in(mlp["w_down"].astype(f32), (0,))
        x = x + jnp.einsum("nlf,fd->nld", act(jax.nn.silu(gate) * up), down)
        return x, cached

    with jax.default_matmul_precision("highest"):
        x = table[tokens]
        x, cached = jax.lax.scan(layer, x, params["layers"])
        if kv:
            return cached
        x = act(_rms(x, params["final_norm"]["scale"], m["eps"]))
        return jnp.einsum("nld,vd->nlv", x, table)


def reference_logits(cfg: dict, params, tokens, precision: str = "float32"):
    """Float32 logits ``(n, L, vocab)``, on the device, of every position of
    ``tokens (n, L)``; ``precision="int8"`` gives the control.  Callers
    keep ``n`` small (a block of sequences) and fixed, so one program
    serves every block."""
    if precision not in ("float32", "int8"):
        raise ValueError(f"precision must be float32 or int8, got {precision!r}")
    m = tuple(sorted(dims(cfg).items()))
    return _forward(params, jnp.asarray(tokens, jnp.int32), m=m,
                    int8=precision == "int8")


def reference_kv(cfg: dict, params, tokens, precision: str = "float32"):
    """Every layer's rotated keys and values ``(layers, n, L, kv_heads,
    head_dim)`` in float32, on the device, for ``tokens (n, L)``: what a
    KV cache holds after those tokens were fed."""
    if precision not in ("float32", "int8"):
        raise ValueError(f"precision must be float32 or int8, got {precision!r}")
    m = tuple(sorted(dims(cfg).items()))
    return _forward(params, jnp.asarray(tokens, jnp.int32), m=m,
                    int8=precision == "int8", kv=True)
