"""Model assembly for all assigned architecture families.

One functional model: ``init_params`` builds (params, partition-specs),
``train_loss`` / ``prefill`` / ``decode_step`` run it.  Layers are stacked
and driven by ``lax.scan`` (compile time O(1) in depth — required for the
95-layer dry-run cells), with ``jax.checkpoint`` remat on the scan body.

Families:
  dense  — pre-norm GQA transformer (qk-norm / qkv-bias / gelu variants)
  moe    — dense attention + stable-sort-dispatch MoE FFN (+ shared experts,
           optional first-k dense layers, MLA attention for deepseek-v3)
  ssm    — Mamba2 (SSD) stack, attention-free
  hybrid — Mamba2 stack + one *shared* attention block every k layers
  vlm/audio — dense backbone; frontend embeddings are injected over the
           token embeddings for the first ``frontend_tokens`` positions
           (the modality encoder itself is a stub per the assignment).

The vocab dimension is never materialised over the full sequence: the loss
is computed in sequence chunks inside a scan (``_chunked_ce``).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def _stack_init(fn, key, n, *args, **kwargs):
    """vmap an init over n layers -> stacked params + specs with leading dim."""
    keys = jax.random.split(key, n)
    sample = fn(keys[0], *args, **kwargs)
    params0, specs = sample[0], sample[1]
    stacked = jax.vmap(lambda k: fn(k, *args, **kwargs)[0])(keys)
    specs = jax.tree.map(
        lambda s: P(*((None,) + tuple(s))), specs,
        is_leaf=lambda s: isinstance(s, P),
    )
    rest = sample[2:] if len(sample) > 2 else ()
    return (stacked, specs, *rest)


def _dense_layer_init(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    hd = cfg.resolved_head_dim
    if cfg.mla:
        ap, asp = mla_mod.init_mla(
            k1, cfg.d_model, cfg.n_heads,
            q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim,
        )
    else:
        ap, asp = attn_mod.init_gqa(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd,
            qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
        )
    mp, msp = L.init_mlp(k2, cfg.d_model, cfg.d_ff, kind=cfg.mlp_kind)
    n1p, n1s = L.init_rmsnorm(cfg.d_model)
    n2p, n2s = L.init_rmsnorm(cfg.d_model)
    p = {"attn": ap, "mlp": mp, "ln1": n1p, "ln2": n2p}
    s = {"attn": asp, "mlp": msp, "ln1": n1s, "ln2": n2s}
    return p, s


def _moe_layer_init(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    p, s = _dense_layer_init(k1, cfg)
    ff = cfg.moe_ff or cfg.d_ff
    mp, msp = moe_mod.init_moe(
        k2, cfg.d_model, ff, cfg.n_experts,
        n_shared=cfg.n_shared_experts, shared_ff=ff * max(cfg.n_shared_experts, 1),
    )
    p["mlp"], s["mlp"] = mp, msp
    return p, s


def _mamba_layer_init(key, cfg: ModelConfig):
    k1, _ = jax.random.split(key)
    mp, msp, meta = ssm_mod.init_mamba2(
        k1, cfg.d_model, expand=cfg.ssm_expand, headdim=cfg.ssm_headdim,
        d_state=cfg.ssm_state, ngroups=cfg.ssm_ngroups,
    )
    np_, ns = L.init_rmsnorm(cfg.d_model)
    return {"mamba": mp, "ln": np_}, {"mamba": msp, "ln": ns}, meta


def init_params(cfg: ModelConfig, key):
    ks = jax.random.split(key, 8)
    params: dict[str, Any] = {}
    specs: dict[str, Any] = {}

    ep, es = L.init_embedding(ks[0], cfg.vocab, cfg.d_model)
    params["embed"], specs["embed"] = ep, es
    if not cfg.tie_embeddings:
        up, us = L.init_embedding(ks[1], cfg.vocab, cfg.d_model)
        params["unembed"], specs["unembed"] = up, us
    fp, fs = L.init_rmsnorm(cfg.d_model)
    params["final_norm"], specs["final_norm"] = fp, fs

    if cfg.frontend != "none":
        params["frontend_proj"] = L.truncated_normal(
            ks[2], (cfg.d_model, cfg.d_model), 0.02
        )
        specs["frontend_proj"] = P("data", None)

    if cfg.ssm:
        lp, lsp, meta = _stack_init(_mamba_layer_init, ks[3], cfg.n_layers, cfg)
        params["layers"], specs["layers"] = lp, lsp
        if cfg.attn_every:  # hybrid: one shared attention + MLP block
            sp, ss = _dense_layer_init(ks[4], cfg)
            params["shared_attn"], specs["shared_attn"] = sp, ss
        return _cast_params(cfg, params), specs

    if cfg.moe:
        n_moe = cfg.n_layers - cfg.first_k_dense
        if cfg.first_k_dense:
            dp, dsp = _stack_init(
                _dense_layer_init, ks[5], cfg.first_k_dense, cfg
            )
            params["dense_layers"], specs["dense_layers"] = dp, dsp
        lp, lsp = _stack_init(_moe_layer_init, ks[3], n_moe, cfg)
        params["layers"], specs["layers"] = lp, lsp
        return _cast_params(cfg, params), specs

    lp, lsp = _stack_init(_dense_layer_init, ks[3], cfg.n_layers, cfg)
    params["layers"], specs["layers"] = lp, lsp
    return _cast_params(cfg, params), specs


def _cast_params(cfg: ModelConfig, params):
    """Store >=2-D weights in cfg.param_dtype (bf16 for the 671B config);
    norms/biases/scalars stay fp32."""
    dt = jnp.dtype(cfg.param_dtype)
    if dt == jnp.float32:
        return params
    return jax.tree.map(
        lambda p: p.astype(dt) if p.ndim >= 2 and p.dtype == jnp.float32 else p,
        params,
    )


def mamba_meta(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    return dict(
        d_inner=d_inner,
        nheads=d_inner // cfg.ssm_headdim,
        d_state=cfg.ssm_state,
        ngroups=cfg.ssm_ngroups,
        d_conv=4,
        headdim=cfg.ssm_headdim,
        conv_dim=d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state,
    )


# --------------------------------------------------------------------------
# forward (train / prefill)
# --------------------------------------------------------------------------


def _rope_tables(cfg: ModelConfig, max_pos: int):
    if cfg.pos_emb != "rope":
        return None, None
    hd = (
        cfg.qk_rope_head_dim if cfg.mla else cfg.resolved_head_dim
    )
    return L.rope_frequencies(hd, max_pos, cfg.rope_theta)


def _embed_inputs(cfg, params, tokens, frontend_embeds, dtype):
    x = L.embed(params["embed"], tokens, dtype)
    if cfg.frontend != "none" and frontend_embeds is not None:
        fe = jnp.einsum(
            "bfd,de->bfe", frontend_embeds.astype(dtype),
            params["frontend_proj"].astype(dtype),
        )
        f = fe.shape[1]
        x = jnp.concatenate([fe, x[:, f:]], axis=1)
    if cfg.pos_emb == "sinusoidal":
        x = x + L.sinusoidal_positions(x.shape[1], cfg.d_model, dtype)
    return x


def _dense_attn_block(cfg, lp, x, cos, sin, positions):
    h = L.rmsnorm(lp["ln1"], x)
    if cfg.mla:
        dims = dict(
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
        )
        a = mla_mod.mla_attention_train(
            lp["attn"], h, cos, sin, positions, dims,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
            causal_skip=cfg.causal_skip,
        )
    else:
        q, k, v = attn_mod.qkv_project(
            lp["attn"], h, cos, sin, positions, qk_norm=cfg.qk_norm
        )
        if cfg.flash_vjp:
            fa = attn_mod.make_flash_attention_vjp(
                causal=True,
                q_chunk=min(cfg.q_chunk, q.shape[1]),
                kv_chunk=min(cfg.kv_chunk, k.shape[1]),
            )
            o = fa(q, k, v)
        else:
            o = attn_mod.flash_attention(
                q, k, v, causal=True, q_chunk=cfg.q_chunk,
                kv_chunk=cfg.kv_chunk, causal_skip=cfg.causal_skip,
            )
        a = attn_mod.attention_output(lp["attn"], o, x.dtype)
    return x + a


def _ffn_block(cfg, lp, x, *, moe_layer):
    h = L.rmsnorm(lp["ln2"], x)
    if moe_layer:
        ff = moe_mod.moe_apply(
            lp["mlp"], h, n_experts=cfg.n_experts, top_k=cfg.moe_top_k,
            capacity_factor=cfg.capacity_factor, scoring=cfg.router_scoring,
            use_merge_sort=cfg.use_merge_sort_dispatch,
            dispatch_groups=cfg.moe_dispatch_groups,
            dispatch=cfg.moe_dispatch,
        )
    else:
        ff = L.mlp(lp["mlp"], h, kind=cfg.mlp_kind)
    return x + ff


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def hidden_states(cfg: ModelConfig, params, tokens, frontend_embeds=None):
    """Token/frontend inputs -> final hidden states (b, s, d)."""
    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    x = _embed_inputs(cfg, params, tokens, frontend_embeds, dtype)
    x = L.constrain_batch_leading(x)
    cos, sin = _rope_tables(cfg, s)
    positions = jnp.arange(s, dtype=jnp.int32)[None, :]

    if cfg.ssm:
        meta = mamba_meta(cfg)
        shared = params.get("shared_attn")

        def body(carry, inp):
            xx = L.constrain_batch_leading(carry)
            lp, idx = inp
            h = L.rmsnorm(lp["ln"], xx)
            out, _ = ssm_mod.mamba2_forward(
                lp["mamba"], meta, h, chunk=cfg.ssm_chunk
            )
            xx = xx + out
            if cfg.attn_every:
                def with_attn(y):
                    y = _dense_attn_block(cfg, shared, y, cos, sin, positions)
                    return _ffn_block(cfg, shared, y, moe_layer=False)

                xx = lax.cond(
                    (idx + 1) % cfg.attn_every == 0, with_attn,
                    lambda y: y, xx,
                )
            return xx, None

        x, _ = lax.scan(
            _remat(cfg, body), x,
            (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)),
        )
        return L.rmsnorm(params["final_norm"], x)

    def dense_body(carry, lp):
        carry = L.constrain_batch_leading(carry)
        xx = _dense_attn_block(cfg, lp, carry, cos, sin, positions)
        xx = _ffn_block(cfg, lp, xx, moe_layer=False)
        return L.constrain_batch_leading(xx), None

    def moe_body(carry, lp):
        carry = L.constrain_batch_leading(carry)
        xx = _dense_attn_block(cfg, lp, carry, cos, sin, positions)
        xx = _ffn_block(cfg, lp, xx, moe_layer=True)
        return L.constrain_batch_leading(xx), None

    if cfg.moe:
        if cfg.first_k_dense:
            x, _ = lax.scan(_remat(cfg, dense_body), x, params["dense_layers"])
        x, _ = lax.scan(_remat(cfg, moe_body), x, params["layers"])
    else:
        x, _ = lax.scan(_remat(cfg, dense_body), x, params["layers"])
    return L.rmsnorm(params["final_norm"], x)


def _unembed_table(cfg, params):
    return params["embed" if cfg.tie_embeddings else "unembed"]["table"]


def _chunked_ce(cfg, params, hidden, labels, mask, chunk: int = 512):
    """Cross-entropy without materialising (b, s, vocab)."""
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    nc = s // chunk
    table = _unembed_table(cfg, params)
    hr = hidden.reshape(b, nc, chunk, d).swapaxes(0, 1)
    yr = labels.reshape(b, nc, chunk).swapaxes(0, 1)
    mr = mask.reshape(b, nc, chunk).swapaxes(0, 1)

    def step(carry, inp):
        hc, yc, mc = inp
        logits = jnp.einsum(
            "bcd,vd->bcv", hc, table.astype(hc.dtype)
        ).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        loss = jnp.sum((lse - gold) * mc)
        return (carry[0] + loss, carry[1] + jnp.sum(mc)), None

    (total, count), _ = lax.scan(step, (jnp.float32(0), jnp.float32(0)),
                                 (hr, yr, mr))
    return total / jnp.maximum(count, 1.0)


def train_loss(cfg: ModelConfig, params, batch):
    """batch: {'tokens': (b,s), 'labels': (b,s), 'mask': (b,s),
    optional 'frontend_embeds': (b,f,d)}."""
    hidden = hidden_states(
        cfg, params, batch["tokens"], batch.get("frontend_embeds")
    )
    return _chunked_ce(cfg, params, hidden, batch["labels"], batch["mask"])


def prefill_logits(cfg: ModelConfig, params, tokens, frontend_embeds=None):
    """Inference prefill: full forward, next-token logits for the last
    position only (b, vocab) — the (b, s, vocab) tensor never exists."""
    hidden = hidden_states(cfg, params, tokens, frontend_embeds)
    last = hidden[:, -1, :]
    table = _unembed_table(cfg, params)
    return jnp.einsum("bd,vd->bv", last, table.astype(last.dtype)).astype(
        jnp.float32
    )


# --------------------------------------------------------------------------
# serving: cache init + decode step
# --------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class Cache:
    """Per-family decode cache (stacked over layers).

    ``kind`` is static pytree metadata so Cache flows through jit/pjit;
    ``data``/``length`` are the array children.
    """

    def __init__(self, kind: str, data: Any, length):
        self.kind = kind  # 'gqa' | 'mla' | 'ssm' | 'hybrid'
        self.data = data
        self.length = length

    def tree_flatten(self):
        return (self.data, self.length), self.kind

    @classmethod
    def tree_unflatten(cls, kind, children):
        data, length = children
        return cls(kind, data, length)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Cache:
    ll = cfg.n_layers
    if cfg.ssm:
        meta = mamba_meta(cfg)
        conv = jnp.zeros(
            (ll, batch, meta["d_conv"] - 1, meta["conv_dim"]), dtype
        )
        state = jnp.zeros(
            (ll, batch, meta["nheads"], meta["headdim"], meta["d_state"]),
            jnp.float32,
        )
        if cfg.attn_every:
            napp = cfg.n_layers // cfg.attn_every
            hd = cfg.resolved_head_dim
            k = jnp.zeros((napp, batch, max_len, cfg.n_kv_heads, hd), dtype)
            v = jnp.zeros((napp, batch, max_len, cfg.n_kv_heads, hd), dtype)
            return Cache("hybrid", (conv, state, k, v), jnp.int32(0))
        return Cache("ssm", (conv, state), jnp.int32(0))
    if cfg.mla:
        ckv = jnp.zeros((ll, batch, max_len, cfg.kv_lora_rank), dtype)
        kr = jnp.zeros((ll, batch, max_len, cfg.qk_rope_head_dim), dtype)
        return Cache("mla", (ckv, kr), jnp.int32(0))
    hd = cfg.resolved_head_dim
    k = jnp.zeros((ll, batch, max_len, cfg.n_kv_heads, hd), dtype)
    v = jnp.zeros((ll, batch, max_len, cfg.n_kv_heads, hd), dtype)
    return Cache("gqa", (k, v), jnp.int32(0))


def cache_specs(cfg: ModelConfig, batch_axes) -> Cache:
    """PartitionSpecs matching init_cache's structure.

    KV caches are **sequence-sharded** on the model axis (decode-time
    sequence parallelism): the GQA archs here have n_kv=8 < 16-way TP, so
    head sharding cannot use the mesh, while the 32k/500k sequence always
    divides it.  Softmax over the sharded axis becomes a small all-reduce
    of per-shard (max, sum) — the production ring-attention layout.
    """
    ba = batch_axes
    if cfg.ssm:
        conv = P(None, ba, None, "model")
        state = P(None, ba, "model", None, None)
        if cfg.attn_every:
            kv = P(None, ba, "model", None, None)  # seq-sharded
            return Cache("hybrid", (conv, state, kv, kv), P())
        return Cache("ssm", (conv, state), P())
    if cfg.mla:
        ckv = P(None, ba, "model", None)  # seq-sharded compressed latent
        return Cache("mla", (ckv, ckv), P())
    kv = P(None, ba, "model", None, None)  # seq-sharded
    return Cache("gqa", (kv, kv), P())


def decode_step(cfg: ModelConfig, params, cache: Cache, tokens):
    """One token for every sequence.  tokens: (b, 1) -> logits (b, vocab).

    The scan carries the residual stream and threads per-layer cache slices
    as scan xs/ys, so decode is O(1) HLO in depth as well.
    """
    dtype = jnp.dtype(cfg.dtype)
    b = tokens.shape[0]
    pos = cache.length
    x = L.embed(params["embed"], tokens, dtype)
    max_len = _cache_max_len(cfg, cache)
    if cfg.pos_emb == "sinusoidal":
        s_table = L.sinusoidal_positions(max_len + 1, cfg.d_model, dtype)
        x = x + s_table[pos][None, None, :]
    cos, sin = _rope_tables(cfg, max_len + 1)
    positions = jnp.full((b, 1), pos, jnp.int32)

    if cfg.ssm:
        x, new_cache = _decode_ssm(cfg, params, cache, x, cos, sin, positions)
    elif cfg.mla:
        x, new_cache = _decode_mla(cfg, params, cache, x, cos, sin, positions)
    else:
        x, new_cache = _decode_gqa(cfg, params, cache, x, cos, sin, positions)

    h = L.rmsnorm(params["final_norm"], x)
    logits = jnp.einsum(
        "bsd,vd->bsv", h, _unembed_table(cfg, params).astype(dtype)
    )
    return logits[:, 0].astype(jnp.float32), new_cache


def decode_step_ragged(cfg: ModelConfig, params, cache: Cache, tokens,
                       lengths):
    """One token for every *slot* at per-slot positions (continuous
    batching).  tokens: (b, 1); lengths: (b,) int32 per-slot cache
    lengths — token ``b`` is written at position ``lengths[b]`` and
    attends over ``lengths[b] + 1`` cache entries.  Returns
    ``(logits (b, vocab), new_cache)`` with ``new_cache.length ==
    lengths + 1`` for every slot; the serving engine holds back the
    lengths of inactive slots itself (they re-write one masked position
    per step, which the per-slot attention mask never reads as history).

    Only the ``gqa`` cache family carries per-slot positions today
    (dense / MoE / VLM / audio archs); MLA and SSM caches raise — the
    serving launcher keeps those archs on the lock-step batch path.
    """
    if cache.kind != "gqa":
        raise NotImplementedError(
            f"continuous-batching decode supports the 'gqa' cache family; "
            f"got {cache.kind!r} (use the lock-step decode_step path)"
        )
    dtype = jnp.dtype(cfg.dtype)
    b = tokens.shape[0]
    lengths = jnp.asarray(lengths, jnp.int32)
    x = L.embed(params["embed"], tokens, dtype)
    max_len = _cache_max_len(cfg, cache)
    if cfg.pos_emb == "sinusoidal":
        s_table = L.sinusoidal_positions(max_len + 1, cfg.d_model, dtype)
        x = x + s_table[lengths][:, None, :]
    cos, sin = _rope_tables(cfg, max_len + 1)
    positions = lengths[:, None]  # (b, 1) — per-slot rope positions
    x, new_cache = _decode_gqa_ragged(
        cfg, params, cache, x, cos, sin, positions, lengths
    )
    h = L.rmsnorm(params["final_norm"], x)
    logits = jnp.einsum(
        "bsd,vd->bsv", h, _unembed_table(cfg, params).astype(dtype)
    )
    return logits[:, 0].astype(jnp.float32), new_cache


def _decode_gqa_ragged(cfg, params, cache, x, cos, sin, positions, lengths):
    kc, vc = cache.data[0], cache.data[1]
    b = x.shape[0]
    rows = jnp.arange(b, dtype=jnp.int32)

    def make_body(moe_layer):
        def body(xx, inp):
            lp, kl, vl = inp
            with obs.span("repro.attention"):
                h = L.rmsnorm(lp["ln1"], xx)
                q, k, v = attn_mod.qkv_project(
                    lp["attn"], h, cos, sin, positions, qk_norm=cfg.qk_norm
                )
                # per-slot scatter: slot b's token lands at its own position
                kl = kl.at[rows, lengths].set(k[:, 0])
                vl = vl.at[rows, lengths].set(v[:, 0])
                o = attn_mod.decode_attention(q, kl, vl, lengths + 1)
                xx = xx + attn_mod.attention_output(lp["attn"], o, xx.dtype)
            with obs.span("repro.ffn"):
                xx = _ffn_block(cfg, lp, xx, moe_layer=moe_layer)
            return xx, (kl, vl)

        return body

    layers = params["layers"]
    with obs.span("repro.decoder_layers"):
        if cfg.moe and cfg.first_k_dense:
            nd = cfg.first_k_dense
            x, (kd, vd) = lax.scan(
                make_body(False), x,
                (params["dense_layers"], kc[:nd], vc[:nd]),
            )
            x, (km, vm) = lax.scan(
                make_body(cfg.moe), x, (layers, kc[nd:], vc[nd:]))
            k_new = jnp.concatenate([kd, km], axis=0)
            v_new = jnp.concatenate([vd, vm], axis=0)
        else:
            x, (k_new, v_new) = lax.scan(
                make_body(cfg.moe), x, (layers, kc, vc))
    return x, Cache("gqa", (k_new, v_new), lengths + 1)


def _cache_max_len(cfg, cache):
    if cache.kind in ("gqa", "hybrid"):
        return cache.data[-1].shape[2]
    if cache.kind == "mla":
        return cache.data[0].shape[2]
    return 1


def _decode_gqa(cfg, params, cache, x, cos, sin, positions):
    kc, vc, pos = cache.data[0], cache.data[1], cache.length

    def body(xx, inp):
        lp, kl, vl = inp
        h = L.rmsnorm(lp["ln1"], xx)
        q, k, v = attn_mod.qkv_project(
            lp["attn"], h, cos, sin, positions, qk_norm=cfg.qk_norm
        )
        kl = lax.dynamic_update_slice(kl, k, (0, pos, 0, 0))
        vl = lax.dynamic_update_slice(vl, v, (0, pos, 0, 0))
        o = attn_mod.decode_attention(q, kl, vl, pos + 1)
        xx = xx + attn_mod.attention_output(lp["attn"], o, xx.dtype)
        xx = _ffn_block(cfg, lp, xx, moe_layer=cfg.moe)
        return xx, (kl, vl)

    layers = params["layers"]
    if cfg.moe and cfg.first_k_dense:
        nd = cfg.first_k_dense

        def dense_body(xx, inp):
            lp, kl, vl = inp
            h = L.rmsnorm(lp["ln1"], xx)
            q, k, v = attn_mod.qkv_project(
                lp["attn"], h, cos, sin, positions, qk_norm=cfg.qk_norm
            )
            kl = lax.dynamic_update_slice(kl, k, (0, pos, 0, 0))
            vl = lax.dynamic_update_slice(vl, v, (0, pos, 0, 0))
            o = attn_mod.decode_attention(q, kl, vl, pos + 1)
            xx = xx + attn_mod.attention_output(lp["attn"], o, xx.dtype)
            xx = _ffn_block(cfg, lp, xx, moe_layer=False)
            return xx, (kl, vl)

        x, (kd, vd) = lax.scan(
            dense_body, x, (params["dense_layers"], kc[:nd], vc[:nd])
        )
        x, (km, vm) = lax.scan(body, x, (layers, kc[nd:], vc[nd:]))
        k_new = jnp.concatenate([kd, km], axis=0)
        v_new = jnp.concatenate([vd, vm], axis=0)
    else:
        x, (k_new, v_new) = lax.scan(body, x, (layers, kc, vc))
    return x, Cache("gqa", (k_new, v_new), cache.length + 1)


def _decode_mla(cfg, params, cache, x, cos, sin, positions):
    ckv_c, kr_c, pos = cache.data[0], cache.data[1], cache.length
    dims = dict(
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
    )

    def body(xx, inp):
        lp, ckv_l, kr_l = inp
        h = L.rmsnorm(lp["ln1"], xx)
        o, new_ckv, new_kr = mla_mod.mla_attention_decode(
            lp["attn"], h, cos, sin, positions, dims, ckv_l, kr_l, pos + 1
        )
        ckv_l = lax.dynamic_update_slice(
            ckv_l, new_ckv.astype(ckv_l.dtype), (0, pos, 0)
        )
        kr_l = lax.dynamic_update_slice(
            kr_l, new_kr.astype(kr_l.dtype), (0, pos, 0)
        )
        xx = xx + o
        xx = _ffn_block(cfg, lp, xx, moe_layer=cfg.moe)
        return xx, (ckv_l, kr_l)

    # NOTE: cache must be updated BEFORE attention sees position `pos`;
    # mla_attention_decode masks with pos+1 but reads the cache arg, so we
    # update first by computing latents inside — handled by updating the
    # cache here prior to the call in a fused pass below.
    def body_fused(xx, inp):
        lp, ckv_l, kr_l = inp
        h = L.rmsnorm(lp["ln1"], xx)
        q_nope, q_rope, c_kv, k_rope = mla_mod.mla_latents(
            lp["attn"], h, cos, sin, positions, dims
        )
        ckv_l = lax.dynamic_update_slice(
            ckv_l, c_kv.astype(ckv_l.dtype), (0, pos, 0)
        )
        kr_l = lax.dynamic_update_slice(
            kr_l, k_rope.astype(kr_l.dtype), (0, pos, 0)
        )
        o, _, _ = mla_mod.mla_attention_decode(
            lp["attn"], h, cos, sin, positions, dims, ckv_l, kr_l, pos + 1
        )
        xx = xx + o
        xx = _ffn_block(cfg, lp, xx, moe_layer=cfg.moe)
        return xx, (ckv_l, kr_l)

    layers = params["layers"]
    if cfg.moe and cfg.first_k_dense:
        nd = cfg.first_k_dense

        def dense_body(xx, inp):
            lp, ckv_l, kr_l = inp
            h = L.rmsnorm(lp["ln1"], xx)
            q_nope, q_rope, c_kv, k_rope = mla_mod.mla_latents(
                lp["attn"], h, cos, sin, positions, dims
            )
            ckv_l = lax.dynamic_update_slice(
                ckv_l, c_kv.astype(ckv_l.dtype), (0, pos, 0)
            )
            kr_l = lax.dynamic_update_slice(
                kr_l, k_rope.astype(kr_l.dtype), (0, pos, 0)
            )
            o, _, _ = mla_mod.mla_attention_decode(
                lp["attn"], h, cos, sin, positions, dims, ckv_l, kr_l,
                pos + 1,
            )
            xx = xx + o
            xx = _ffn_block(cfg, lp, xx, moe_layer=False)
            return xx, (ckv_l, kr_l)

        x, (c_d, r_d) = lax.scan(
            dense_body, x, (params["dense_layers"], ckv_c[:nd], kr_c[:nd])
        )
        x, (c_m, r_m) = lax.scan(body_fused, x, (layers, ckv_c[nd:], kr_c[nd:]))
        ckv_new = jnp.concatenate([c_d, c_m], axis=0)
        kr_new = jnp.concatenate([r_d, r_m], axis=0)
    else:
        x, (ckv_new, kr_new) = lax.scan(body_fused, x, (layers, ckv_c, kr_c))
    return x, Cache("mla", (ckv_new, kr_new), cache.length + 1)


def _decode_ssm(cfg, params, cache, x, cos, sin, positions):
    meta = mamba_meta(cfg)
    pos = cache.length
    if cfg.attn_every:
        conv_c, st_c, kc, vc = cache.data
    else:
        conv_c, st_c = cache.data
        kc = vc = None
    shared = params.get("shared_attn")

    def body(carry, inp):
        xx, kc_, vc_ = carry
        lp, conv_l, st_l, idx = inp
        h = L.rmsnorm(lp["ln"], xx)
        out, (conv_n, st_n) = ssm_mod.mamba2_forward(
            lp["mamba"], meta, h, chunk=1, state=(conv_l, st_l)
        )
        xx = xx + out
        if cfg.attn_every:
            app = idx // cfg.attn_every

            def with_attn(args):
                y, kc2, vc2 = args
                h2 = L.rmsnorm(shared["ln1"], y)
                q, k, v = attn_mod.qkv_project(
                    shared["attn"], h2, cos, sin, positions,
                    qk_norm=cfg.qk_norm,
                )
                ka = lax.dynamic_update_slice(
                    kc2[app], k, (0, pos, 0, 0)
                )
                va = lax.dynamic_update_slice(
                    vc2[app], v, (0, pos, 0, 0)
                )
                o = attn_mod.decode_attention(q, ka, va, pos + 1)
                y = y + attn_mod.attention_output(shared["attn"], o, y.dtype)
                y = _ffn_block(cfg, shared, y, moe_layer=False)
                kc2 = lax.dynamic_update_slice(
                    kc2, ka[None], (app, 0, 0, 0, 0)
                )
                vc2 = lax.dynamic_update_slice(
                    vc2, va[None], (app, 0, 0, 0, 0)
                )
                return y, kc2, vc2

            xx, kc_, vc_ = lax.cond(
                (idx + 1) % cfg.attn_every == 0,
                with_attn,
                lambda a: a,
                (xx, kc_, vc_),
            )
        return (xx, kc_, vc_), (conv_n, st_n)

    idxs = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    if cfg.attn_every:
        (x, kc, vc), (conv_n, st_n) = lax.scan(
            body, (x, kc, vc), (params["layers"], conv_c, st_c, idxs)
        )
        return x, Cache("hybrid", (conv_n, st_n, kc, vc), cache.length + 1)
    (x, _, _), (conv_n, st_n) = lax.scan(
        body, (x, None, None), (params["layers"], conv_c, st_c, idxs)
    )
    return x, Cache("ssm", (conv_n, st_n), cache.length + 1)
