"""Decoder-only transformer families: dense, moe, vlm, hybrid.

One implementation covers:
* ``dense``  — llama-style: RMSNorm, RoPE (optionally partial), GQA,
  SwiGLU; optional QKV bias (qwen2/chatglm), optional sliding window.
* ``moe``    — same attention, or latent attention (MLA) when
  ``kv_lora_rank`` is set; FFN replaced by top-k expert routing
  (``repro.models.moe``: softmax with capacity, or the sigmoid-routed
  expert share), optional leading dense layers + shared experts.
* ``vlm``    — dense decoder consuming a projected patch-embedding prefix
  (vision encoder is a stub per the brief).
* ``hybrid`` — Griffin/RecurrentGemma: RG-LRU recurrent blocks with a local
  sliding-window attention block every ``attn_period`` layers; layers are
  scanned in stacked (rec, ..., rec, attn) groups with an unscanned tail.

Uniform-layer families are scanned (``lax.scan`` over stacked params) to
keep HLO size O(1) in depth — essential for the 61-layer 1T-param dry-run.

API (used by launchers, smoke tests and the dry-run):
    init_params(key, cfg)                       -> params
    forward(params, batch, cfg)                 -> (logits, aux_loss)
    loss_fn(params, batch, cfg)                 -> scalar loss
    lm_loss(params, tokens, labels, cfg)        -> (loss, counters)
    init_cache(cfg, batch, cache_len)           -> cache
    decode_step(params, cache, tokens, pos, cfg)-> (logits, cache)
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as MOE

Params = Any


# ---------------------------------------------------------------- params


def _init_attn(key, cfg, dtype):
    if cfg.is_mla:
        return A.init_mla(key, cfg, dtype)
    D = cfg.d_model
    hd = cfg.resolved_head_dim
    H, KVH = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], (D, H * hd), dtype=dtype),
        "wk": L.dense_init(ks[1], (D, KVH * hd), dtype=dtype),
        "wv": L.dense_init(ks[2], (D, KVH * hd), dtype=dtype),
        "wo": L.dense_init(ks[3], (H * hd, D), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KVH * hd,), dtype)
        p["bv"] = jnp.zeros((KVH * hd,), dtype)
    return p


def _init_mlp(key, cfg, dtype, d_ff):
    D = cfg.d_model
    ks = jax.random.split(key, 3)
    return {
        "wi": L.dense_init(ks[0], (D, d_ff), dtype=dtype),
        "wg": L.dense_init(ks[1], (D, d_ff), dtype=dtype),
        "wo": L.dense_init(ks[2], (d_ff, D), dtype=dtype),
    }


def _init_dense_layer(key, cfg, dtype, d_ff=None):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "attn": _init_attn(k1, cfg, dtype),
        "mlp": _init_mlp(k2, cfg, dtype, d_ff or cfg.d_ff),
    }


def _init_moe_layer(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "attn": _init_attn(k1, cfg, dtype),
        "moe": MOE.init_moe(k2, cfg, dtype),
    }


def _init_rglru_block(key, cfg, dtype):
    D = cfg.d_model
    W = cfg.lru_width or D
    ks = jax.random.split(key, 7)
    return {
        "ln1": jnp.zeros((D,), dtype),
        "ln2": jnp.zeros((D,), dtype),
        "rec": {
            "w_x": L.dense_init(ks[0], (D, W), dtype=dtype),
            "w_gate": L.dense_init(ks[1], (D, W), dtype=dtype),
            "conv_w": (jax.random.normal(ks[2], (4, W), jnp.float32) * 0.1).astype(dtype),
            "w_r": L.dense_init(ks[3], (W, W), dtype=dtype),
            "w_i": L.dense_init(ks[4], (W, W), dtype=dtype),
            "lam": jnp.full((W,), 2.0, jnp.float32),  # softplus-param of decay
            "w_out": L.dense_init(ks[5], (W, D), dtype=dtype),
        },
        "mlp": _init_mlp(ks[6], cfg, dtype, cfg.d_ff),
    }


def _stack(keys, fn):
    return jax.vmap(fn)(keys)


def init_params(key, cfg) -> Params:
    dtype = L.dtype_of(cfg)
    ks = jax.random.split(key, 8)
    params: dict = {
        "embed": L.embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dtype=dtype)

    if cfg.family in ("dense", "vlm"):
        lk = jax.random.split(ks[2], cfg.n_layers)
        params["layers"] = _stack(lk, lambda k: _init_dense_layer(k, cfg, dtype))
    elif cfg.family == "moe":
        nd = cfg.first_dense_layers
        if nd:
            dk = jax.random.split(ks[3], nd)
            params["dense_layers"] = _stack(
                dk, lambda k: _init_dense_layer(k, cfg, dtype, cfg.dense_d_ff)
            )
        mk = jax.random.split(ks[4], cfg.n_layers - nd)
        params["layers"] = _stack(mk, lambda k: _init_moe_layer(k, cfg, dtype))
    elif cfg.family == "hybrid":
        # (p-1) recurrent blocks + 1 local-attention block per group; the
        # groups are stacked and scanned (compile-time O(1) in depth), with
        # a short unscanned tail of recurrent blocks for the remainder.
        p = cfg.attn_period
        G, tail_n = cfg.n_layers // p, cfg.n_layers % p

        def group(k):
            gk = jax.random.split(k, p)
            g = {f"rec{i}": _init_rglru_block(gk[i], cfg, dtype) for i in range(p - 1)}
            g["attn"] = _init_dense_layer(gk[p - 1], cfg, dtype)
            return g

        params["groups"] = _stack(jax.random.split(ks[5], G), group)
        tk = jax.random.split(ks[7], max(tail_n, 1))
        params["tail"] = [_init_rglru_block(tk[i], cfg, dtype) for i in range(tail_n)]
    else:
        raise ValueError(cfg.family)

    if cfg.family == "vlm":
        params["vision_proj"] = L.dense_init(ks[6], (cfg.vision_dim, cfg.d_model), dtype=dtype)
    return params


def _is_attn_layer(i: int, cfg) -> bool:
    return cfg.attn_period > 0 and (i % cfg.attn_period) == (cfg.attn_period - 1)


# ---------------------------------------------------------------- forward


def _project_qkv(x, p, cfg, positions):
    B, S, D = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    q = L.apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_fraction, cfg.rope_theta)
    return q, k, v


def _attn_block(x, p, cfg, positions, window):
    with jax.named_scope("lm_attn"):
        h = L.rmsnorm(x, p["ln1"], cfg.rms_eps)
        if cfg.is_mla:
            q = A.mla_query(h, p["attn"], cfg, positions)
            k, v = A.mla_expand(*A.mla_latent(h, p["attn"], cfg, positions),
                                p["attn"], cfg)
        else:
            q, k, v = _project_qkv(h, p["attn"], cfg, positions)
        o = A.attend(q, k, v, causal=True, window=window, impl=cfg.attn_impl)
        o = jnp.einsum("bsh,he->bse", o.reshape(o.shape[0], o.shape[1], -1), p["attn"]["wo"])
        return x + o.astype(x.dtype)


def _mlp_block(x, p, cfg):
    with jax.named_scope("lm_ffn"):
        h = L.rmsnorm(x, p["ln2"], cfg.rms_eps)
        return x + L.swiglu(h, p["mlp"]["wi"], p["mlp"]["wg"], p["mlp"]["wo"])


NO_COUNTERS = {"moe_local_tokens": 0, "moe_dropped_tokens": 0}


def _moe_block(x, p, cfg):
    """Returns ``(x + moe(x), aux_loss, counters)``; the sigmoid-routed
    share has no aux loss, the softmax layers count nothing."""
    h = L.rmsnorm(x, p["ln2"], cfg.rms_eps)
    counters = {k: jnp.int32(v) for k, v in NO_COUNTERS.items()}
    aux = jnp.float32(0.0)
    if cfg.router_score == "sigmoid":
        bias = jax.lax.stop_gradient(p["moe"]["router_bias"])
        out, counters = MOE.routed_ffn(h, p["moe"], bias, cfg)
    elif cfg.moe_impl == "expert_parallel":
        out, aux = MOE.moe_ffn_shardmap(h, p["moe"], cfg)
    else:
        out, aux = MOE.moe_ffn(h, p["moe"], cfg)
    return x + out, aux, counters


def _rglru_scan(xg, rec, h0=None):
    """RG-LRU over a sequence. xg: (B, S, W) post-conv activations.

    Returns (y (B,S,W), h_last (B,W)). Associative-scan formulation:
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t).
    """
    r = jax.nn.sigmoid(jnp.einsum("bsw,wv->bsv", xg, rec["w_r"]).astype(jnp.float32))
    i = jax.nn.sigmoid(jnp.einsum("bsw,wv->bsv", xg, rec["w_i"]).astype(jnp.float32))
    log_a = -8.0 * jax.nn.softplus(rec["lam"]) * r  # (B,S,W) f32
    a = jnp.exp(log_a)
    gated = i * xg.astype(jnp.float32)
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * gated
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0)

    def comb(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    aa, y = jax.lax.associative_scan(comb, (a, b), axis=1)
    return y, y[:, -1]


def _causal_conv(x, w):
    """Depthwise causal conv over sequence. x: (B,S,W); w: (K,W)."""
    K = w.shape[0]
    xf = x.astype(jnp.float32)
    pad = jnp.pad(xf, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, j : j + x.shape[1]] * w[j].astype(jnp.float32) for j in range(K))
    return out.astype(x.dtype)


def _rglru_block_fwd(x, p, cfg):
    h = L.rmsnorm(x, p["ln1"])
    rec = p["rec"]
    xb = jnp.einsum("bsd,dw->bsw", h, rec["w_x"])
    gate = jax.nn.gelu(
        jnp.einsum("bsd,dw->bsw", h, rec["w_gate"]).astype(jnp.float32)
    )
    xb = _causal_conv(xb, rec["conv_w"])
    y, _ = _rglru_scan(xb, rec)
    y = (y * gate).astype(x.dtype)
    o = jnp.einsum("bsw,wd->bsd", y, rec["w_out"])
    x = x + o
    return _mlp_block(x, p, cfg)


def _embed_tokens(params, tokens, cfg):
    # Pin the lookup to batch-sharded / feature-replicated: letting sharding
    # propagation push a tensor-sharded layout INTO the gather trips an XLA
    # GSPMD check-crash (PartitionGather / ExpandDeviceGroupsWithIota) at
    # several of our table shapes. The following matmul reshards cheaply.
    x = params["embed"][tokens]
    return L.maybe_shard(x, ("pod", "data"), *([None] * (x.ndim - 1)))


def forward(params: Params, batch: dict, cfg) -> tuple[jax.Array, jax.Array]:
    """Training/prefill forward. Returns (logits f32 (B,S,V), aux_loss)."""
    logits, aux, _ = _forward(params, batch, cfg)
    return logits, aux


def _forward(params: Params, batch: dict, cfg):
    """:func:`forward` with the MoE layers' counters, summed over layers."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = _embed_tokens(params, tokens, cfg)
    prefix = 0
    if cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(x.dtype)
        proj = jnp.einsum("bpv,vd->bpd", patches, params["vision_proj"])
        x = jnp.concatenate([proj, x], axis=1)
        prefix = patches.shape[1]
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
    window = cfg.sliding_window

    aux_total = jnp.float32(0.0)
    counters = {k: jnp.int32(v) for k, v in NO_COUNTERS.items()}
    if cfg.family in ("dense", "vlm"):
        def body(carry, pl):
            h = _attn_block(carry, pl, cfg, positions, window)
            h = _mlp_block(h, pl, cfg)
            return h, None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"], unroll=cfg.scan_unroll)
    elif cfg.family == "moe":
        if cfg.first_dense_layers:
            def dbody(carry, pl):
                h = _attn_block(carry, pl, cfg, positions, window)
                h = _mlp_block(h, pl, cfg)
                return h, None

            x, _ = jax.lax.scan(jax.checkpoint(dbody), x, params["dense_layers"], unroll=cfg.scan_unroll)

        def mbody(carry, pl):
            h, aux, cnt = carry
            h = _attn_block(h, pl, cfg, positions, window)
            h, a, c = _moe_block(h, pl, cfg)
            return (h, aux + a, jax.tree_util.tree_map(jnp.add, cnt, c)), None

        (x, aux_total, counters), _ = jax.lax.scan(
            jax.checkpoint(mbody), (x, aux_total, counters), params["layers"],
            unroll=cfg.scan_unroll)
    elif cfg.family == "hybrid":
        p = cfg.attn_period

        def gbody(carry, gp):
            h = carry
            for i in range(p - 1):
                h = _rglru_block_fwd(h, gp[f"rec{i}"], cfg)
            h = _attn_block(h, gp["attn"], cfg, positions, cfg.local_window)
            h = _mlp_block(h, gp["attn"], cfg)
            return h, None

        x, _ = jax.lax.scan(jax.checkpoint(gbody), x, params["groups"],
                            unroll=cfg.scan_unroll)
        for blk in params["tail"]:
            x = jax.checkpoint(lambda h, b: _rglru_block_fwd(h, b, cfg))(x, blk)
    else:
        raise ValueError(cfg.family)

    with jax.named_scope("lm_head"):
        x = L.rmsnorm(x, params["final_norm"], cfg.rms_eps)
        if prefix:
            x = x[:, prefix:]
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32)
    return logits, aux_total, counters


def _gold_logit(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """sum(where(v == label)) instead of take_along_axis: gathers along a
    tensor-sharded vocab dim hard-crash XLA's SPMD partitioner (PartitionGather
    check failure); the iota-compare reduce partitions cleanly."""
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    mask = vocab_iota == labels[..., None]
    return jnp.sum(jnp.where(mask, logits, 0.0), axis=-1)


def loss_fn(params: Params, batch: dict, cfg) -> jax.Array:
    logits, aux = forward(params, batch, cfg)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    nll = jnp.mean(lse - _gold_logit(logits, labels))
    return nll + cfg.aux_loss_coef * aux


def lm_loss(params: Params, tokens, labels, cfg):
    """Mean next-token cross-entropy of ``(B, S)`` tokens against their
    labels, and the MoE counters: ``(loss, counters)``. The softmax
    layers' aux loss is added as in :func:`loss_fn`."""
    logits, aux, counters = _forward(params, {"tokens": tokens}, cfg)
    with jax.named_scope("lm_head"):
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = jnp.mean(lse - gold)
    return nll + cfg.aux_loss_coef * aux, counters


# ----------------------------------------------------------------- decode


def init_cache(cfg, batch_size: int, cache_len: int, dtype=None) -> dict:
    """KV cache pytree. cache_len == window size for ring (sliding) caches."""
    dtype = dtype or L.dtype_of(cfg)
    hd = cfg.resolved_head_dim
    KVH = cfg.n_kv_heads
    nL = cfg.n_layers
    if cfg.family in ("dense", "vlm", "moe"):
        nd = cfg.first_dense_layers if cfg.family == "moe" else 0
        # MLA caches the latent ("k") and the rotary key ("v") per token.
        k_tail = (cfg.kv_lora_rank,) if cfg.is_mla else (KVH, hd)
        v_tail = (cfg.qk_rope_head_dim,) if cfg.is_mla else (KVH, hd)
        cache = {
            "k": jnp.zeros((nL - nd, batch_size, cache_len) + k_tail, dtype),
            "v": jnp.zeros((nL - nd, batch_size, cache_len) + v_tail, dtype),
        }
        if nd:
            cache["dk"] = jnp.zeros((nd, batch_size, cache_len) + k_tail, dtype)
            cache["dv"] = jnp.zeros((nd, batch_size, cache_len) + v_tail, dtype)
        return cache
    if cfg.family == "hybrid":
        W = cfg.lru_width or cfg.d_model
        p = cfg.attn_period
        G, tail_n = nL // p, nL % p
        wlen = min(cache_len, cfg.local_window)

        def rec_cache(lead=()):
            return {
                "h": jnp.zeros((*lead, batch_size, W), jnp.float32),
                "conv": jnp.zeros((*lead, batch_size, 3, W), dtype),
            }

        groups = {f"rec{i}": rec_cache((G,)) for i in range(p - 1)}
        groups["attn"] = {
            "k": jnp.zeros((G, batch_size, wlen, KVH, hd), dtype),
            "v": jnp.zeros((G, batch_size, wlen, KVH, hd), dtype),
        }
        return {"groups": groups, "tail": [rec_cache() for _ in range(tail_n)]}
    raise ValueError(cfg.family)


def _decode_attn(x, p, cfg, kc, vc, pos, ring: bool):
    """One-token attention for a single layer. x: (B,1,D)."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    h = L.rmsnorm(x, p["ln1"], cfg.rms_eps)
    if cfg.is_mla:
        posb = jnp.full((1, 1), pos, jnp.int32)
        q = A.mla_query(h, p["attn"], cfg, posb)
        c_kv, k_rope = A.mla_latent(h, p["attn"], cfg, posb)
        update = A.update_cache_ring if ring else A.update_cache_full
        kc, vc = update(kc, vc, c_kv, k_rope, pos)
        k, v = A.mla_expand(kc, vc, p["attn"], cfg)
        attend = A.decode_attend_ring if ring else A.decode_attend_full
        o = attend(q, k, v, pos)
        o = jnp.einsum("bth,he->bte", o.reshape(B, 1, -1), p["attn"]["wo"])
        return x + o.astype(x.dtype), kc, vc
    q = jnp.einsum("btd,dh->bth", h, p["attn"]["wq"])
    k = jnp.einsum("btd,dh->bth", h, p["attn"]["wk"])
    v = jnp.einsum("btd,dh->bth", h, p["attn"]["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["attn"]["bq"], k + p["attn"]["bk"], v + p["attn"]["bv"]
    q = q.reshape(B, 1, cfg.n_heads, hd)
    k = k.reshape(B, 1, cfg.n_kv_heads, hd)
    v = v.reshape(B, 1, cfg.n_kv_heads, hd)
    posb = jnp.full((1, 1), pos, jnp.int32)
    q = L.apply_rope(q, posb, cfg.rope_fraction, cfg.rope_theta)
    k = L.apply_rope(k, posb, cfg.rope_fraction, cfg.rope_theta)
    if ring:
        kc, vc = A.update_cache_ring(kc, vc, k, v, pos)
        o = A.decode_attend_ring(q, kc, vc, pos)
    else:
        kc, vc = A.update_cache_full(kc, vc, k, v, pos)
        o = A.decode_attend_full(q, kc, vc, pos)
    o = jnp.einsum("bth,he->bte", o.reshape(B, 1, -1), p["attn"]["wo"])
    return x + o.astype(x.dtype), kc, vc


def decode_step(params, cache, tokens, pos, cfg, *, ring: bool = False):
    """One decode step. tokens: (B, 1) int32; pos: () int32.

    ``ring=True`` uses sliding-window ring caches (long_500k path).
    Returns (logits (B, 1, V) f32, new cache).
    """
    x = _embed_tokens(params, tokens, cfg)

    if cfg.family in ("dense", "vlm", "moe"):
        if cfg.family == "moe" and cfg.first_dense_layers:
            def dbody(carry, inp):
                h = carry
                pl, kc, vc = inp
                h, kc, vc = _decode_attn(h, pl, cfg, kc, vc, pos, ring)
                h = _mlp_block(h, pl, cfg)
                return h, (kc, vc)

            x, (dk, dv) = jax.lax.scan(
                dbody, x, (params["dense_layers"], cache["dk"], cache["dv"]),
                unroll=cfg.scan_unroll,
            )
            cache = dict(cache, dk=dk, dv=dv)

        def body(carry, inp):
            h = carry
            pl, kc, vc = inp
            h, kc, vc = _decode_attn(h, pl, cfg, kc, vc, pos, ring)
            if cfg.family == "moe":
                h, _, _ = _moe_block(h, pl, cfg)
            else:
                h = _mlp_block(h, pl, cfg)
            return h, (kc, vc)

        x, (k_new, v_new) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]), unroll=cfg.scan_unroll)
        cache = dict(cache, k=k_new, v=v_new)
    elif cfg.family == "hybrid":
        p = cfg.attn_period

        def gbody(carry, inp):
            h = carry
            gp, gc = inp
            new_c = {}
            for i in range(p - 1):
                h, rc = _rglru_decode(h, gp[f"rec{i}"], cfg, gc[f"rec{i}"])
                new_c[f"rec{i}"] = rc
            h, kc, vc = _decode_attn(h, gp["attn"], cfg,
                                     gc["attn"]["k"], gc["attn"]["v"], pos, True)
            h = _mlp_block(h, gp["attn"], cfg)
            new_c["attn"] = {"k": kc, "v": vc}
            return h, new_c

        x, new_groups = jax.lax.scan(
            gbody, x, (params["groups"], cache["groups"]), unroll=cfg.scan_unroll)
        new_tail = []
        for blk, c in zip(params["tail"], cache["tail"]):
            x, rc = _rglru_decode(x, blk, cfg, c)
            new_tail.append(rc)
        cache = {"groups": new_groups, "tail": new_tail}
    else:
        raise ValueError(cfg.family)

    x = L.rmsnorm(x, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("btd,dv->btv", x, head).astype(jnp.float32)
    return logits, cache


def _rglru_decode(x, p, cfg, c):
    """Single-step RG-LRU. x: (B,1,D); cache {h (B,W) f32, conv (B,3,W)}."""
    rec = p["rec"]
    h = L.rmsnorm(x, p["ln1"])
    xb = jnp.einsum("btd,dw->btw", h, rec["w_x"])[:, 0]  # (B,W)
    gate = jax.nn.gelu(
        jnp.einsum("btd,dw->btw", h, rec["w_gate"]).astype(jnp.float32)
    )[:, 0]
    # causal conv with kernel 4: state holds previous 3 inputs
    win = jnp.concatenate([c["conv"], xb[:, None]], axis=1)  # (B,4,W)
    w = rec["conv_w"].astype(jnp.float32)
    xc = jnp.sum(win.astype(jnp.float32) * w[None], axis=1).astype(x.dtype)
    r = jax.nn.sigmoid((xc @ rec["w_r"]).astype(jnp.float32))
    i = jax.nn.sigmoid((xc @ rec["w_i"]).astype(jnp.float32))
    a = jnp.exp(-8.0 * jax.nn.softplus(rec["lam"]) * r)
    hnew = a * c["h"] + jnp.sqrt(jnp.maximum(1 - a * a, 1e-12)) * (i * xc.astype(jnp.float32))
    y = (hnew * gate).astype(x.dtype)
    o = jnp.einsum("bw,wd->bd", y, rec["w_out"])[:, None]
    x = x + o
    x = _mlp_block(x, p, cfg)
    return x, {"h": hnew, "conv": win[:, 1:]}
