"""Plain float32 reference of the MLA + sigmoid-routed MoE decoder.

The DeepSeek-V3 block (arXiv:2412.19437) as Moonlight-16B-A3B and Kimi K2
use it, written out once more in straightforward ``jax.numpy`` with no
scan, no dispatch, no checkpointing and no cache, for the tests to hold
``repro.models.transformer`` against. It reads the same parameter tree
(``transformer.init_params``) and computes:

* RMSNorm ``x / rms(x) * (1 + w)`` (the repository stores norm weights as
  offsets from 1);
* latent attention with explicit per-head keys and values: ``q = W_q h``
  (or ``W_qb norm(W_qa h)``); ``[c_kv, k_rope] = W_kva h``, ``c_kv <-
  norm(c_kv)``; head ``i``'s ``[k_nope_i, v_i] = W_kvb,i c_kv``; RoPE
  (rotate-half) on ``q_rope`` and on the one ``k_rope`` all heads share;
  causal softmax of ``q . k / sqrt(nope + rope)``; ``W_o``;
* the sigmoid router: scores ``s = sigmoid(W_r h)`` over all experts, the
  top-k of ``s + b`` selected, weights ``g = s / sum_sel s * scale``;
* every token through every held expert, masked by its selection weight,
  plus the shared experts: the dropless layer;
* pre-norm residual blocks, a final norm, the untied head, next-token
  cross-entropy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def rope(x, pos, theta):
    """Rotate-half RoPE over ``x``'s last dim; ``pos`` (S,) broadcasts over
    ``x``'s second-to-last axis but one: x (..., S, d) or (..., S, H, d)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    if x.ndim == 4:
        cos, sin = cos[:, None], sin[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def mla(h, p, cfg):
    """(B, S, D) -> (B, S, D): latent attention, head by head."""
    B, S, _ = h.shape
    H, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dv, r = cfg.v_head_dim, cfg.kv_lora_rank
    pos = jnp.arange(S)
    if "wq" in p:
        q = _mm(h, p["wq"])
    else:
        q = _mm(rmsnorm(_mm(h, p["wq_a"]), p["q_norm"], cfg.rms_eps), p["wq_b"])
    q = q.reshape(B, S, H, dn + dr)
    kv_a = _mm(h, p["wkv_a"])
    c_kv = rmsnorm(kv_a[..., :r], p["kv_norm"], cfg.rms_eps)
    k_rope = rope(kv_a[..., r:], pos, cfg.rope_theta)  # (B, S, dr)
    w_kvb = p["wkv_b"].reshape(r, H, dn + dv)
    mask = jnp.tril(jnp.ones((S, S), bool))
    heads = []
    for i in range(H):
        k_nope = _mm(c_kv, w_kvb[:, i, :dn])
        v = _mm(c_kv, w_kvb[:, i, dn:])
        qi = jnp.concatenate(
            [q[:, :, i, :dn], rope(q[:, :, i, dn:], pos, cfg.rope_theta)], -1)
        ki = jnp.concatenate([k_nope, k_rope], -1)
        s = jnp.einsum("bqd,bkd->bqk", qi, ki, precision=HIGHEST)
        s = jnp.where(mask, s / jnp.sqrt(jnp.float32(dn + dr)), -jnp.inf)
        heads.append(jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v,
                                precision=HIGHEST))
    return _mm(jnp.concatenate(heads, -1), p["wo"])


def swiglu(x, wi, wg, wo):
    return _mm(jax.nn.silu(_mm(x, wg)) * _mm(x, wi), wo)


def route(h2, router, bias, cfg):
    """(T, D) -> the selected experts (T, K) and their weights (T, K)."""
    s = jax.nn.sigmoid(_mm(h2, router))
    _, sel = jax.lax.top_k(s + bias, cfg.top_k)
    g = jnp.take_along_axis(s, sel, -1)
    if cfg.norm_topk_prob:
        g = g / jnp.sum(g, -1, keepdims=True)
    return sel, g * cfg.routed_scale


def moe(h, p, bias, cfg):
    """(B, S, D) -> the held experts' part plus the shared experts."""
    shape = h.shape
    h2 = h.reshape(-1, shape[-1])
    sel, g = route(h2, p["router"], bias, cfg)
    out = jnp.zeros_like(h2)
    for e in range(cfg.held_experts):
        ge = jnp.sum(jnp.where(sel == cfg.expert_offset + e, g, 0.0), -1)
        out = out + ge[:, None] * swiglu(h2, p["wi"][e], p["wg"][e], p["wo"][e])
    if cfg.n_shared_experts:
        s = p["shared"]
        out = out + swiglu(h2, s["wi"], s["wg"], s["wo"])
    return out.reshape(shape)


def _layer(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def logits(params, tokens, cfg):
    """(B, S) tokens -> (B, S, V) logits."""
    x = params["embed"][tokens]
    eps = cfg.rms_eps
    for i in range(cfg.first_dense_layers):
        p = _layer(params["dense_layers"], i)
        x = x + mla(rmsnorm(x, p["ln1"], eps), p["attn"], cfg)
        m = p["mlp"]
        x = x + swiglu(rmsnorm(x, p["ln2"], eps), m["wi"], m["wg"], m["wo"])
    for i in range(cfg.n_layers - cfg.first_dense_layers):
        p = _layer(params["layers"], i)
        x = x + mla(rmsnorm(x, p["ln1"], eps), p["attn"], cfg)
        x = x + moe(rmsnorm(x, p["ln2"], eps), p["moe"],
                    p["moe"]["router_bias"], cfg)
    return _mm(rmsnorm(x, params["final_norm"], eps), params["lm_head"])


def loss(params, tokens, labels, cfg):
    """Mean next-token cross-entropy."""
    lg = logits(params, tokens, cfg)
    logp = jax.nn.log_softmax(lg, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
