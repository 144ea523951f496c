"""Attention: GQA/MHA and latent (MLA) projections, training and cached decode.

* ``attend_train``: full causal, sliding-window causal, or non-causal
  (whisper encoder / cross attention) over (B, S, H, hd) projections.
* ``decode_attend``: one-token decode against a KV cache. Full-attention
  caches are (B, S_max, KVH, hd) with positions < ``pos`` valid.
  Sliding-window caches are ring buffers (B, W, KVH, hd) indexed ``pos % W``
  — this is what makes ``long_500k`` (524288-token context) feasible: the
  live cache is O(window), not O(context).

* ``init_mla`` / ``mla_query`` / ``mla_latent`` / ``mla_expand``: latent
  attention (DeepSeek-V2/V3, arXiv:2412.19437): the keys and values of every
  head come up from one normalised latent ``c_kv`` per token, and one
  rotary key part is shared by all heads. A decode cache holds the latent
  and the rotary key, not the per-head keys and values.

Softmax is computed in f32; logits scaled by 1/sqrt(hd).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import layers as L

NEG_INF = -1e30


def _scores(q, k):  # q (B,Sq,H,hd) k (B,Sk,KVH,hd) -> (B,H,Sq,Sk)
    B, Sq, H, hd = q.shape
    KVH = k.shape[2]
    rep = H // KVH
    qg = q.reshape(B, Sq, KVH, rep, hd)
    s = jnp.einsum("bqgrh,bkgh->bgrqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    return s.reshape(B, KVH * rep, Sq, k.shape[1]) / math.sqrt(hd)


def _combine(p, v, H):  # p (B,H,Sq,Sk), v (B,Sk,KVH,hd) -> (B,Sq,H,hd)
    B, _, Sq, Sk = p.shape
    KVH = v.shape[2]
    rep = H // KVH
    pg = p.reshape(B, KVH, rep, Sq, Sk)
    o = jnp.einsum("bgrqk,bkgh->bqgrh", pg, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, v.shape[-1])


def attend_train(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
) -> jax.Array:
    """Full-materialized attention. window>0 adds a sliding-window mask."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    s = _scores(q, k)
    if causal or window:
        qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)
        kpos = jnp.arange(Sk)[None, :]
        mask = jnp.ones((Sq, Sk), bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _combine(p, v, H).astype(q.dtype)


def attend_train_blockwise(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 512,
    block_kv: int = 1024,
) -> jax.Array:
    """Flash-style blockwise attention with online softmax (pure JAX).

    Never materializes the (Sq, Sk) score matrix: peak live set per layer is
    O(block_q x block_kv) scores + O(Sq x hd) accumulators. This is the
    XLA-level equivalent of flash attention (MaxText-style) and is the
    memory-term hillclimb lever for the roofline (Sec. Perf). FLOPs match
    full attention (masked blocks are still computed — acceptable at S=4k,
    and XLA cannot skip data-dependent blocks inside scan anyway).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    KVH = k.shape[2]
    dv = v.shape[-1]
    rep = H // KVH
    assert Sq % block_q == 0 and Sk % block_kv == 0, (Sq, Sk, block_q, block_kv)
    nq, nk = Sq // block_q, Sk // block_kv
    scale = 1.0 / math.sqrt(hd)

    qb = q.reshape(B, nq, block_q, KVH, rep, hd)
    kb = k.reshape(B, nk, block_kv, KVH, hd)
    vb = v.reshape(B, nk, block_kv, KVH, hd)
    offs = Sk - Sq  # query positions offset (prefill: 0)

    def q_block(qi, i):
        # qi: (B, block_q, KVH, rep, hd); i: () block index
        qpos = i * block_q + jnp.arange(block_q)[:, None] + offs
        m0 = jnp.full((B, KVH, rep, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, rep, block_q), jnp.float32)
        a0 = jnp.zeros((B, KVH, rep, block_q, dv), jnp.float32)

        def kv_step(carry, inp):
            m, l, acc = carry
            kj, vj, j = inp
            kpos = j * block_kv + jnp.arange(block_kv)[None, :]
            s = jnp.einsum(
                "bqgrh,bkgh->bgrqk", qi.astype(jnp.float32), kj.astype(jnp.float32)
            ) * scale  # (B,KVH,rep,bq,bk)
            mask = jnp.ones((block_q, block_kv), bool)
            if causal:
                mask &= kpos <= qpos
            if window:
                mask &= kpos > qpos - window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bgrqk,bkgh->bgrqh", p, vj.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0),
             jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-30)  # (B,KVH,rep,bq,hd)
        return jnp.transpose(out, (0, 3, 1, 2, 4))  # (B,bq,KVH,rep,hd)

    ob = jax.vmap(q_block, in_axes=(1, 0), out_axes=1)(
        qb, jnp.arange(nq))  # (B,nq,bq,KVH,rep,hd)
    return ob.reshape(B, Sq, H, dv).astype(q.dtype)


def _pick_block(seq: int, target: int) -> int:
    """Largest power-of-two-ish divisor of ``seq`` not above ``target``."""
    for b in (target, target // 2, target // 4, target // 8, 64, 32):
        if b and seq % b == 0:
            return b
    return 0


def attend(q, k, v, *, causal=True, window=0, impl="naive",
           block_q=512, block_kv=1024):
    if impl == "per_sequence":
        # One sequence's (H, S, S) scores at a time, recomputed in the
        # backward pass: what a long-sequence gradient can hold.
        one = jax.checkpoint(lambda a: attend_train(
            a[0][None], a[1][None], a[2][None], causal=causal,
            window=window)[0])
        return jax.lax.map(one, (q, k, v))
    if impl == "blockwise":
        bq = _pick_block(q.shape[1], block_q)
        bk = _pick_block(k.shape[1], block_kv)
        if bq and bk:
            return attend_train_blockwise(q, k, v, causal=causal, window=window,
                                          block_q=bq, block_kv=bk)
    return attend_train(q, k, v, causal=causal, window=window)


def decode_attend_full(
    q: jax.Array,  # (B, 1, H, hd)
    k_cache: jax.Array,  # (B, S_max, KVH, hd)
    v_cache: jax.Array,
    pos: jax.Array,  # () int32 -- current position (0-based)
) -> jax.Array:
    s = _scores(q, k_cache)  # (B,H,1,S_max)
    valid = jnp.arange(k_cache.shape[1])[None, None, None, :] <= pos
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _combine(p, v_cache, q.shape[2]).astype(q.dtype)


def decode_attend_ring(
    q: jax.Array,  # (B, 1, H, hd)
    k_ring: jax.Array,  # (B, W, KVH, hd) ring buffer
    v_ring: jax.Array,
    pos: jax.Array,  # () int32
) -> jax.Array:
    """Sliding-window decode: slots with ring_pos > pos - W are live."""
    W = k_ring.shape[1]
    s = _scores(q, k_ring)  # (B,H,1,W)
    slot = jnp.arange(W)
    # absolute position currently stored in each slot
    cycle = (pos // W) * W
    abs_pos = jnp.where(slot <= (pos % W), cycle + slot, cycle - W + slot)
    valid = (abs_pos >= 0) & (abs_pos >= pos - W + 1) & (abs_pos <= pos)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _combine(p, v_ring, q.shape[2]).astype(q.dtype)


def update_cache_full(k_cache, v_cache, k_new, v_new, pos):
    """Insert one token's K/V at ``pos``. k_new: (B, 1, KVH, hd)."""
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), pos, axis=1)
    return k_cache, v_cache


def update_cache_ring(k_ring, v_ring, k_new, v_new, pos):
    W = k_ring.shape[1]
    slot = pos % W
    k_ring = jax.lax.dynamic_update_slice_in_dim(k_ring, k_new.astype(k_ring.dtype), slot, axis=1)
    v_ring = jax.lax.dynamic_update_slice_in_dim(v_ring, v_new.astype(v_ring.dtype), slot, axis=1)
    return k_ring, v_ring


# ------------------------------------------------------ latent attention


def init_mla(key, cfg, dtype):
    """MLA projections: the query (straight, or through a normalised
    ``q_lora_rank`` latent), the joint latent-and-rotary-key down
    projection, the latent's RMSNorm, its up projection to every head's
    non-rotary key and value, and the output."""
    D, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    ks = jax.random.split(key, 5)
    p = {}
    if cfg.q_lora_rank:
        p["wq_a"] = L.dense_init(ks[0], (D, cfg.q_lora_rank), dtype=dtype)
        p["q_norm"] = jnp.zeros((cfg.q_lora_rank,), dtype)
        p["wq_b"] = L.dense_init(ks[1], (cfg.q_lora_rank, H * (dn + dr)),
                                 dtype=dtype)
    else:
        p["wq"] = L.dense_init(ks[0], (D, H * (dn + dr)), dtype=dtype)
    p["wkv_a"] = L.dense_init(ks[2], (D, r + dr), dtype=dtype)
    p["kv_norm"] = jnp.zeros((r,), dtype)
    p["wkv_b"] = L.dense_init(ks[3], (r, H * (dn + dv)), dtype=dtype)
    p["wo"] = L.dense_init(ks[4], (H * dv, D), dtype=dtype)
    return p


def rope_rotate_half(x, positions, theta: float):
    """Rotary embedding over all of ``x``'s last dim, rotate-half pairing
    (dim ``i`` with ``i + d/2``). x: (..., S, H, d); positions (..., S)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, d/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[..., None, :]
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], axis=-1)
    return (xf * cos + rot * sin).astype(x.dtype)


def mla_query(h, p, cfg, positions):
    """(B, S, D) -> per-head queries (B, S, H, nope + rope), rotary part
    rotated."""
    B, S, _ = h.shape
    dn = cfg.qk_nope_head_dim
    if "wq" in p:
        q = jnp.einsum("bsd,dh->bsh", h, p["wq"])
    else:
        qa = L.rmsnorm(jnp.einsum("bsd,dr->bsr", h, p["wq_a"]), p["q_norm"],
                       cfg.rms_eps)
        q = jnp.einsum("bsr,rh->bsh", qa, p["wq_b"])
    q = q.reshape(B, S, cfg.n_heads, dn + cfg.qk_rope_head_dim)
    q_rope = rope_rotate_half(q[..., dn:], positions, cfg.rope_theta)
    return jnp.concatenate([q[..., :dn], q_rope], axis=-1)


def mla_latent(h, p, cfg, positions):
    """(B, S, D) -> the normalised latent (B, S, r) and the shared rotary
    key (B, S, rope), rotated: what a decode cache holds."""
    r = cfg.kv_lora_rank
    kv_a = jnp.einsum("bsd,dr->bsr", h, p["wkv_a"])
    c_kv = L.rmsnorm(kv_a[..., :r], p["kv_norm"], cfg.rms_eps)
    k_rope = rope_rotate_half(kv_a[..., None, r:], positions,
                              cfg.rope_theta)[..., 0, :]
    return c_kv, k_rope


def mla_expand(c_kv, k_rope, p, cfg):
    """Latent and rotary key -> per-head keys (B, S, H, nope + rope) and
    values (B, S, H, v): the latent's up projection, the rotary key shared
    by every head."""
    B, S, _ = c_kv.shape
    H, dn = cfg.n_heads, cfg.qk_nope_head_dim
    kv = jnp.einsum("bsr,rh->bsh", c_kv, p["wkv_b"]).reshape(
        B, S, H, dn + cfg.v_head_dim)
    rope = jnp.broadcast_to(k_rope[:, :, None, :],
                            (B, S, H, cfg.qk_rope_head_dim))
    return jnp.concatenate([kv[..., :dn], rope], axis=-1), kv[..., dn:]
