"""Plain reference of the Moonlight-16B-A3B FedSGD round, and its work.

Nothing here imports the program under test. The model is the DeepSeek-V3
block (arXiv:2412.19437) that Moonlight-16B-A3B uses, in straightforward
``jax.numpy`` with every product at ``highest`` precision, read from the
parameter tree the program initialises (norm weights stored as offsets
from 1):

* latent attention with explicit per-head keys and values: ``q = W_q h``
  split per head into ``q_nope`` and ``q_rope``; ``[c_kv, k_rope] = W_kva
  h`` and ``c_kv <- RMSNorm(c_kv)``; each head's ``[k_nope, v] = W_kvb
  c_kv``; rotate-half RoPE on ``q_rope`` and on the one ``k_rope`` all
  heads share; causal softmax of ``q . k / sqrt(nope + rope)``; ``W_o``;
* the sigmoid router: ``s = sigmoid(W_r h)`` over all the router's experts,
  the top-k of ``s + b`` selected, ``g = s / sum_sel s * scale``;
* every token through every expert this chip holds, weighted by its
  ``g`` (0 where the expert is not selected), plus the shared experts: the
  dropless layer, with the absent experts' part left out;
* pre-norm residual blocks, a final norm, the untied head over the
  vocabulary slice, next-token cross-entropy.

One sequence's attention and one layer are computed at a time, and
recomputed for the gradient, so the reference fits on the chip once the
program's state is freed. ``dtype=bfloat16`` computes the whole model and
its gradient in bfloat16: the control below the configuration's float32.

The round's inputs come from the seed alone (:func:`round_inputs`), as the
paper's round draws them: ``key = PRNGKey(seed)``, the parameters from the
first ``split`` (LeCun-normal projections, N(0, 0.02^2) embeddings, norm
offsets 0; :func:`init_params`), the round key from the next, client ``c``'s
uplink on ``fold_in(round_key, c)``, and each client's minibatch rows from
``default_rng(seed)``. The routers' correction biases are the fixed
buffers the configuration assumes, drawn N(0, std^2) from ``BUFFER_SEED``.

The uplink is ``bench.refmath``'s counter-RNG channel with the counter
widened past 2^32 symbols: each further 2^32 symbols of a payload draw
from the seed folded with their segment number. ``widen=False`` keeps the
32-bit counter that wraps: the control the correctness check must refuse.

``work`` counts the operations and bytes of a round from shapes alone.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import refmath

HIGHEST = jax.lax.Precision.HIGHEST
_U32 = jnp.uint32


# ------------------------------------------------------------- the shapes


def shapes(cfg: dict) -> dict:
    """The model's sizes from a configuration file's keys."""
    ep = cfg["expert_parallel"]
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "r": cfg["kv_lora_rank"],
        "layers": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"],
        "dense_ff": cfg["intermediate_size"],
        "moe_ff": cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"], "experts": ep["router_experts"],
        "held": cfg["n_routed_experts"], "offset": ep["expert_offset"],
        "top_k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
        "scale": cfg["routed_scaling_factor"],
        "norm_topk": cfg["norm_topk_prob"],
    }


def n_params(cfg: dict) -> int:
    """The payload: every parameter but the router's correction biases."""
    s = shapes(cfg)
    d, h = s["d"], s["heads"]
    attn = (d * h * (s["nope"] + s["rope"]) + d * (s["r"] + s["rope"])
            + s["r"] + s["r"] * h * (s["nope"] + s["v"]) + h * s["v"] * d)
    norms = 2 * d
    moe = (s["experts"] * d + 3 * d * s["moe_ff"] * (s["held"] + s["shared"]))
    return (2 * s["vocab"] * d + d + s["layers"] * (attn + norms)
            + s["dense"] * 3 * d * s["dense_ff"]
            + (s["layers"] - s["dense"]) * moe)


def forward_flops(cfg: dict, sequences: int, seq_len: int) -> int:
    """Operations of one forward pass over ``sequences`` of ``seq_len``.

    A multiply-add counts 2. Attention counts the causal pairs only; each
    held expert counts the tokens routed to it, ``T * k / experts`` on
    average; the router, the shared experts and the head count every
    token. Norms, RoPE, softmax and the embedding lookup are left out."""
    s = shapes(cfg)
    d, h, t = s["d"], s["heads"], sequences * seq_len
    proj = (d * h * (s["nope"] + s["rope"]) + d * (s["r"] + s["rope"])
            + s["r"] * h * (s["nope"] + s["v"]) + h * s["v"] * d)
    pairs = sequences * seq_len * (seq_len + 1) // 2
    attn = 2 * t * proj + 2 * pairs * h * (s["nope"] + s["rope"] + s["v"])
    swiglu = 3 * d
    dense = 2 * t * swiglu * s["dense_ff"]
    routed_tokens = t * s["top_k"] * s["held"] // s["experts"]
    moe = (2 * t * d * s["experts"] + 2 * t * swiglu * s["moe_ff"] * s["shared"]
           + 2 * routed_tokens * swiglu * s["moe_ff"])
    head = 2 * t * d * s["vocab"]
    return (s["layers"] * attn + s["dense"] * dense
            + (s["layers"] - s["dense"]) * moe + head)


def work(cfg: dict, traffic: dict) -> dict:
    """Operations and bytes one round needs, from shapes alone: each
    client's forward and backward (twice the forward) over its minibatch,
    the forward over the held-out set on evaluated rounds, and the uplink
    kernel's bytes (``bench.refmath.uplink_bytes``)."""
    payload = n_params(cfg)
    s_len, clients = traffic["seq_len"], traffic["clients"]
    fwd = forward_flops(cfg, traffic["batch_per_round"], s_len)
    return {
        "train_flops": clients * 3 * fwd,
        "eval_flops": forward_flops(cfg, traffic["eval_sequences"], s_len),
        "uplink_bytes": refmath.uplink_bytes(clients, payload, 4),
        "payload": payload,
    }


# --------------------------------------------------------- the round's inputs

# The key of the routers' correction biases: one fixed draw for every seed.
BUFFER_SEED = 0xB1A5


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _lecun(key, shape):
    return _normal(key, shape, 1.0 / math.sqrt(shape[-2]))


def _attn_init(key, s):
    d, h = s["d"], s["heads"]
    ks = jax.random.split(key, 5)
    return {
        "wq": _lecun(ks[0], (d, h * (s["nope"] + s["rope"]))),
        "wkv_a": _lecun(ks[2], (d, s["r"] + s["rope"])),
        "kv_norm": jnp.zeros((s["r"],), jnp.float32),
        "wkv_b": _lecun(ks[3], (s["r"], h * (s["nope"] + s["v"]))),
        "wo": _lecun(ks[4], (h * s["v"], d)),
    }


def _swiglu_init(key, d, f):
    ks = jax.random.split(key, 3)
    return {"wi": _lecun(ks[0], (d, f)), "wg": _lecun(ks[1], (d, f)),
            "wo": _lecun(ks[2], (f, d))}


def _layer_init(key, s, moe: bool):
    d = s["d"]
    k_attn, k_ffn = jax.random.split(key)
    layer = {"ln1": jnp.zeros((d,), jnp.float32),
             "ln2": jnp.zeros((d,), jnp.float32),
             "attn": _attn_init(k_attn, s)}
    if not moe:
        layer["mlp"] = _swiglu_init(k_ffn, d, s["dense_ff"])
        return layer
    ks = jax.random.split(k_ffn, 5)
    f, held = s["moe_ff"], s["held"]
    layer["moe"] = {
        "router": _lecun(ks[0], (d, s["experts"])),
        "wi": _lecun(ks[1], (held, d, f)),
        "wg": _lecun(ks[2], (held, d, f)),
        "wo": _lecun(ks[3], (held, f, d)),
        "shared": _swiglu_init(ks[4], d, f * s["shared"]),
    }
    return layer


def _stack(layers):
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers)


def init_params(key, s) -> dict:
    """The payload tree at round 0 from ``key``, layer by layer, op by op:
    under ``jit`` XLA folds each scale into the normal draw's own constant
    and rounds the product otherwise."""
    d, v, nd = s["d"], s["vocab"], s["dense"]
    ks = jax.random.split(key, 8)
    dense = jax.random.split(ks[3], nd)
    moe = jax.random.split(ks[4], s["layers"] - nd)
    return {
        "embed": _normal(ks[0], (v, d), 0.02),
        "final_norm": jnp.zeros((d,), jnp.float32),
        "lm_head": _lecun(ks[1], (d, v)),
        "dense_layers": _stack([_layer_init(k, s, False) for k in dense]),
        "layers": _stack([_layer_init(k, s, True) for k in moe]),
    }


def router_biases(cfg: dict) -> jax.Array:
    """The MoE layers' correction biases ``(MoE layers, experts)``."""
    s = shapes(cfg)
    return _normal(jax.random.PRNGKey(BUFFER_SEED),
                   (s["layers"] - s["dense"], s["experts"]),
                   cfg["model"]["router_bias_std"])


def round_inputs(cfg: dict, traffic: dict, seed: int, shards) -> dict:
    """Round 1's inputs from the seed: the parameters at round 0 (on the
    device), the correction biases, each client's ``(B, S + 1)`` rows drawn
    from ``shards`` ``(M, n, S + 1)``, and the round key."""
    if cfg["q_lora_rank"]:
        raise ValueError("the reference covers the query without a LoRA")
    s = shapes(cfg)
    key = jax.random.PRNGKey(seed)
    key, pk = jax.random.split(key)
    _, rk = jax.random.split(key)
    m, n = shards.shape[:2]
    take = np.random.default_rng(seed).integers(
        0, n, (m, traffic["batch_per_round"]))
    return {
        "p0": init_params(pk, s),
        "biases": router_biases(cfg),
        "xb": np.take_along_axis(shards, take[:, :, None], axis=1),
        "round_key": rk,
    }


# -------------------------------------------------------------- the model


def _mm(a, b, dtype):
    return jnp.matmul(a, b, precision=HIGHEST,
                      preferred_element_type=dtype)


def _norm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rope(x, theta):
    """Rotate-half RoPE over ``x``'s last dim; x (S, ..., d), positions
    0..S-1 on the first axis."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = np.arange(x.shape[0], dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), x.dtype)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), x.dtype)
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _attention(h, p, s):
    """One sequence (S, D) -> (S, D): latent attention, keys and values of
    every head made explicit."""
    dt = h.dtype
    S, H = h.shape[0], s["heads"]
    dn, dr, dv, r = s["nope"], s["rope"], s["v"], s["r"]
    q = _mm(h, p["wq"], dt).reshape(S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], s["theta"])], -1)
    kv_a = _mm(h, p["wkv_a"], dt)
    c_kv = _norm(kv_a[:, :r], p["kv_norm"], s["eps"])
    k_rope = _rope(kv_a[:, r:], s["theta"])
    w = p["wkv_b"].astype(dt).reshape(r, H, dn + dv)
    k_nope = jnp.einsum("sr,rhd->shd", c_kv, w[..., :dn], precision=HIGHEST,
                        preferred_element_type=dt)
    v = jnp.einsum("sr,rhd->shd", c_kv, w[..., dn:], precision=HIGHEST,
                   preferred_element_type=dt)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (S, H, dr))], -1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST,
                    preferred_element_type=jnp.float32)
    sc = sc / math.sqrt(dn + dr)
    sc = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], sc, -jnp.inf)
    a = jax.nn.softmax(sc, -1).astype(dt)
    o = jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST,
                   preferred_element_type=dt)
    return _mm(o.reshape(S, H * dv), p["wo"], dt)


def _swiglu(x, wi, wg, wo):
    dt = x.dtype
    g = _mm(x, wg, dt)
    return _mm((jax.nn.silu(g.astype(jnp.float32)).astype(dt)
                * _mm(x, wi, dt)), wo, dt)


def _moe(h2, p, bias, s):
    """(T, D) -> the held experts' part plus the shared experts."""
    dt = h2.dtype
    sc = jax.nn.sigmoid(_mm(h2, p["router"], jnp.float32))
    _, sel = jax.lax.top_k(sc + bias, s["top_k"])
    g = jnp.take_along_axis(sc, sel, -1)
    if s["norm_topk"]:
        g = g / jnp.sum(g, -1, keepdims=True)
    g = g * s["scale"]
    out = jnp.zeros(h2.shape, jnp.float32)
    for e in range(s["held"]):
        ge = jnp.sum(jnp.where(sel == s["offset"] + e, g, 0.0), -1)
        y = _swiglu(h2, p["wi"][e], p["wg"][e], p["wo"][e])
        out = out + ge[:, None] * y.astype(jnp.float32)
    sh = p["shared"]
    out = out + _swiglu(h2, sh["wi"], sh["wg"], sh["wo"]).astype(jnp.float32)
    return out.astype(dt)


def _block(x, p, bias, s, moe: bool):
    attn = jax.checkpoint(lambda seq: _attention(seq, p["attn"], s))
    x = x + jax.lax.map(attn, _norm(x, p["ln1"], s["eps"]))
    h = _norm(x, p["ln2"], s["eps"])
    B, S, D = h.shape
    if moe:
        f = _moe(h.reshape(B * S, D), p["moe"], bias, s).reshape(B, S, D)
    else:
        m = p["mlp"]
        f = _swiglu(h, m["wi"], m["wg"], m["wo"])
    return x + f


def loss(params, biases, tokens, s, dtype=jnp.float32):
    """Mean next-token cross-entropy of ``(B, S + 1)`` token rows.
    ``biases`` ``(MoE layers, experts)`` are the routers' correction
    biases; ``params`` is the payload tree."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    x = p["embed"][tokens[:, :-1]]
    for i in range(s["layers"]):
        dense = i < s["dense"]
        tree = p["dense_layers"] if dense else p["layers"]
        j = i if dense else i - s["dense"]
        layer = jax.tree_util.tree_map(lambda a: a[j], tree)
        bias = None if dense else biases[j]
        x = jax.checkpoint(lambda x_, l_, b_, m=not dense: _block(
            x_, l_, b_, s, m))(x, layer, bias)
    lg = _mm(_norm(x, p["final_norm"], s["eps"]), p["lm_head"], jnp.float32)
    logp = jax.nn.log_softmax(lg, -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


def grad_fn(s, dtype=jnp.float32):
    """The jitted gradient of :func:`loss` at ``dtype``, returned in
    float32 and flattened in the tree's leaf order: ``(D,)``."""
    @jax.jit
    def g(params, biases, tokens):
        with jax.default_matmul_precision("highest"):
            tree = jax.grad(loss)(params, biases, tokens, s, dtype)
        return jnp.concatenate([a.reshape(-1).astype(jnp.float32)
                                for a in jax.tree_util.tree_leaves(tree)])
    return g


# ------------------------------------------------------------- the uplink

# Tiles of 1024 words per channel call: what one call holds of the
# 16-symbol-a-word expansion stays near 1 GB.
CHUNK_TILES = 4096


def uplink_client(x, key, *, transport: dict, widen: bool = True):
    """One client's ``(D,)`` f32 payload through the approximate uplink
    (``bench.refmath.uplink_client``'s channel) with the symbol counter
    widened past 2^32 (``widen=False``: the 32-bit counter that wraps).
    Returns ``x_hat (D,) f32``.

    Tiles of 1024 words go through the channel ``CHUNK_TILES`` at a time,
    read from ``x`` and written into the result in place; the last chunk
    starts early enough to end at the last whole tile (the tiles it shares
    with the one before come out the same), and a last partial tile is
    padded with zero words, as the program pads it."""
    k = refmath.BITS_PER_SYMBOL[transport["modulation"]]
    gain = transport["tx_power"] * transport["distance"] ** (
        -transport["pathloss_exp"])
    noise = gain / (10.0 ** (float(transport["snr_db"]) / 10.0))
    bw, rows = refmath.BLOCK_WORDS, refmath.BLOCK_WORDS // refmath.LANES
    per_tile = bw * (32 // k)
    shift = 32 - (per_tile.bit_length() - 1)
    mask = _U32(refmath.clamp_mask(transport["clamp_bound"]))
    seed = refmath.seed_from_key(key)
    d = x.shape[0]
    words = jax.lax.bitcast_convert_type(x, _U32)

    def channel(u, t):
        """Tiles ``t`` (int32) of words ``u`` ``(len(t), rows, 128)``."""
        seg = (t >> shift).astype(_U32)
        sd = seed ^ refmath.fmix32(seg * _U32(0x9E3779B9))
        sd = jnp.where(seg > 0, sd, seed) if widen else jnp.broadcast_to(
            seed, t.shape)
        out = jax.vmap(lambda w, b, s_: refmath.channel_tile(
            w, s_, b, jnp.float32(noise), jnp.float32(gain), k=k,
            fading=transport["fading"],
            fade_block=transport.get("block_len", 64)))(u, t * per_tile, sd)
        return out & mask

    full = d // bw
    chunk = min(CHUNK_TILES, full)
    out = jnp.zeros((d,), _U32)

    def body(ci, out):
        first = jnp.minimum(ci * chunk, full - chunk)
        u = jax.lax.dynamic_slice(words, (first * bw,), (chunk * bw,))
        t = first + jnp.arange(chunk, dtype=jnp.int32)
        hat = channel(u.reshape(chunk, rows, refmath.LANES), t)
        return jax.lax.dynamic_update_slice(out, hat.reshape(-1),
                                            (first * bw,))

    if full:
        out = jax.lax.fori_loop(0, -(-full // chunk), body, out)
    if d > full * bw:
        tail = jnp.pad(words[full * bw:], (0, (full + 1) * bw - d))
        hat = channel(tail.reshape(1, rows, refmath.LANES),
                      jnp.full((1,), full, jnp.int32))
        out = out.at[full * bw:].set(hat.reshape(-1)[:d - full * bw])
    return jax.lax.bitcast_convert_type(out, jnp.float32)
