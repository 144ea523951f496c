"""Mixture-of-Experts FFN with sort-based capacity dispatch.

TPU-native dispatch (MaxText/MegaBlocks-style, no (T, E, C) one-hot blowup):

  1. route: softmax router, ``lax.top_k`` -> (T, K) experts + weights
  2. sort the T*K assignments by expert id
  3. position-in-run via an associative max-scan (no one-hot)
  4. scatter tokens into an (E, C, D) buffer (capacity C static), dropping
     overflow (capacity factor configurable)
  5. batched expert matmuls (E-dim shardable as expert-parallel)
  6. gather back, combine with routing weights (dropped slots contribute 0)

A load-balance auxiliary loss (Switch-style) is returned alongside.

``routed_ffn`` is the DeepSeek-V3 layer (``router_score == "sigmoid"``):
sigmoid scores over all the router's experts, the top-k of score plus a
correction bias selected, the selected scores normalised and scaled; the
layer holds a contiguous share of the experts (expert parallelism) and
computes only their part of the result. Its dispatch is the same sorted
capacity layout over the held experts; with no capacity limit
(``capacity_factor <= 0``) every token goes through every held expert,
weighted by its gate (0 where the expert was not selected), which drops
nothing and needs no gather or scatter. It counts the pairs it computed and
any it dropped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L


def init_moe(key, cfg, dtype):
    D, F, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    Eh = cfg.held_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(ks[0], (D, E), dtype=jnp.float32),
        "wi": L.dense_init(ks[1], (Eh, D, F), in_axis=-2, dtype=dtype),
        "wg": L.dense_init(ks[2], (Eh, D, F), in_axis=-2, dtype=dtype),
        "wo": L.dense_init(ks[3], (Eh, F, D), in_axis=-2, dtype=dtype),
    }
    if cfg.router_score == "sigmoid":
        # The correction bias steers selection only: a buffer, which the
        # forward pass holds out of the gradient. Its key is the init key's
        # sixth draw, no round or client lane.
        p["router_bias"] = cfg.router_bias_std * jax.random.normal(
            jax.random.fold_in(key, 5),  # lint: ignore[keylane]
            (E,), jnp.float32)
    if cfg.n_shared_experts:
        Fs = cfg.moe_d_ff * cfg.n_shared_experts
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wi": L.dense_init(kk[0], (D, Fs), dtype=dtype),
            "wg": L.dense_init(kk[1], (D, Fs), dtype=dtype),
            "wo": L.dense_init(kk[2], (Fs, D), dtype=dtype),
        }
    return p


def capacity(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    # keep the expert batch MXU-friendly but never above the token count
    c = min(max(c, 8), n_tokens)
    return c


def moe_ffn(x: jax.Array, p, cfg):
    """x: (..., D) -> (out (..., D), aux_loss scalar f32)."""
    orig_shape = x.shape
    D = orig_shape[-1]
    x2 = x.reshape(-1, D)
    T = x2.shape[0]
    E, K = cfg.n_experts, cfg.top_k
    C = capacity(T, cfg)

    logits = (x2.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, K)  # (T,K)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    # Switch-style load-balance aux loss.
    density = jnp.mean(
        jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0
    )
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(density * mean_prob)

    flat_e = topi.reshape(-1)  # (T*K,)
    flat_t = jnp.arange(T * K, dtype=jnp.int32) // K  # token of each slot
    flat_w = topv.reshape(-1)

    order = jnp.argsort(flat_e)
    se = flat_e[order]
    st = flat_t[order]
    sw = flat_w[order]

    # position within each expert's contiguous run (associative max-scan)
    n = T * K
    ar = jnp.arange(n, dtype=jnp.int32)
    change = jnp.concatenate([jnp.ones((1,), bool), se[1:] != se[:-1]])
    run_start = jax.lax.associative_scan(jnp.maximum, jnp.where(change, ar, 0))
    pos = ar - run_start
    keep = pos < C
    slot_c = jnp.where(keep, pos, C)  # column C is the overflow trash slot

    # (E, C+1, D): the expert dim stays explicit (expert-parallel shardable);
    # column C is a trash slot for capacity overflow. NOTE: under pjit, XLA
    # replicates these data-dependent scatter/gather buffers across shards
    # (measured ~1 TiB/device temp on kimi-k2 train_4k) — the shard-local
    # all_to_all dispatch in ``moe_ffn_shardmap`` is the production fix;
    # this dense form is the recorded baseline (EXPERIMENTS.md Sec. Perf).
    buf = jnp.zeros((E, C + 1, D), x.dtype).at[se, slot_c].set(x2[st])
    h = buf[:, :C]
    hi = jnp.einsum("ecd,edf->ecf", h, p["wi"])
    hg = jnp.einsum("ecd,edf->ecf", h, p["wg"])
    act = jax.nn.silu(hg.astype(jnp.float32)).astype(hi.dtype) * hi
    y = jnp.einsum("ecf,efd->ecd", act, p["wo"])
    y = jnp.concatenate([y, jnp.zeros((E, 1, D), y.dtype)], axis=1)

    contrib = y[se, slot_c] * sw[:, None].astype(y.dtype)
    out = jnp.zeros((T, D), x.dtype).at[st].add(contrib)

    if cfg.n_shared_experts:
        s = p["shared"]
        out = out + L.swiglu(x2, s["wi"], s["wg"], s["wo"])
    return out.reshape(orig_shape), aux


def route_sigmoid(x2, router, bias, cfg):
    """``(T, D)`` -> selected experts ``(T, K)`` int32 and their weights
    ``(T, K)`` f32: top-k of ``sigmoid(x W_r) + bias``, weighted by the
    selected scores (normalised over the k when ``norm_topk_prob``) times
    ``routed_scale``. The bias changes which experts are chosen, never
    their weights."""
    scores = jax.nn.sigmoid(x2.astype(jnp.float32) @ router.astype(jnp.float32))
    _, sel = jax.lax.top_k(scores + bias, cfg.top_k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sel, w * cfg.routed_scale


def held_capacity(n_tokens: int, cfg) -> int:
    """Slots per held expert: ``capacity_factor`` times the mean load
    ``T * k / n_experts``, never above ``T`` (a token picks an expert once);
    ``T`` when ``capacity_factor <= 0`` (no limit)."""
    if cfg.capacity_factor <= 0:
        return n_tokens
    c = -(-int(n_tokens * cfg.top_k * cfg.capacity_factor) // cfg.n_experts)
    return min(max(c, 8), n_tokens)


def _held_dense(x2, sel, w, p, cfg):
    """Every token through every held expert, weighted by its gate (0 where
    the expert was not selected): ``(T, D)``. The gates scale each expert's
    hidden activations, so one contraction over experts and width sums
    them."""
    gate = jnp.sum(jax.nn.one_hot(sel - cfg.expert_offset, cfg.held_experts,
                                  dtype=jnp.float32) * w[..., None], axis=1)
    hi = jnp.einsum("td,edf->etf", x2, p["wi"])
    hg = jnp.einsum("td,edf->etf", x2, p["wg"])
    a = jax.nn.silu(hg) * hi * gate.T[:, :, None].astype(hi.dtype)
    return jnp.einsum("etf,efd->td", a, p["wo"])


def routed_ffn(x: jax.Array, p, bias, cfg):
    """The sigmoid-routed expert share: ``(..., D)`` -> ``(out, counters)``.

    ``out`` is the held experts' part, ``sum_{i in sel, held} g_i
    SwiGLU_i(x)``, plus the shared experts. Pairs (token, selected expert)
    whose expert is held are sorted by expert into ``(E_held, C)`` slots,
    or, when a slot row holds every token (``C == T``), every token goes
    through every held expert with its gate (:func:`_held_dense`).
    ``counters`` holds ``moe_local_tokens`` (held pairs) and
    ``moe_dropped_tokens`` (held pairs beyond a slot row: 0 when dropless).
    """
    shape = x.shape
    D = shape[-1]
    x2 = x.reshape(-1, D)
    T = x2.shape[0]
    K, Eh = cfg.top_k, cfg.held_experts
    C = held_capacity(T, cfg)
    with jax.named_scope("lm_router"):
        sel, w = route_sigmoid(x2, p["router"], bias, cfg)
        pair_e = sel.reshape(-1) - cfg.expert_offset
        held = (pair_e >= 0) & (pair_e < Eh)
        counters = {"moe_local_tokens": jnp.sum(held.astype(jnp.int32)),
                    "moe_dropped_tokens": jnp.int32(0)}
    if C == T:
        with jax.named_scope("lm_experts"):
            out = _held_dense(x2, sel, w, p, cfg)
    else:
        with jax.named_scope("lm_router"):
            # Held pairs first, by expert; the rest sort past them.
            order = jnp.argsort(jnp.where(held, pair_e, Eh), stable=True)
            se = jnp.where(held, pair_e, Eh)[order]
            ar = jnp.arange(T * K, dtype=jnp.int32)
            change = jnp.concatenate([jnp.ones((1,), bool),
                                      se[1:] != se[:-1]])
            pos = ar - jax.lax.associative_scan(jnp.maximum,
                                                jnp.where(change, ar, 0))
            keep = (se < Eh) & (pos < C)
            trash = Eh * C
            slot = jnp.where(keep, se * C + pos, trash)
            tok = jnp.zeros((trash + 1,), jnp.int32).at[slot].set(
                (order // K).astype(jnp.int32))[:trash]
            wt = jnp.zeros((trash + 1,), jnp.float32).at[slot].set(
                w.reshape(-1)[order])[:trash]
            counters["moe_dropped_tokens"] = jnp.sum(
                (held[order] & ~keep).astype(jnp.int32))
        with jax.named_scope("lm_experts"):
            h = x2[tok].reshape(Eh, C, D)
            hi = jnp.einsum("ecd,edf->ecf", h, p["wi"])
            hg = jnp.einsum("ecd,edf->ecf", h, p["wg"])
            y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(hg) * hi, p["wo"])
            # Empty slots carry weight 0 and add nothing to token 0.
            out = jnp.zeros((T, D), y.dtype).at[tok].add(
                y.reshape(trash, D) * wt[:, None].astype(y.dtype))
    if cfg.n_shared_experts:
        with jax.named_scope("lm_ffn"):
            s = p["shared"]
            out = out + L.swiglu(x2, s["wi"], s["wg"], s["wo"])
    return out.reshape(shape), counters


# ------------------------------------------------------------------------
# Expert-parallel dispatch via shard_map + all_to_all (the production path).
#
# Under plain pjit, the data-dependent scatter/gather through the (E, C, D)
# dispatch buffers defeats XLA's sharding propagation: it replicates the
# buffers across shards (~1 TiB/device temp measured on kimi-k2 train_4k).
# This variant makes the communication pattern explicit: tokens are routed
# locally on each data shard, exchanged with the expert-owner shards by a
# pair of all_to_alls, and each shard runs only its E/n_d experts — the
# canonical expert-parallel schedule (Switch/DeepSpeed-MoE), expressed in
# jax.shard_map over the data axes with the tensor axis left auto.
# ------------------------------------------------------------------------


def _usable_data_axes(cfg):
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return (), 1
    manual = {n for n, t in zip(mesh.axis_names, mesh.axis_types)
              if "Manual" in str(t)}
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names and a not in manual)
    nd = 1
    for a in axes:
        nd *= mesh.shape[a]
    return axes, nd


def _local_dispatch(x2, p, cfg, C):
    """Route + scatter local tokens into an (E, C, D) buffer. Returns
    (buf, se, slot_c, st, sw, aux)."""
    T, D = x2.shape
    E, K = cfg.n_experts, cfg.top_k
    logits = (x2.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, K)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    density = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(density * jnp.mean(probs, axis=0))

    flat_e = topi.reshape(-1)
    flat_t = jnp.arange(T * K, dtype=jnp.int32) // K  # token of each slot
    flat_w = topv.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    n = T * K
    ar = jnp.arange(n, dtype=jnp.int32)
    change = jnp.concatenate([jnp.ones((1,), bool), se[1:] != se[:-1]])
    run_start = jax.lax.associative_scan(jnp.maximum, jnp.where(change, ar, 0))
    pos = ar - run_start
    slot_c = jnp.where(pos < C, pos, C)
    buf = jnp.zeros((E, C + 1, D), x2.dtype).at[se, slot_c].set(x2[st])
    return buf[:, :C], se, slot_c, st, sw, aux


def moe_ffn_shardmap(x: jax.Array, p, cfg):
    """Expert-parallel MoE: (B, S, D) -> (out, aux). Falls back to the dense
    dispatch when no auto data axes exist (e.g. inside the per-client
    uplink shard_map, where experts are replicated per client cohort)."""
    axes, nd = _usable_data_axes(cfg)
    E = cfg.n_experts
    if not axes or nd == 1 or E % nd != 0 or x.ndim != 3 or x.shape[0] % nd != 0:
        return moe_ffn(x, p, cfg)
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E_loc = E // nd
    T_l = (B // nd) * S
    C = capacity(T_l, cfg)

    def local(xl, router, wi_l, wg_l, wo_l):
        Bl = xl.shape[0]
        x2 = xl.reshape(-1, D)
        buf, se, slot_c, st, sw, aux = _local_dispatch(
            x2, {"router": router}, cfg, C)
        # keep the dispatch buffers sharded over the (auto) tensor axis: the
        # per-shard (E, C, D) buffer can exceed 2^31 elements at kimi-k2
        # scale, which breaks XLA CPU if propagation replicates it
        buf = L.maybe_shard(buf, None, None, "model")
        # exchange with expert owners (tiled all_to_all: (E,C,D)->(E/nd,nd*C,D))
        h = jax.lax.all_to_all(buf, axes, split_axis=0, concat_axis=1, tiled=True)
        h = L.maybe_shard(h, None, None, "model")
        # f32 expert compute: with D model-sharded, the contractions (and
        # their VJPs) emit partial-sum all-reduces; f32 matches MXU
        # accumulate practice and sidesteps an XLA CPU AllReducePromotion
        # check-crash on large bf16 copy-reduction ARs. The all_to_all
        # payloads on either side stay bf16.
        h32 = h.astype(jnp.float32)
        hi = jnp.einsum("ecd,edf->ecf", h32, wi_l.astype(jnp.float32))
        hg = jnp.einsum("ecd,edf->ecf", h32, wg_l.astype(jnp.float32))
        act = jax.nn.silu(hg) * hi
        y = jnp.einsum("ecf,efd->ecd", act, wo_l.astype(jnp.float32)).astype(h.dtype)
        y = L.maybe_shard(y, None, None, "model")
        y_loc = jax.lax.all_to_all(y, axes, split_axis=1, concat_axis=0, tiled=True)
        y_loc = L.maybe_shard(y_loc, None, None, "model")
        y_pad = jnp.concatenate([y_loc, jnp.zeros((E, 1, D), y_loc.dtype)], axis=1)
        contrib = y_pad[se, slot_c] * sw[:, None].astype(y_loc.dtype)
        out = jnp.zeros_like(x2).at[st].add(contrib)
        aux = jax.lax.pmean(aux, axes)
        return out.reshape(Bl, S, D), aux

    fn = jax.shard_map(
        local,
        axis_names=set(axes),
        in_specs=(P(axes, None, None), P(), P(axes, None, None),
                  P(axes, None, None), P(axes, None, None)),
        out_specs=(P(axes, None, None), P()),
        check_vma=False,
    )
    out, aux = fn(x, p["router"], p["wi"], p["wg"], p["wo"])
    if cfg.n_shared_experts:
        # routing-independent: computed at the pjit level. Keeping replicated
        # bf16 params out of the shard_map also avoids an XLA CPU
        # AllReducePromotion crash on their cotangent psum (copy-reduction AR).
        s_ = p["shared"]
        out = out + L.swiglu(x.reshape(-1, D), s_["wi"], s_["wg"], s_["wo"]).reshape(x.shape)
    return out, aux
