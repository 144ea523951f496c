"""Latent attention and the sigmoid-routed expert share against the plain
float32 reference (``repro.models.reference_mla_moe``), on seeded random
weights at a tiny Moonlight-shaped size: d 64, 4 heads, kv_lora 16,
nope/rope/v 8, 1 dense + 4 MoE layers, 8 experts of which 4 are held,
top-3, 1 shared expert."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import reference_mla_moe as ref
from repro.models import transformer as T

B, S = 2, 16


def tiny_cfg(**kw):
    cfg = dataclasses.replace(
        get_config("moonlight-16b-a3b"), n_layers=5, d_model=64, n_heads=4,
        n_kv_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, n_experts=8, experts_held=4,
        top_k=3, n_shared_experts=1, moe_d_ff=32, dense_d_ff=128,
        vocab_size=128, dtype="float32", capacity_factor=0.0)
    return dataclasses.replace(cfg, **kw)


@pytest.fixture(scope="module")
def cfg():
    return tiny_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    p = jax.jit(T.init_params, static_argnums=1)(jax.random.PRNGKey(0), cfg)
    # Norm weights away from their zero init, so a norm applied twice or
    # not at all shows.
    def jiggle(path, a):
        name = jax.tree_util.keystr(path)
        if "norm" in name or "ln" in name:
            return 0.1 * jax.random.normal(jax.random.PRNGKey(len(name)),
                                           a.shape)
        return a
    return jax.tree_util.tree_map_with_path(jiggle, p)


def _tokens(seed, shape=(B, S + 1), vocab=128):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab,
                              jnp.int32)


@pytest.mark.parametrize("impl", ["naive", "per_sequence", "blockwise"])
def test_mla_matches_explicit_per_head_kv(cfg, params, impl):
    c = dataclasses.replace(cfg, attn_impl=impl)
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, c.d_model))
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    got = T._attn_block(x, p, c, pos, 0) - x
    want = ref.mla(ref.rmsnorm(x, p["ln1"], c.rms_eps), p["attn"], c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_mla_decode_through_the_latent_cache_matches_the_forward(cfg, params):
    tokens = _tokens(2, (B, S))
    want, _ = T.forward(params, {"tokens": tokens}, cfg)
    cache = T.init_cache(cfg, B, S)
    assert cache["k"].shape[-1] == cfg.kv_lora_rank
    step = jax.jit(T.decode_step, static_argnums=4)
    outs = []
    for t in range(S):
        lg, cache = step(params, cache, tokens[:, t:t + 1], jnp.int32(t), cfg)
        outs.append(lg[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


def test_the_bias_changes_the_selection_and_not_the_weights(cfg):
    x = jax.random.normal(jax.random.PRNGKey(3), (32, cfg.d_model))
    router = L.dense_init(jax.random.PRNGKey(4), (cfg.d_model, cfg.n_experts),
                          dtype=jnp.float32)
    s = jax.nn.sigmoid(x @ router)
    sel0, w0 = MOE.route_sigmoid(x, router, jnp.zeros(cfg.n_experts), cfg)
    # A bias that lifts the experts the scores rank last.
    bias = jnp.zeros(cfg.n_experts).at[jnp.argsort(s.mean(0))[:2]].set(10.0)
    sel1, w1 = MOE.route_sigmoid(x, router, bias, cfg)
    assert not np.array_equal(np.sort(sel0, -1), np.sort(sel1, -1))
    for sel, w in ((sel0, w0), (sel1, w1)):
        g = jnp.take_along_axis(s, sel, -1)
        want = g / g.sum(-1, keepdims=True) * cfg.routed_scale
        np.testing.assert_allclose(np.asarray(w), np.asarray(want), rtol=1e-6)
        rsel, rw = ref.route(x, router, bias if sel is sel1 else 0.0, cfg)
        np.testing.assert_array_equal(np.asarray(sel), np.asarray(rsel))
        np.testing.assert_allclose(np.asarray(w), np.asarray(rw), rtol=1e-6)
    # The weights sum to the scale, whatever the bias chose.
    np.testing.assert_allclose(np.asarray(w1.sum(-1)), cfg.routed_scale,
                               rtol=1e-5)


def _moe_params(cfg, key=5):
    full = dataclasses.replace(cfg, experts_held=cfg.n_experts)
    return full, MOE.init_moe(jax.random.PRNGKey(key), full, jnp.float32)


def _share(p, lo, hi):
    q = dict(p)
    for k in ("wi", "wg", "wo"):
        q[k] = p[k][lo:hi]
    return q


# No capacity limit (every token through every held expert), and the sorted
# slots with room for every pair of these tokens.
@pytest.mark.parametrize("capacity_factor", [0.0, 2.5])
def test_the_expert_shares_sum_to_the_uncut_layer(cfg, capacity_factor):
    cfg = dataclasses.replace(cfg, capacity_factor=capacity_factor)
    full, p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (B, S, cfg.d_model))
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(7), (cfg.n_experts,))
    whole, _ = MOE.routed_ffn(x, p, bias, full)
    parts = []
    for off in (0, 4):
        c = dataclasses.replace(cfg, experts_held=4, expert_offset=off)
        part, cnt = MOE.routed_ffn(x, _share(p, off, off + 4), bias, c)
        assert int(cnt["moe_dropped_tokens"]) == 0
        parts.append(part)
        # Each share against the reference given the same share.
        want = ref.moe(x, _share(p, off, off + 4), bias, c)
        np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)
    s = p["shared"]
    shared = L.swiglu(x, s["wi"], s["wg"], s["wo"])
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] - shared),
                               np.asarray(whole), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(whole),
                               np.asarray(ref.moe(x, p, bias, full)),
                               rtol=2e-5, atol=2e-6)


def test_the_counters_count_held_pairs_and_drops(cfg):
    full, p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (64, cfg.d_model))
    bias = jnp.zeros(cfg.n_experts)
    c = dataclasses.replace(cfg, experts_held=4, expert_offset=4)
    sel, _ = ref.route(x, p["router"], bias, c)
    held = int(jnp.sum((sel >= 4) & (sel < 8)))
    _, cnt = MOE.routed_ffn(x, _share(p, 4, 8), bias, c)
    assert int(cnt["moe_local_tokens"]) == held > 0
    assert int(cnt["moe_dropped_tokens"]) == 0
    tight = dataclasses.replace(c, capacity_factor=0.1)
    _, cnt = MOE.routed_ffn(x, _share(p, 4, 8), bias, tight)
    cap = MOE.held_capacity(64, tight)
    assert int(cnt["moe_dropped_tokens"]) > 0
    assert int(cnt["moe_local_tokens"]) - int(cnt["moe_dropped_tokens"]) <= 4 * cap


def test_the_model_loss_and_gradient_match_the_reference(cfg, params):
    tok = _tokens(9)
    x, y = tok[:, :-1], tok[:, 1:]
    (loss, cnt), grad = jax.jit(jax.value_and_grad(T.lm_loss, has_aux=True),
                                static_argnums=3)(params, x, y, cfg)
    want, want_grad = jax.jit(jax.value_and_grad(ref.loss),
                              static_argnums=3)(params, x, y, cfg)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    assert int(cnt["moe_local_tokens"]) > 0
    assert int(cnt["moe_dropped_tokens"]) == 0
    flat = jax.tree_util.tree_leaves_with_path(want_grad)
    got = dict(jax.tree_util.tree_leaves_with_path(grad))
    for path, w in flat:
        g = got[path]
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.any(np.asarray(g)), name  # a buffer: no gradient
            continue
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(w) / scale,
                                   atol=2e-5, err_msg=name)
