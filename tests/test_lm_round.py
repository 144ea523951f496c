"""A Moonlight-shaped LM as the FL payload, through ``RoundEngine`` and
``FedSGD`` as ``run_fl`` builds them, at a tiny size on the CPU: the round
against the plain reference step, the approximate uplink against the
kernel's oracle, and cohort waves against one launch."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import channel as channel_lib
from repro.core import float_codec
from repro.core import transport as transport_lib
from repro.fl import engine as E
from repro.fl.payload import LmPayload
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models import reference_mla_moe as ref

M, N_LOCAL, S, BATCH, SEED, LR = 4, 6, 16, 2, 11, 0.05


def tiny_cfg():
    return dataclasses.replace(
        get_config("moonlight-16b-a3b"), n_layers=5, d_model=64, n_heads=4,
        n_kv_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, n_experts=8, experts_held=4,
        top_k=3, n_shared_experts=1, moe_d_ff=32, dense_d_ff=128,
        vocab_size=64, dtype="float32", capacity_factor=0.0)


def _world():
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, 64, (M, N_LOCAL, S + 1)).astype(np.int32)
    test = rng.integers(0, 64, (3, S + 1)).astype(np.int32)
    return (tokens, np.zeros((M, N_LOCAL), np.int32), test,
            np.zeros((3,), np.int32))


def _tcfg(mode="approx"):
    return transport_lib.TransportConfig(
        mode=mode, modulation="qpsk",
        channel=channel_lib.ChannelConfig(snr_db=10.0, fading="rayleigh"),
        clamp_bound=2.0, use_kernel=mode == "approx")


def _engine(tcfg, fused=True):
    tokens, labels, test_x, test_y = _world()
    algo = E.FedSGD(LmPayload(tiny_cfg(), LR, S), batch_per_round=BATCH)
    return E.RoundEngine(algo, tcfg, tokens, labels, test_x, test_y,
                         n_rounds=1, seed=SEED, eval_every=1,
                         fused_aggregate=fused)


def _round_inputs(engine):
    """The first round's minibatches and key, as ``run`` draws them."""
    take = np.random.default_rng(SEED).integers(0, N_LOCAL, (M, BATCH))
    tokens = np.asarray(engine.client_x)
    xb = np.stack([tokens[i, take[i]] for i in range(M)])
    key = jax.random.split(jax.random.split(jax.random.PRNGKey(SEED))[0])[1]
    return jnp.asarray(xb), jnp.zeros((M, BATCH), jnp.int32), key


def _leaves(tree):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]


@pytest.fixture(scope="module")
def approx_round():
    """One fused approximate-uplink round: the engine before and after."""
    engine = _engine(_tcfg())
    p0 = jax.tree_util.tree_map(jnp.copy, engine.params)
    res = engine.run()
    return engine, p0, res


def test_the_buffers_are_not_payload():
    engine = _engine(_tcfg("perfect"))
    assert "router_bias" not in engine.params["layers"]["moe"]
    assert engine.algo.model.buffers.shape == (4, 8)
    assert engine.uplink_waves == 1


def test_a_perfect_round_is_the_reference_step():
    engine = _engine(_tcfg("perfect"))
    model = engine.algo.model
    p0 = jax.tree_util.tree_map(jnp.copy, engine.params)
    xb, _, _ = _round_inputs(engine)
    res = engine.run()
    full = model._full(p0)
    grad = jax.jit(jax.grad(ref.loss), static_argnums=3)
    grads = [grad(full, x[:, :-1], x[:, 1:], model.cfg) for x in xb]
    mean = jax.tree_util.tree_map(lambda *g: sum(g) / M, *grads)
    for path, a0 in jax.tree_util.tree_leaves_with_path(p0):
        a1 = dict(jax.tree_util.tree_leaves_with_path(engine.params))[path]
        g = dict(jax.tree_util.tree_leaves_with_path(mean))[path]
        step = (np.asarray(a0) - np.asarray(a1)) / LR
        scale = float(jnp.max(jnp.abs(g))) + 1e-12
        np.testing.assert_allclose(step / scale, np.asarray(g) / scale,
                                   atol=1e-4, err_msg=jax.tree_util.keystr(path))
    # The counters came back with the round: every client's held pairs.
    counters = res.records[0].counters
    assert counters["moe_local_tokens"] > 0
    assert counters["moe_dropped_tokens"] == 0
    # The held-out metric is the reference's mean next-token loss.
    test = np.asarray(engine.test_x)
    want = np.mean([float(ref.loss(model._full(engine.params), t[None, :-1],
                                   t[None, 1:], model.cfg)) for t in test])
    assert res.accuracy[-1] == pytest.approx(want, rel=1e-5)


def test_the_approximate_round_is_the_oracle_on_the_same_payload(approx_round):
    engine, p0, _ = approx_round
    xb, yb, key = _round_inputs(engine)
    grads = jax.jit(engine.algo.payload)(p0, xb, yb)
    flat, spec = transport_lib._flatten_client_tree(grads)
    d = flat.shape[1]
    tcfg, ch = engine.transport_cfg, engine.transport_cfg.channel
    agg = jnp.zeros((d,), jnp.float32)
    oracle = jax.jit(kref.ref_approx_channel, static_argnames=(
        "bits_per_symbol", "fading", "clamp_mask", "valid_words"))
    for i in range(M):
        seed = kops._seed_from_key(jax.random.fold_in(key, i))
        hat, _ = oracle(
            jnp.pad(flat[i], (0, (-d) % 1024)), seed,
            jnp.float32(ch.noise_power), jnp.float32(ch.large_scale_gain),
            bits_per_symbol=2, fading="rayleigh",
            clamp_mask=float_codec.exponent_clamp_mask(tcfg.clamp_bound),
            valid_words=d)
        agg = agg + jnp.float32(1.0 / M) * hat[:d]
    want = jax.jit(engine.algo.apply)(
        p0, engine.algo.init_opt(p0),
        transport_lib._unflatten_aggregate_tree(agg, spec))[0]
    for got, exp in zip(_leaves(engine.params), _leaves(want)):
        np.testing.assert_array_equal(got.view(np.uint32), exp.view(np.uint32))


def _per_client_payload(self, params, xb, yb):
    """FedSGD's payload with each client's gradient its own computation:
    a gradient batched over 1, 2 or 4 clients may round differently, and
    the waves are to show the uplink and the aggregate alone."""
    grads, counters = jax.lax.map(
        lambda a: self.grad_fn(params, a[0], a[1]), (xb, yb))
    return grads, jax.tree_util.tree_map(lambda c: jnp.sum(c, 0), counters)


@pytest.fixture(scope="module")
def one_launch_round():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(E.FedSGD, "payload_counted", _per_client_payload)
        engine = _engine(_tcfg())
        assert engine.uplink_waves == 1
        return engine, engine.run()


@pytest.mark.parametrize("wave", [1, 2])
def test_waves_give_the_one_launch_aggregate(one_launch_round, monkeypatch,
                                              wave):
    one, res_one = one_launch_round
    monkeypatch.setattr(E.FedSGD, "payload_counted", _per_client_payload)
    monkeypatch.setattr(E, "wave_clients", lambda *a, **k: wave)
    engine = _engine(_tcfg())
    assert engine.uplink_waves == M // wave
    res = engine.run()
    for got, want in zip(_leaves(engine.params), _leaves(one.params)):
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32))
    assert res.records[0].counters == res_one.records[0].counters


@pytest.mark.parametrize("wave", [1, 2, 4])
def test_a_wave_continues_the_aggregate_of_the_waves_before(wave):
    # The same (M, D) payload: one kernel launch, or waves folded into the
    # running aggregate with the client offset of each wave's first client.
    x = jax.random.normal(jax.random.PRNGKey(3), (M, 3000)) * 0.05
    key = jax.random.PRNGKey(4)
    w = jnp.full((M,), 1.0 / M, jnp.float32)
    tcfg = _tcfg()
    want, want_st = transport_lib.transmit_batch_aggregate(x, key, tcfg, w)
    acc, errs = jnp.zeros((3000,), jnp.float32), []
    for i in range(0, M, wave):
        acc, st = transport_lib.transmit_batch_aggregate(
            x[i:i + wave], key, tcfg, w[i:i + wave], client_offset=i, acc=acc)
        errs.append(st.bit_errors)
    np.testing.assert_array_equal(np.asarray(acc).view(np.uint32),
                                  np.asarray(want).view(np.uint32))
    np.testing.assert_array_equal(np.concatenate(errs), want_st.bit_errors)


def test_the_wave_size_comes_from_the_payload_and_the_memory():
    gib = 1 << 30
    # The paper's CNN cohort fits: one launch.
    assert E.wave_clients(1000, 21_840, 16 * gib) == 1000
    # 568.5M floats a client: one client a wave on a 16 GB chip.
    assert E.wave_clients(4, 568_486_912, 16 * gib) == 1
    assert E.wave_clients(4, 568_486_912, 64 * gib) == 2
    # No memory figure (the CPU reports none): one launch.
    assert E.wave_clients(4, 568_486_912, 0) == 4
