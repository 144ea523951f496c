"""The benchmark's plain reference against the repository's own oracle and
model, at tiny sizes: the copied channel math bit for bit, the CNN's
initialisation bit for bit and its loss to float32 rounding."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import refmath

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "cnn-static-qpsk.json").read_text())


def _transport(modulation, fading="rayleigh", snr_db=10.0):
    return dict(CONFIG["transport"], modulation=modulation, fading=fading,
                snr_db=snr_db)


@pytest.mark.parametrize("modulation,fading", [
    ("qpsk", "rayleigh"), ("16qam", "rayleigh"), ("256qam", "awgn")])
def test_uplink_matches_the_oracle_bit_for_bit(modulation, fading):
    from repro.kernels import ref as kref

    t = _transport(modulation, fading)
    n = 2500  # pads to 3 tiles of 1024 words
    x = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32) * 0.05
    key = jax.random.fold_in(jax.random.PRNGKey(2), 7)
    hat, errs = refmath.uplink_client(x, key, transport=t)

    gain = t["tx_power"] * t["distance"] ** (-t["pathloss_exp"])
    xp = jnp.pad(x, (0, (-n) % 1024))
    want, want_errs = kref.ref_approx_channel(
        xp, refmath.seed_from_key(key),
        jnp.float32(gain / 10.0 ** (t["snr_db"] / 10.0)), jnp.float32(gain),
        bits_per_symbol=refmath.BITS_PER_SYMBOL[modulation], fading=fading,
        clamp_mask=refmath.clamp_mask(t["clamp_bound"]), valid_words=n)
    np.testing.assert_array_equal(
        np.asarray(hat).view(np.uint32), np.asarray(want[:n]).view(np.uint32))
    assert int(errs) == int(want_errs) > 0


@pytest.mark.parametrize("bound", [2.0, 1.0, 0.5, 1e-3])
def test_clamp_mask_matches_the_codec(bound):
    from repro.core import float_codec

    assert refmath.clamp_mask(bound) == float_codec.exponent_clamp_mask(bound)


def test_seed_matches_the_kernel_adapter():
    from repro.kernels import ops

    key = jax.random.PRNGKey(5)
    assert int(refmath.seed_from_key(key)) == int(ops._seed_from_key(key))


def test_cnn_init_and_loss_match_the_program():
    from repro.configs.mnist_cnn import MnistCnnConfig
    from repro.fl import cnn

    key = jax.random.PRNGKey(3)
    mine = refmath.cnn_init(key, CONFIG["model"])
    theirs = cnn.init_params(key, MnistCnnConfig())
    assert sorted(mine) == sorted(theirs)
    for k in mine:
        np.testing.assert_array_equal(np.asarray(mine[k]), np.asarray(theirs[k]))
    x = jax.random.uniform(jax.random.PRNGKey(4), (6, 28, 28))
    y = jnp.arange(6) % 10
    np.testing.assert_allclose(float(refmath.cnn_loss(mine, x, y)),
                               float(cnn.loss_fn(theirs, x, y)), rtol=1e-5)


def test_client_grads_follow_the_sorted_leaf_layout():
    key = jax.random.PRNGKey(6)
    params = refmath.cnn_init(key, CONFIG["model"])
    x = jax.random.uniform(key, (2, 3, 28, 28))
    y = jnp.zeros((2, 3), jnp.int32)
    flat = refmath.client_grads(params, x, y)
    assert flat.shape == (2, 21_840)
    g = jax.grad(refmath.cnn_loss)(params, x[1], y[1])
    back = refmath.unflatten(flat[1], params)
    for k in params:
        np.testing.assert_allclose(np.asarray(back[k]), np.asarray(g[k]),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("op,shapes", [
    ("_dot_op", ((6, 20), (20, 5))),
    ("_conv_op", ((2, 3, 12, 12), (4, 3, 5, 5)))])
def test_three_bf16_passes_are_close_to_float32_but_not_equal(op, shapes):
    f = getattr(refmath, op)
    a = jax.random.normal(jax.random.PRNGKey(7), shapes[0])
    b = jax.random.normal(jax.random.PRNGKey(8), shapes[1])

    def loss(a, b, precision):
        return jnp.sum(jnp.sin(refmath._product(f, a, b, precision)))

    exact = jax.grad(loss, (0, 1))(a, b, "highest")
    three = jax.grad(loss, (0, 1))(a, b, "bf16_3x")
    for e, t in zip(exact, three):
        np.testing.assert_allclose(np.asarray(t), np.asarray(e),
                                   rtol=1e-3, atol=2e-3)
        assert not np.array_equal(np.asarray(t), np.asarray(e))

