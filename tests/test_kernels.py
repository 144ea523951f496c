"""Fused approx-channel Pallas kernel vs the pure-jnp oracle.

Exactness (not allclose): kernel and ref share the counter-RNG, so outputs
must match bit-for-bit across every modulation / fading / shape swept here.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops as O
from repro.kernels import ref as R

G0 = 1e-3  # tx_power * d^-alpha at d=10, alpha=3


def _run_both(x, seed, snr_db, k, fading, block_words):
    npow = G0 / (10 ** (snr_db / 10))
    ref_out, ref_err = R.ref_approx_channel(
        x, jnp.uint32(seed), jnp.float32(npow), jnp.float32(G0),
        bits_per_symbol=k, fading=fading, fade_block=64, block_words=block_words)
    ker_out, ker_err = O.approx_channel(
        x, jnp.uint32(seed), npow, G0, bits_per_symbol=k, fading=fading,
        fade_block=64, block_words=block_words, interpret=True)
    return ref_out, int(ref_err), ker_out, int(ker_err)


@pytest.mark.parametrize("k", [2, 4, 8])
@pytest.mark.parametrize("fading", ["rayleigh", "awgn", "block_rayleigh"])
def test_kernel_bitexact_vs_ref(k, fading):
    x = jax.random.uniform(jax.random.PRNGKey(0), (2048,), minval=-1, maxval=1)
    ref_out, ref_err, ker_out, ker_err = _run_both(x, 77, 10.0, k, fading, 512)
    np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(ker_out))
    assert ref_err == ker_err


@pytest.mark.parametrize("k", [2, 8])
@pytest.mark.parametrize("num_active", [1, 3, 5])
def test_masked_partial_batch_grid(k, num_active):
    """The masked (clients, tiles) grid: active rows bit-identical to the
    unmasked batch, masked tail rows all-zero with zero error count — the
    contract the adaptive dispatch's padded buckets rely on."""
    C, N = 5, 1024
    x = jax.random.uniform(jax.random.PRNGKey(3), (C, N), minval=-1, maxval=1)
    seeds = jnp.arange(100, 100 + C, dtype=jnp.uint32)
    npow = jnp.full((C,), G0 / 10.0, jnp.float32)
    gains = jnp.full((C,), G0, jnp.float32)
    full, full_err = O.approx_channel_batch(
        x, seeds, npow, gains, bits_per_symbol=k, block_words=512,
        interpret=True)
    part, part_err = O.approx_channel_batch(
        x, seeds, npow, gains, bits_per_symbol=k, block_words=512,
        interpret=True, num_active=jnp.int32(num_active))
    np.testing.assert_array_equal(
        np.asarray(full[:num_active]), np.asarray(part[:num_active]))
    np.testing.assert_array_equal(
        np.asarray(full_err[:num_active]), np.asarray(part_err[:num_active]))
    np.testing.assert_array_equal(
        np.asarray(part[num_active:]), np.zeros((C - num_active, N)))
    np.testing.assert_array_equal(
        np.asarray(part_err[num_active:]), np.zeros(C - num_active))


@settings(max_examples=10, deadline=None)
@given(
    st.integers(0, 2**31 - 1),
    st.sampled_from([2, 4, 8]),
    st.sampled_from([128, 256, 1024]),
    st.integers(1, 6),  # payload in blocks
    st.sampled_from([0.0, 10.0, 25.0]),
)
def test_kernel_bitexact_sweep(seed, k, block_words, nblocks, snr):
    """Kernel == oracle, modulo rounding-boundary ties: the shared demod
    rounds (y*inv + L-1)/2, and XLA may fuse that differently (fma) in the
    vmapped reference vs the interpret-mode kernel, flipping the decision
    for symbols landing exactly on a decision boundary. Allow <=0.5% of
    elements to differ at such ties; everything else must be bit-exact."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.uniform(key, (block_words * nblocks,), minval=-1.999, maxval=1.999)
    ref_out, ref_err, ker_out, ker_err = _run_both(x, seed, snr, k, "rayleigh", block_words)
    mism = np.asarray(ref_out) != np.asarray(ker_out)
    assert mism.mean() <= 0.005, f"{mism.sum()} / {mism.size} mismatches"
    assert abs(ref_err - ker_err) <= 32 * int(mism.sum())


def test_kernel_output_always_bounded():
    x = jax.random.uniform(jax.random.PRNGKey(3), (4096,), minval=-1.9, maxval=1.9)
    out, _ = O.approx_channel(x, jnp.uint32(5), G0 / 1.0, G0)  # SNR 0 dB
    assert bool(jnp.isfinite(out).all())
    assert float(jnp.abs(out).max()) < 2.0


def test_kernel_padding_path():
    """Non-multiple payloads go through ops.py padding."""
    x = jax.random.uniform(jax.random.PRNGKey(4), (1000,), minval=-1, maxval=1)
    out, errs = O.approx_channel(x, jnp.uint32(6), G0 / 10, G0, block_words=512)
    assert out.shape == (1000,)
    assert bool(jnp.isfinite(out).all())


def test_kernel_naive_mode_mask():
    """clamp_mask=0xFFFFFFFF reproduces naive (unbounded) transmission."""
    x = jax.random.uniform(jax.random.PRNGKey(5), (4096,), minval=-1, maxval=1)
    out, errs = O.approx_channel(
        x, jnp.uint32(7), G0 / 10, G0, clamp_mask=0xFFFFFFFF)
    assert errs > 0
    # without the clamp some decoded values exceed the bound (or are NaN)
    bad = (~jnp.isfinite(out)) | (jnp.abs(out) >= 2.0)
    assert bool(bad.any())


def test_demod_closed_form_equals_bruteforce_in_pipeline():
    """ref.py closed-form demod == modulation.demod_ml on the same symbols."""
    from repro.core import modulation as M

    for name in ("qpsk", "16qam", "256qam"):
        scheme = M.MOD_SCHEMES[name]
        key = jax.random.PRNGKey(8)
        y = (jax.random.normal(key, (1024,)) * 0.7 +
             1j * jax.random.normal(jax.random.PRNGKey(9), (1024,)) * 0.7
             ).astype(jnp.complex64)
        np.testing.assert_array_equal(
            np.asarray(M.demod_hard(y, scheme)), np.asarray(M.demod_ml(y, scheme)))


@pytest.mark.parametrize("k", [2, 4, 8])
def test_kernel_bf16_wire(k):
    """16-bit (bf16) wire variant: kernel == oracle, output bounded, and
    half the symbols per value vs the f32 wire."""
    x = jax.random.uniform(jax.random.PRNGKey(6), (2048,), minval=-1.9, maxval=1.9)
    ref_out, ref_err = R.ref_approx_channel(
        x, jnp.uint32(7), jnp.float32(G0 / 10), jnp.float32(G0),
        bits_per_symbol=k, fading="rayleigh", fade_block=64,
        clamp_mask=0xBFFF, block_words=512, word_bits=16)
    ker_out, ker_err = O.approx_channel(
        x, jnp.uint32(7), G0 / 10, G0, bits_per_symbol=k, clamp_mask=0xBFFF,
        block_words=512, word_bits=16, interpret=True)
    r32 = np.asarray(ref_out, np.float32)
    k32 = np.asarray(ker_out, np.float32)
    mism = (r32 != k32).mean()
    assert mism <= 0.005
    assert int(ref_err) == int(ker_err) or mism > 0
    assert np.isfinite(k32).all() and (np.abs(k32) < 2.0).all()


# ------------------------------------------- fast path and exact recompute

K = importlib.import_module("repro.kernels.approx_channel")


def _cohort(c, n, snr_db, seed=21):
    x = jax.random.uniform(jax.random.PRNGKey(seed), (c, n),
                           minval=-1.9, maxval=1.9)
    seeds = jnp.arange(c, dtype=jnp.uint32) * jnp.uint32(0x9E3779B1) + 5
    noise = jnp.full((c,), G0 / 10 ** (snr_db / 10), jnp.float32)
    gains = jnp.full((c,), G0, jnp.float32)
    weights = jnp.linspace(0.5, 1.5, c, dtype=jnp.float32)
    return x, seeds, noise, gains, weights


def _ref_rows(x, seeds, noise, gains, **params):
    return [R.ref_approx_channel(x[i], seeds[i], noise[i], gains[i], **params)
            for i in range(x.shape[0])]


def _assert_is_the_scan(agg, refs, weights):
    """The fused kernel's sum is the client-order scan of the oracle's
    rows (``transport._scan_weighted_sum``, the layered round's sum)."""
    from repro.core import transport as T

    rows = jnp.stack([want.astype(jnp.float32) for want, _ in refs])
    np.testing.assert_array_equal(
        np.asarray(agg).view(np.uint32),
        np.asarray(T._scan_weighted_sum(rows, weights)).view(np.uint32))


@pytest.mark.parametrize("kernel", ["batch", "fused"])
@pytest.mark.parametrize("word_bits", [32, 16], ids=["f32", "bf16"])
@pytest.mark.parametrize("fading", ["rayleigh", "block_rayleigh", "awgn"])
@pytest.mark.parametrize("k", [2, 4, 8], ids=["qpsk", "16qam", "256qam"])
def test_fast_path_kernels_equal_the_oracle_bit_for_bit(k, fading, word_bits,
                                                        kernel):
    """Both kernels, fast path plus exact recompute, give the oracle's
    words and error counts exactly, on either wire."""
    c, n = 2, 2048
    x, seeds, noise, gains, weights = _cohort(c, n, 10.0)
    wire = jnp.bfloat16 if word_bits == 16 else jnp.float32
    x = x.astype(wire)
    params = dict(bits_per_symbol=k, fading=fading, fade_block=64,
                  block_words=512, word_bits=word_bits,
                  clamp_mask=0xBFFF if word_bits == 16 else 0xBFFFFFFF)
    refs = _ref_rows(x, seeds, noise, gains, **params)
    if kernel == "batch":
        rows, errs = K.approx_channel_batch_pallas(
            x, seeds, noise, gains, interpret=True, **params)
        for i, (want, want_errs) in enumerate(refs):
            np.testing.assert_array_equal(
                np.asarray(rows[i]).view(f"u{word_bits // 8}"),
                np.asarray(want).view(f"u{word_bits // 8}"))
            assert int(errs[i]) == int(want_errs)
        return
    agg, errs, exact = K.approx_channel_batch_aggregate_pallas(
        x, seeds, noise, gains, weights, interpret=True, **params)
    for i, (_, want_errs) in enumerate(refs):
        assert int(errs[i]) == int(want_errs)
    _assert_is_the_scan(agg, refs, weights)
    assert ((np.asarray(exact) >= 0) & (np.asarray(exact) <= n // 512)).all()


@pytest.mark.parametrize("resident", [False, True],
                         ids=["per_tile_counter", "resident_counter"])
def test_high_noise_takes_the_exact_path_and_keeps_the_oracles_words(
        monkeypatch, resident):
    """At 0 dB most tiles hold a decision near a threshold: the exact path
    runs, is counted per client, and the words stay the oracle's."""
    if resident:
        monkeypatch.setattr(K, "PER_TILE_COUNTER_BYTES", 0)
    c, n = 3, 4096
    x, seeds, noise, gains, weights = _cohort(c, n, 0.0, seed=22)
    params = dict(bits_per_symbol=2, fading="rayleigh", block_words=1024,
                  clamp_mask=0xFFFFFFFF)
    agg, errs, exact = K.approx_channel_batch_aggregate_pallas(
        x, seeds, noise, gains, weights, interpret=True, **params)
    refs = _ref_rows(x, seeds, noise, gains, **params)
    for i, (_, want_errs) in enumerate(refs):
        assert int(errs[i]) == int(want_errs)
    _assert_is_the_scan(agg, refs, weights)
    exact = np.asarray(exact)
    assert exact.shape == (c,)
    assert exact.sum() > 0 and (exact <= n // 1024).all()



@pytest.mark.parametrize("kernel", ["batch", "fused"])
def test_a_partial_last_group_keeps_the_oracles_words(kernel):
    """A full group of ``GROUP_TILES`` tiles and a partial one of 3: the
    partial group transmits its tiles alone, masked clients stay out, and
    a running aggregate is added to, as the oracle's rows give."""
    tiles = K.GROUP_TILES + 3
    c, n, active = 3, tiles * 256, 2
    x, seeds, noise, gains, weights = _cohort(c, n, 0.0, seed=24)
    params = dict(bits_per_symbol=2, fading="rayleigh", block_words=256,
                  clamp_mask=0xBFFFFFFF)
    refs = _ref_rows(x, seeds, noise, gains, **params)
    if kernel == "batch":
        rows, errs = K.approx_channel_batch_pallas(
            x, seeds, noise, gains, interpret=True, num_active=active,
            **params)
        for i, (want, want_errs) in enumerate(refs[:active]):
            np.testing.assert_array_equal(np.asarray(rows[i]).view("u4"),
                                          np.asarray(want).view("u4"))
            assert int(errs[i]) == int(want_errs)
        assert not np.asarray(rows[active:]).any()
        assert not np.asarray(errs[active:]).any()
        return
    acc = jax.random.normal(jax.random.PRNGKey(4), (n,))
    agg, errs, exact = K.approx_channel_batch_aggregate_pallas(
        x, seeds, noise, gains, weights, interpret=True, num_active=active,
        acc=acc, **params)
    for i, (_, want_errs) in enumerate(refs[:active]):
        assert int(errs[i]) == int(want_errs)
    from repro.core import transport as T

    rows = jnp.stack([want.astype(jnp.float32) for want, _ in refs])
    np.testing.assert_array_equal(
        np.asarray(agg).view(np.uint32),
        np.asarray(T._scan_weighted_sum(rows, weights, active, acc)).view(
            np.uint32))
    exact = np.asarray(exact)
    assert exact[:active].sum() > 0 and (exact <= tiles).all()
    assert not exact[active:].any() and not np.asarray(errs[active:]).any()

def test_without_noise_no_tile_takes_the_exact_path():
    c, n = 2, 4096
    x, seeds, _, gains, weights = _cohort(c, n, 10.0, seed=23)
    noise = jnp.zeros((c,), jnp.float32)
    agg, errs, exact = K.approx_channel_batch_aggregate_pallas(
        x, seeds, noise, gains, weights, bits_per_symbol=8, interpret=True)
    assert not np.asarray(exact).any()
    assert not np.asarray(errs).any()


def test_the_fast_sincos_is_within_its_bound_over_every_angle():
    """Over all 2^24 Box-Muller angles, against float64: the fast pair is
    within ``SINCOS_ERR`` and this platform's ``jnp.sin`` / ``jnp.cos``
    within ``PLATFORM_SINCOS_ERR``, the two bounds the margin assumes.
    The angle is made in the same program as the pair, as in the kernel."""

    @jax.jit
    def pairs(h):
        ang = jnp.float32(R._TWO_PI) * R.uniform01(h)
        return (ang, *K.sincos_2pi(ang), jnp.sin(ang), jnp.cos(ang))

    worst = np.zeros(4)
    chunk = 1 << 22  # of the 2^24 draws ``h >> 8``
    for start in range(0, 1 << 24, chunk):
        i = jnp.arange(start, start + chunk, dtype=jnp.int32)
        h = jax.lax.bitcast_convert_type(i, jnp.uint32) << 8
        ang, *got = (np.asarray(a, np.float64) for a in pairs(h))
        true = (np.sin(ang), np.cos(ang)) * 2
        worst = np.maximum(worst, [np.max(np.abs(g - t))
                                   for g, t in zip(got, true)])
    assert worst[:2].max() <= K.SINCOS_ERR
    assert worst[2:].max() <= K.PLATFORM_SINCOS_ERR
