"""The channel RNG's symbol counter past 2^32: a payload of more than 2^32
symbols (268M float32 words at QPSK) folds each further 2^32 symbols into a
new seed, and every symbol below 2^32 keeps the stream it always had."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.approx_channel import (
    approx_channel_batch_aggregate_pallas, approx_channel_batch_pallas)
from repro.kernels import ref as kref

SEED = jnp.uint32(0x1234567)
NOISE, GAIN = jnp.float32(1e-4), jnp.float32(1e-3)


def _parent_oracle(x, seed, *, bits_per_symbol=2, block_words=1024):
    """The oracle as it was with a 32-bit counter: one seed, a wrapping
    int32 base."""
    s_per_word = 32 // bits_per_symbol
    u = kref.wire_words(x, 32)
    tiles = u.reshape(-1, block_words // kref.LANES, kref.LANES)
    base = jnp.arange(tiles.shape[0], dtype=jnp.int32) * (block_words * s_per_word)
    u_hat = jax.vmap(lambda t, b: kref.channel_tile(
        t, seed, b, NOISE, GAIN, bits_per_symbol=bits_per_symbol,
        fading="rayleigh", fade_block=64))(tiles, base)
    return u_hat.reshape(-1)


def _tile(seed, base):
    u = jnp.full((8, kref.LANES), 0x3C000000, jnp.uint32)
    return kref.channel_tile(u, seed, jnp.int32(base), NOISE, GAIN,
                             bits_per_symbol=2, fading="rayleigh",
                             fade_block=64)


def test_segment_zero_keeps_the_seed_and_the_rest_get_new_streams():
    assert int(kref.segment_seed(SEED, 0)) == int(SEED)
    seeds = {int(kref.segment_seed(SEED, s)) for s in range(4)}
    assert len(seeds) == 4
    # Base 2^32 (segment 1, low word 0) is not base 0's stream.
    wrapped = _tile(kref.segment_seed(SEED, 1), 0)
    assert not np.array_equal(np.asarray(wrapped), np.asarray(_tile(SEED, 0)))


@pytest.mark.parametrize("tiles, want", [
    (1, (1, 0)), (2**18, (1, 0)), (2**18 + 1, (2, 18)), (555_163, (3, 18))])
def test_segments_of_a_qpsk_float32_payload(tiles, want):
    # 16 symbols a word, 1024 words a tile: 2^18 tiles fill 2^32 symbols.
    assert kref.segments(tiles, 1024, 16) == want


def test_a_layout_that_would_straddle_a_segment_is_refused():
    with pytest.raises(ValueError, match="power-of-two"):
        kref.segments(2**20, 1536, 16)
    with pytest.raises(ValueError, match="fade_block"):
        kref.segments(2**19, 1024, 16, "block_rayleigh", 48)
    # Below 2^32 symbols any layout is as before.
    assert kref.segments(1000, 1536, 16, "block_rayleigh", 48) == (1, 0)


@pytest.mark.parametrize("bits_per_symbol", [2, 4])
def test_below_two_to_the_32_the_oracle_gives_the_parents_bytes(
        bits_per_symbol):
    x = jax.random.normal(jax.random.PRNGKey(0), (5 * 1024,)) * 0.05
    got, _ = kref.ref_approx_channel(x, SEED, NOISE, GAIN,
                                     bits_per_symbol=bits_per_symbol,
                                     clamp_mask=0xFFFFFFFF)
    want = _parent_oracle(x, SEED, bits_per_symbol=bits_per_symbol)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want))


def test_the_kernel_picks_each_tiles_segment_seed_as_the_oracle(monkeypatch):
    # With the segment width cut to 2^16 symbols (4 QPSK tiles), a small
    # payload spans several segments: the kernel's per-tile seed table and
    # the oracle agree, and the first segment is the parent's stream.
    monkeypatch.setattr(kref, "SEGMENT_BITS", 16)
    n, c = 11 * 1024, 3
    x = jax.random.normal(jax.random.PRNGKey(1), (c, n)) * 0.05
    seeds = jnp.arange(1, c + 1, dtype=jnp.uint32) * jnp.uint32(0x9E3779B1)
    noise = jnp.full((c,), NOISE)
    gain = jnp.full((c,), GAIN)
    hat, errs = approx_channel_batch_pallas(
        x, seeds, noise, gain, clamp_mask=0xFFFFFFFF, interpret=True)
    agg, agg_errs, _ = approx_channel_batch_aggregate_pallas(
        x, seeds, noise, gain, jnp.full((c,), 0.5), clamp_mask=0xFFFFFFFF,
        interpret=True)
    total = jnp.zeros((n,), jnp.float32)
    for i in range(c):
        want, want_errs = kref.ref_approx_channel(
            x[i], seeds[i], NOISE, GAIN, clamp_mask=0xFFFFFFFF)
        np.testing.assert_array_equal(np.asarray(hat[i]).view(np.uint32),
                                      np.asarray(want).view(np.uint32))
        assert int(errs[i]) == int(want_errs) == int(agg_errs[i])
        total = total + jnp.float32(0.5) * want
        parent = _parent_oracle(x[i], seeds[i])
        same = np.asarray(want).view(np.uint32) == np.asarray(parent)
        assert same[:4 * 1024].all() and not same[4 * 1024:].all()
    np.testing.assert_array_equal(np.asarray(agg).view(np.uint32),
                                  np.asarray(total).view(np.uint32))


def test_the_resident_counter_block_counts_as_the_per_tile_blocks(
        monkeypatch):
    # A launch past PER_TILE_COUNTER_BYTES sums every tile's counts into
    # one resident block: the same counts and words as the oracle, with
    # and without a running aggregate to start from.
    import importlib

    kernel = importlib.import_module("repro.kernels.approx_channel")
    monkeypatch.setattr(kernel, "PER_TILE_COUNTER_BYTES", 0)
    n, c = 13 * 1024, 5
    x = jax.random.normal(jax.random.PRNGKey(2), (c, n)) * 0.05
    seeds = jnp.arange(7, 7 + c, dtype=jnp.uint32) * jnp.uint32(0x85EBCA6B)
    noise = jnp.full((c,), NOISE)
    gain = jnp.full((c,), GAIN)
    weights = jnp.full((c,), 0.25)
    acc = jax.random.normal(jax.random.PRNGKey(3), (n,))
    hat, errs = approx_channel_batch_pallas(
        x, seeds, noise, gain, clamp_mask=0xFFFFFFFF, interpret=True)
    agg, agg_errs, _ = approx_channel_batch_aggregate_pallas(
        x, seeds, noise, gain, weights, clamp_mask=0xFFFFFFFF,
        interpret=True, acc=acc)
    total = acc
    for i in range(c):
        want, want_errs = kref.ref_approx_channel(
            x[i], seeds[i], NOISE, GAIN, clamp_mask=0xFFFFFFFF)
        np.testing.assert_array_equal(np.asarray(hat[i]).view(np.uint32),
                                      np.asarray(want).view(np.uint32))
        assert int(errs[i]) == int(want_errs) == int(agg_errs[i]) > 0
        total = total + jnp.float32(0.25) * want
    np.testing.assert_array_equal(np.asarray(agg).view(np.uint32),
                                  np.asarray(total).view(np.uint32))
