"""jit'd public wrappers for the fused approximate-channel kernel.

``approx_channel`` pads arbitrary-length vectors to the tile size and calls
the Pallas kernel (compiled on TPU, interpret mode on CPU; see
``default_interpret``).
``approx_channel_transmit`` adapts it to the ``TransportConfig`` interface so
``transport.transmit_flat(..., use_kernel=True)`` routes through the kernel.
``approx_channel_batch`` / ``approx_channel_transmit_batch`` are the
multi-client variants backing ``transport.transmit_batch``: a ``(C, N)``
payload matrix through the 2-D-grid kernel in one launch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.approx_channel import (
    approx_channel_batch_aggregate_pallas,
    approx_channel_batch_pallas,
    approx_channel_pallas,
)

__all__ = [
    "approx_channel",
    "approx_channel_batch",
    "approx_channel_batch_aggregate",
    "approx_channel_transmit",
    "approx_channel_transmit_batch",
    "approx_channel_transmit_batch_aggregate",
    "default_interpret",
    "padded_words",
    "donation_supported",
]


def default_interpret() -> bool:
    """Compiled Mosaic kernels on TPU, the Pallas interpreter on CPU.

    Any other backend raises: the kernels are written for the TPU, and the
    interpreter is a CPU test vehicle, not a fallback for another device.
    """
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the approx-channel kernels run on TPU (compiled) or CPU "
            f"(interpret mode), not on {backend!r}")
    return backend == "cpu"


def donation_supported() -> bool:
    """Whether ``donate_argnums`` actually releases buffers on this backend.

    XLA CPU ignores donation (and warns); only gpu/tpu honour it, so the
    ``donate=`` fast paths fall back to the plain jit twin elsewhere.
    """
    return jax.default_backend() in ("gpu", "tpu")


@functools.partial(
    jax.jit,
    static_argnames=(
        "bits_per_symbol", "fading", "fade_block", "clamp_mask",
        "block_words", "word_bits", "interpret",
    ),
)
def approx_channel(
    x: jax.Array,
    seed: jax.Array,
    noise_power,
    large_scale_gain,
    *,
    bits_per_symbol: int = 2,
    fading: str = "rayleigh",
    fade_block: int = 64,
    clamp_mask: int = 0xBFFFFFFF,
    block_words: int = 1024,
    word_bits: int = 32,
    interpret: bool = True,
):
    """Arbitrary-length wrapper: pads with zeros to a tile multiple.

    The kernel counts bit errors over the first ``N`` words only, so
    ``bit_errors`` covers the true payload and not the padding.
    """
    n = x.shape[0]
    pad = (-n) % block_words
    wire = jnp.bfloat16 if word_bits == 16 else jnp.float32
    xp = jnp.pad(x.astype(wire), (0, pad))
    x_hat, errs = approx_channel_pallas(
        xp,
        jnp.asarray(seed),
        jnp.asarray(noise_power, jnp.float32),
        jnp.asarray(large_scale_gain, jnp.float32),
        bits_per_symbol=bits_per_symbol,
        fading=fading,
        fade_block=fade_block,
        clamp_mask=clamp_mask,
        block_words=block_words,
        word_bits=word_bits,
        valid_words=n,
        interpret=interpret,
    )
    return x_hat[:n], errs


def _transport_kernel_params(cfg):
    """(wire_bits, clamp_mask, bits_per_symbol) for a TransportConfig."""
    from repro.core import float_codec as fc

    wb = 16 if cfg.wire_dtype == "bfloat16" else 32
    if cfg.mode != "approx":
        clamp_mask = 0xFFFFFFFF
    elif wb == 16:
        clamp_mask = fc.exponent_clamp_mask16(cfg.clamp_bound)
    else:
        clamp_mask = fc.exponent_clamp_mask(cfg.clamp_bound)
    return wb, clamp_mask, cfg.scheme.bits_per_symbol


def _seed_from_key(key: jax.Array) -> jax.Array:
    return jax.random.randint(
        key, (), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32
    ).astype(jnp.uint32)


def approx_channel_transmit(x: jax.Array, key: jax.Array, cfg, *, snr_db=None):
    """TransportConfig adapter (mode='approx'|'naive' with use_kernel).

    ``snr_db`` optionally overrides ``cfg.channel.snr_db`` (traced scalar ok).
    """
    from repro.core import channel as channel_lib
    from repro.core import transport as transport_lib

    ch = cfg.channel
    seed = _seed_from_key(key)
    wb, clamp_mask, k = _transport_kernel_params(cfg)
    npow = (ch.noise_power if snr_db is None
            else channel_lib.noise_power_for(ch, snr_db))
    x_hat, errs = approx_channel(
        x,
        seed,
        npow,
        ch.large_scale_gain,
        bits_per_symbol=k,
        fading=ch.fading,
        fade_block=ch.block_len,
        clamp_mask=clamp_mask,
        word_bits=wb,
        interpret=default_interpret(),
    )
    n = x.shape[0]
    stats = transport_lib._stats(n * (wb // k), 1, errs, n * wb, n * wb)
    return x_hat.astype(jnp.float32), stats


def _batch_impl(
    x: jax.Array,
    seeds: jax.Array,
    noise_powers,
    large_scale_gains,
    *,
    bits_per_symbol: int = 2,
    fading: str = "rayleigh",
    fade_block: int = 64,
    clamp_mask: int = 0xBFFFFFFF,
    block_words: int = 1024,
    word_bits: int = 32,
    interpret: bool = True,
    num_active=None,
):
    """Batched arbitrary-length wrapper: pads ``(C, N)`` payloads along the
    payload dim to a tile multiple, one fused kernel launch for all clients.
    Returns ``(x_hat (C, N), bit_errors (C,) int32)``; errors are counted
    on the first ``N`` words only (see ``approx_channel``).
    ``num_active`` masks the tail client rows (partial-batch grid): masked
    rows cost no PHY work and return zeros — the adaptive dispatch's padded
    buckets discard them."""
    c, n = x.shape
    pad = (-n) % block_words
    wire = jnp.bfloat16 if word_bits == 16 else jnp.float32
    xp = jnp.pad(x.astype(wire), ((0, 0), (0, pad)))
    x_hat, errs = approx_channel_batch_pallas(
        xp,
        jnp.asarray(seeds),
        jnp.asarray(noise_powers, jnp.float32),
        jnp.asarray(large_scale_gains, jnp.float32),
        bits_per_symbol=bits_per_symbol,
        fading=fading,
        fade_block=fade_block,
        clamp_mask=clamp_mask,
        block_words=block_words,
        word_bits=word_bits,
        valid_words=n,
        interpret=interpret,
        num_active=num_active,
    )
    return x_hat[:, :n], errs


_BATCH_STATIC = (
    "bits_per_symbol", "fading", "fade_block", "clamp_mask",
    "block_words", "word_bits", "interpret",
)
approx_channel_batch = jax.jit(_batch_impl, static_argnames=_BATCH_STATIC)
# Donated twin (see approx_channel_batch_aggregate below): the uplink payload
# buffer is released into the launch on backends that honour donation.
_batch_donated = jax.jit(
    _batch_impl, static_argnames=_BATCH_STATIC, donate_argnums=(0,))


def approx_channel_transmit_batch(x: jax.Array, keys: jax.Array, cfg,
                                  snr_db=None, *, num_active=None,
                                  donate: bool = False):
    """Batched TransportConfig adapter behind ``transport.transmit_batch``.

    Args:
      x: ``(C, N)`` float32 payload matrix.
      keys: ``(C, key_size)`` per-client keys (the fold_in schedule built by
        ``transport.client_keys`` — each row seeds that client's kernel RNG
        exactly as ``approx_channel_transmit`` would).
      cfg: TransportConfig with mode 'approx'|'naive'.
      snr_db: optional ``(C,)`` per-client SNR; ``None`` = config scalar.
      num_active: optional scalar — compute only the first ``num_active``
        client rows (masked partial-batch grid for padded adaptive buckets).
      donate: release the ``x`` buffer into the launch (donated jit twin) on
        backends that honour donation.

    Returns ``(x_hat (C, N) float32, TxStats with (C,) fields)``.
    """
    from repro.core import channel as channel_lib
    from repro.core import transport as transport_lib

    ch = cfg.channel
    c, n = x.shape
    seeds = jax.vmap(_seed_from_key)(keys)
    wb, clamp_mask, k = _transport_kernel_params(cfg)
    if snr_db is None:
        npow = jnp.full((c,), ch.noise_power, jnp.float32)
    else:
        npow = channel_lib.noise_power_for(ch, snr_db)
    gains = jnp.full((c,), ch.large_scale_gain, jnp.float32)
    batch_fn = (_batch_donated if donate and donation_supported()
                else approx_channel_batch)
    x_hat, errs = batch_fn(
        x,
        seeds,
        npow,
        gains,
        bits_per_symbol=k,
        fading=ch.fading,
        fade_block=ch.block_len,
        clamp_mask=clamp_mask,
        word_bits=wb,
        interpret=default_interpret(),
        num_active=num_active,
    )
    # Counts as floats: a 568M-float payload has 9.1e9 symbols.
    ones = jnp.ones((c,), jnp.float32)
    stats = transport_lib.TxStats(
        ones * float(n * (wb // k)), ones, errs.astype(jnp.float32),
        ones * float(n * wb), bits_on_air=ones * float(n * wb),
    )
    return x_hat.astype(jnp.float32), stats


def _batch_aggregate_impl(
    x: jax.Array,
    seeds: jax.Array,
    noise_powers,
    large_scale_gains,
    weights,
    *,
    bits_per_symbol: int = 2,
    fading: str = "rayleigh",
    fade_block: int = 64,
    clamp_mask: int = 0xBFFFFFFF,
    block_words: int = 1024,
    word_bits: int = 32,
    interpret: bool = True,
    num_active=None,
    acc=None,
):
    """Fused batch + in-kernel weighted aggregation over the client axis.

    Pads ``(C, N)`` payloads to a tile multiple and runs the aggregating
    kernel: the per-client demapped payload never materializes in HBM — the
    only payload-sized output is the f32 accumulator, which starts from the
    ``(N,)`` running aggregate ``acc`` when one is given. Bit errors are
    masked to the first ``N`` words inside the kernel (``valid_words``).
    Returns ``(agg (N,) float32, bit_errors (C,) int32, exact_tiles (C,)
    int32)``, the last the kernel's count of each client's tiles that took
    its exact path.
    """
    c, n = x.shape
    pad = (-n) % block_words
    wire = jnp.bfloat16 if word_bits == 16 else jnp.float32
    xp = jnp.pad(x.astype(wire), ((0, 0), (0, pad)))
    # An aggregate of the padded length stays padded: a round that streams
    # waves carries it so, and copies nothing each wave.
    padded = acc is not None and acc.shape[0] == n + pad
    if acc is not None and not padded:
        acc = jnp.pad(acc, (0, pad))
    agg, errs, exact = approx_channel_batch_aggregate_pallas(
        xp,
        jnp.asarray(seeds),
        jnp.asarray(noise_powers, jnp.float32),
        jnp.asarray(large_scale_gains, jnp.float32),
        jnp.asarray(weights, jnp.float32),
        bits_per_symbol=bits_per_symbol,
        fading=fading,
        fade_block=fade_block,
        clamp_mask=clamp_mask,
        block_words=block_words,
        word_bits=word_bits,
        valid_words=n,
        interpret=interpret,
        num_active=num_active,
        acc=acc,
    )
    return (agg if padded else agg[:n]), errs, exact


_AGG_STATIC = (
    "bits_per_symbol", "fading", "fade_block", "clamp_mask",
    "block_words", "word_bits", "interpret",
)
approx_channel_batch_aggregate = jax.jit(
    _batch_aggregate_impl, static_argnames=_AGG_STATIC)
# Donated twin: same impl, uplink payload buffer released to the output
# allocator. Only meaningful at an outermost jit boundary on gpu/tpu
# (donation_supported); callers pick between the twins.
_batch_aggregate_donated = jax.jit(
    _batch_aggregate_impl, static_argnames=_AGG_STATIC, donate_argnums=(0,))


def padded_words(n: int, block_words: int = 1024) -> int:
    """A payload's length in the kernel's tiles: ``n`` rounded up to
    ``block_words``."""
    return n + (-n) % block_words


def approx_channel_transmit_batch_aggregate(
        x: jax.Array, keys: jax.Array, cfg, snr_db, weights, *,
        num_active=None, donate: bool = False, acc=None):
    """Batched TransportConfig adapter with in-kernel aggregation.

    Same contract as ``approx_channel_transmit_batch`` except the per-client
    demapped rows collapse to ``acc + sum_c weights[c] * x_hat[c]`` inside
    the kernel (weights are used as given — normalize first; ``acc``
    defaults to zero, and one of :func:`padded_words` length comes back
    at that length). ``donate=True`` releases the ``x`` buffer on
    backends that honour donation.

    Returns ``(agg (N,) float32, TxStats with (C,) fields)``; the stats
    carry the kernel's ``exact_tiles``.
    """
    from repro.core import channel as channel_lib
    from repro.core import transport as transport_lib

    ch = cfg.channel
    c, n = x.shape
    seeds = jax.vmap(_seed_from_key)(keys)
    wb, clamp_mask, k = _transport_kernel_params(cfg)
    if snr_db is None:
        npow = jnp.full((c,), ch.noise_power, jnp.float32)
    else:
        npow = channel_lib.noise_power_for(ch, snr_db)
    gains = jnp.full((c,), ch.large_scale_gain, jnp.float32)
    fn = (_batch_aggregate_donated if donate and donation_supported()
          else approx_channel_batch_aggregate)
    agg, errs, exact = fn(
        x,
        seeds,
        npow,
        gains,
        weights,
        bits_per_symbol=k,
        fading=ch.fading,
        fade_block=ch.block_len,
        clamp_mask=clamp_mask,
        word_bits=wb,
        interpret=default_interpret(),
        num_active=num_active,
        acc=acc,
    )
    # Counts as floats: a 568M-float payload has 9.1e9 symbols.
    ones = jnp.ones((c,), jnp.float32)
    stats = transport_lib.TxStats(
        ones * float(n * (wb // k)), ones, errs.astype(jnp.float32),
        ones * float(n * wb), bits_on_air=ones * float(n * wb),
        exact_tiles=exact,
    )
    return agg, stats
