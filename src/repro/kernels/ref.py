"""Pure-jnp oracle for the fused approximate-channel kernel.

Implements EXACTLY the same math as ``approx_channel.py`` — including the
counter-based RNG (murmur3-finalizer hash + Box–Muller) — so kernel-vs-ref
tests are bit-exact, not just statistically close. The reference materializes
every intermediate (symbols, complex stream, noise) in HBM; the kernel fuses
the whole pipeline in VMEM. Shared helpers live here and are imported by the
kernel body (they are plain jnp and trace fine inside ``pallas_call``).

Pipeline (paper Sec. IV, per tile of ``block_words`` float32 words):

    bitcast -> MSB-first k-bit symbols -> block-local row/column interleave
    -> Gray square-QAM modulate -> Rayleigh/AWGN channel (counter RNG)
    -> coherent equalize -> closed-form ML demod -> de-interleave
    -> reassemble words -> exponent-bit clamp -> bitcast back.

Returns ``(x_hat, bit_errors)`` where bit_errors counts residual flipped
bits vs. the transmitted words (post-clamp).

The RNG counter is a payload's global symbol index. Its low 32 bits are the
hash input; the words above them (``segment``: the index over 2^32, one
per 2^32 symbols, 268M float32 words at QPSK) go into the seed,
``seed ^ fmix32(segment * golden)``, and only where the segment is above
0. So every payload below 2^32 symbols draws exactly the stream it always
did, and a longer one never repeats its noise. A tile holds a power-of-two
number of symbols, so no tile straddles a segment; the per-tile seed is a
scalar, and no per-symbol work is added.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["ref_approx_channel"]

_U32 = jnp.uint32
LANES = 128  # words per tile row: the TPU vector lane width
_TWO_PI = 6.283185307179586

# Streams for the counter RNG (arbitrary odd constants).
_STREAM_NOISE = 0x9E3779B9
_STREAM_FADE = 0x7FEB352D
_STREAM_PHASE = 0x68E31DA4


def fmix32(x: jax.Array) -> jax.Array:
    """murmur3 finalizer — a well-mixed 32-bit hash."""
    x = x.astype(_U32)
    x = x ^ (x >> 16)
    x = x * _U32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * _U32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def hash_u32(seed: jax.Array, idx: jax.Array, stream: int) -> jax.Array:
    return fmix32(seed.astype(_U32) ^ fmix32(idx.astype(_U32) * _U32(0x9E3779B9) + _U32(stream)))


def uniform01(h: jax.Array) -> jax.Array:
    """uint32 hash -> uniform float32 in (0, 1].

    The 24-bit integer goes through int32 on its way to float32 (exact below
    2^24): Mosaic has no uint32 -> float32 conversion."""
    h24 = jax.lax.bitcast_convert_type(h >> 8, jnp.int32)
    return h24.astype(jnp.float32) * jnp.float32(1.0 / 16777216.0) + jnp.float32(2.0**-25)


def gauss_pair(seed: jax.Array, idx: jax.Array, stream: int):
    """Two iid N(0,1) float32 via Box-Muller on counter-RNG uniforms."""
    u1 = uniform01(hash_u32(seed, idx, stream))
    u2 = uniform01(hash_u32(seed, idx, stream ^ _STREAM_PHASE))
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    ang = jnp.float32(_TWO_PI) * u2
    return r * jnp.cos(ang), r * jnp.sin(ang)


def gray_encode(n):
    n = n.astype(_U32)
    return n ^ (n >> 1)


def gray_decode(g):
    g = g.astype(_U32)
    for s in (1, 2, 4):
        g = g ^ (g >> s)
    return g


def _popcount(x):
    x = x.astype(_U32)
    x = x - ((x >> 1) & _U32(0x55555555))
    x = (x & _U32(0x33333333)) + ((x >> 2) & _U32(0x33333333))
    x = (x + (x >> 4)) & _U32(0x0F0F0F0F)
    return (x * _U32(0x01010101)) >> 24


def _u32(x: jax.Array) -> jax.Array:
    """Reinterpret non-negative int32 as uint32 (no conversion op)."""
    return jax.lax.bitcast_convert_type(x, _U32)


def _i32(x: jax.Array) -> jax.Array:
    """Reinterpret uint32 as int32: Mosaic converts and reduces only signed
    integers, and every value routed through here is below 2^31."""
    return jax.lax.bitcast_convert_type(x, jnp.int32)


SEGMENT_BITS = 32  # the hash counter's width: symbols per seed segment 2^32


def segment_seed(seed: jax.Array, segment) -> jax.Array:
    """A tile's seed: the client's seed in segment 0, else folded with the
    segment (its symbol counter's bits above 32)."""
    seed = seed.astype(_U32)
    segment = jnp.asarray(segment, _U32)
    return jnp.where(segment > 0, seed ^ fmix32(segment * _U32(0x9E3779B9)),
                     seed)


def segments(tiles: int, block_words: int, s_per_word: int,
             fading: str = "rayleigh", fade_block: int = 64) -> tuple:
    """``(n_segments, tile_shift)``: how many seed segments ``tiles`` tiles
    span, and the shift from a tile index to its segment. Raises where a
    tile or a fading block would straddle a segment boundary."""
    per_tile = block_words * s_per_word
    n = -(-(tiles * per_tile) // (1 << SEGMENT_BITS))
    if n <= 1:
        return 1, 0
    if per_tile & (per_tile - 1):
        raise ValueError(
            f"a payload past 2^32 symbols needs a power-of-two number of "
            f"symbols per tile; block_words={block_words} gives {per_tile}")
    if fading == "block_rayleigh" and fade_block & (fade_block - 1):
        raise ValueError(
            f"a payload past 2^32 symbols needs a power-of-two fade_block; "
            f"got {fade_block}")
    return n, SEGMENT_BITS - (per_tile.bit_length() - 1)


def tile_word_index(shape) -> jax.Array:
    """Row-major word index within a ``(rows, 128)`` tile (int32)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 2)
    lanes = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return rows * shape[-1] + lanes


def channel_tile(
    u: jax.Array,  # (R, 128) uint32 words of one tile (low word_bits used)
    seed: jax.Array,  # () uint32
    base_sym: jax.Array,  # () int32 — global index of this tile's 1st symbol
    noise_power: jax.Array,  # () f32
    large_scale_gain: jax.Array,  # () f32
    *,
    bits_per_symbol: int,
    fading: str,
    fade_block: int,
    word_bits: int = 32,
) -> jax.Array:
    """Shared tile body: words -> noisy received words (pre-clamp).

    A tile is ``R x 128`` words in row-major order (word ``w`` sits at row
    ``w // 128``, lane ``w % 128``), the layout of one VMEM block. Symbol
    ``j`` (MSB-first) of word ``w`` is transmitted at tile position
    ``j * R * 128 + w``: the block-local row/column interleave.

    ``word_bits=16`` implements the bf16 wire format (same exponent layout
    as f32, so the clamp prior transfers; half the symbols per word)."""
    k = bits_per_symbol
    p = k // 2
    L = 1 << p
    bw = u.size
    s_per_word = word_bits // k
    amp = math.sqrt(3.0 / (2.0 * (L * L - 1)))
    shape = (s_per_word,) + u.shape

    # words -> symbols, MSB-first, already in transmit order: (S, R, 128)
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    shifts = _u32(word_bits - k * (j + 1))
    stream = (jnp.broadcast_to(u[None], shape) >> shifts) & _U32((1 << k) - 1)

    # split to Gray axis bits (alternating I/Q allocation, MSB-first)
    gi = jnp.zeros_like(stream)
    gq = jnp.zeros_like(stream)
    for b in range(p):
        bi = (stream >> _U32(k - 1 - 2 * b)) & _U32(1)
        bq = (stream >> _U32(k - 2 - 2 * b)) & _U32(1)
        gi = gi | (bi << _U32(p - 1 - b))
        gq = gq | (bq << _U32(p - 1 - b))
    li = _i32(gray_decode(gi)).astype(jnp.float32)
    lq = _i32(gray_decode(gq)).astype(jnp.float32)
    s_re = (2.0 * li - (L - 1)) * jnp.float32(amp)
    s_im = (2.0 * lq - (L - 1)) * jnp.float32(amp)

    # global symbol index in transmit order; int32 arithmetic wraps to the
    # same bits as uint32 arithmetic mod 2^32
    gidx = _u32(base_sym + j * bw + tile_word_index(shape))

    # channel: r = c s + n ; receiver equalizes y = s + n/c
    n_re, n_im = gauss_pair(seed, gidx, _STREAM_NOISE)
    nscale = jnp.sqrt(noise_power * 0.5)
    n_re = n_re * nscale
    n_im = n_im * nscale
    if fading == "awgn":
        c_re = jnp.sqrt(large_scale_gain) * jnp.ones_like(s_re)
        c_im = jnp.zeros_like(s_re)
    else:
        fidx = gidx // _U32(fade_block) if fading == "block_rayleigh" else gidx
        h_re, h_im = gauss_pair(seed, fidx, _STREAM_FADE)
        hs = jnp.sqrt(jnp.float32(0.5))
        c_re = jnp.sqrt(large_scale_gain) * h_re * hs
        c_im = jnp.sqrt(large_scale_gain) * h_im * hs
    c2 = jnp.maximum(c_re * c_re + c_im * c_im, jnp.float32(1e-20))
    # n / c = n * conj(c) / |c|^2
    y_re = s_re + (n_re * c_re + n_im * c_im) / c2
    y_im = s_im + (n_im * c_re - n_re * c_im) / c2

    # closed-form ML demod per axis
    inv = jnp.float32(1.0 / amp)

    def axis_level(x):
        lvl = jnp.round((x * inv + (L - 1)) * 0.5)
        return _u32(jnp.clip(lvl, 0, L - 1).astype(jnp.int32))

    gi_hat = gray_encode(axis_level(y_re))
    gq_hat = gray_encode(axis_level(y_im))
    rx = jnp.zeros_like(stream)
    for b in range(p):
        bi = (gi_hat >> _U32(p - 1 - b)) & _U32(1)
        bq = (gq_hat >> _U32(p - 1 - b)) & _U32(1)
        rx = rx | (bi << _U32(k - 1 - 2 * b))
        rx = rx | (bq << _U32(k - 2 - 2 * b))

    # de-interleave: reassemble each word from its disjoint symbol fields
    u_hat = rx[0] << _U32(word_bits - k)
    for s in range(1, s_per_word):
        u_hat = u_hat | (rx[s] << _U32(word_bits - k * (s + 1)))
    return u_hat


def bit_flips(u: jax.Array, u_hat: jax.Array) -> jax.Array:
    """Per-word count of flipped bits, int32."""
    return _i32(_popcount(u ^ u_hat))


def wire_words(x: jax.Array, word_bits: int) -> jax.Array:
    """Wire floats -> uint32 words (bf16 zero-extended when word_bits=16)."""
    if word_bits == 16:
        return jax.lax.bitcast_convert_type(
            x.astype(jnp.bfloat16), jnp.uint16).astype(_U32)
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), _U32)


def wire_values(u: jax.Array, word_bits: int) -> jax.Array:
    """uint32 words -> wire floats (bf16 when word_bits=16, else f32)."""
    if word_bits == 16:
        return jax.lax.bitcast_convert_type(u.astype(jnp.uint16), jnp.bfloat16)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def ref_approx_channel(
    x: jax.Array,
    seed: jax.Array,
    noise_power: jax.Array,
    large_scale_gain: jax.Array,
    *,
    bits_per_symbol: int = 2,
    fading: str = "rayleigh",
    fade_block: int = 64,
    clamp_mask: int = 0xBFFFFFFF,
    block_words: int = 1024,
    word_bits: int = 32,
    valid_words: int | None = None,
):
    """Oracle for the fused kernel. x: (N,) f32 (or bf16 when word_bits=16),
    N % block_words == 0, block_words % 128 == 0. Bit errors are counted on
    the first ``valid_words`` words (``None`` = all N), as the kernel does."""
    n = x.shape[0]
    if n % block_words or block_words % LANES:
        raise ValueError(
            f"N={n} must be a multiple of block_words={block_words}, itself "
            f"a multiple of {LANES}")
    s_per_word = word_bits // bits_per_symbol
    u = wire_words(x, word_bits)
    tiles = u.reshape(-1, block_words // LANES, LANES)
    _, shift = segments(tiles.shape[0], block_words, s_per_word, fading,
                        fade_block)
    index = jnp.arange(tiles.shape[0], dtype=jnp.int32)
    base = index * (block_words * s_per_word)  # wraps mod 2^32
    seeds = segment_seed(seed, index >> shift if shift else 0)
    seeds = jnp.broadcast_to(seeds, index.shape)

    def per_tile(tile, b, sd):
        return channel_tile(
            tile, sd, b,
            jnp.float32(noise_power), jnp.float32(large_scale_gain),
            bits_per_symbol=bits_per_symbol, fading=fading, fade_block=fade_block,
            word_bits=word_bits,
        )

    u_hat = jax.vmap(per_tile)(tiles, base, seeds).reshape(-1)
    u_hat = u_hat & _U32(clamp_mask)
    errs = jnp.sum(bit_flips(u, u_hat)[:valid_words])
    return wire_values(u_hat, word_bits), errs
