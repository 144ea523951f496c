"""Fused Pallas TPU kernel for the approximate-channel gradient pipeline.

The paper's receive pipeline is elementwise bit manipulation over every
gradient float. A layer-by-layer jnp implementation (see ``ref.py``) streams
each intermediate through HBM:

    u32 words (4 B) -> symbols (32/k x 4 B) -> complex stream (32/k x 8 B)
    -> noise/fading (2 x that) -> rx symbols -> words

i.e. >= 36 B of HBM traffic per 4 B gradient at QPSK — memory-bound by 9x
more traffic than necessary. This kernel fuses the whole chain inside one
VMEM tile: 4 B in, 4 B out, plus one error-counter block per launch.
Channel noise and Rayleigh fading are generated *inside* the kernel from a
counter-based RNG (murmur3-finalizer hash + Box-Muller over the global
symbol index), so no randomness is streamed from HBM. On real TPUs
``pltpu.prng_random_bits`` could replace the hash; we keep the hash so
interpret-mode CPU validation is bit-exact against the oracle.

Tiling: the ``(C, N)`` payload is viewed as ``(C, N / 128, 128)`` and cut
into ``(block_words / 128, 128)`` tiles (default 1024 words = 8 sublanes x
128 lanes, one f32 vreg tile). A grid step transmits ``GROUP_TILES``
consecutive tiles of one client over a ``(groups, clients)`` grid (a last
group may hold fewer); the single-client entry point is the C=1 view. Each tile expands to
``(32/k, 8, 128)`` symbols in VMEM — at QPSK that is 16 x 1024 x 4 B x ~6
live arrays ~ 400 KiB, comfortably inside the ~16 MiB v5e VMEM budget; the
MXU is not used (this is a VPU/bit-op kernel). The symbol interleaver is
block-local (row/column within the tile), matching one PHY frame per tile.

Words enter and leave the kernel as uint32 on both wires: the bf16 wire is
widened and narrowed by XLA around the launch, so Mosaic sees 32-bit
vectors only (the bf16 wire then moves 4 B per word through the kernel, as
the f32 wire does).

The RNG counter is the symbol's index in its client's payload. Its low 32
bits feed the hash, and each further 2^32 symbols (268M float32 words at
QPSK) is a segment whose seed is the client's seed folded with the segment
number (``ref.segment_seed``); segment 0 keeps the client's seed, so a
payload below 2^32 symbols draws the stream it always did. A tile holds a
power-of-two number of symbols and never straddles a segment, so the seed
is picked per tile from a ``(C, segments)`` scalar table, with no work per
symbol.

Per-client seeds / noise / gain (and aggregation weight) are scalar-prefetch
operands in SMEM, indexed by the client grid coordinate. Bit-error counters
are lane-dense ``(rows, 128)`` int32 blocks, client ``c``'s count at flat
position ``c``. A launch whose per-group blocks stay under
``PER_TILE_COUNTER_BYTES`` writes one block a group (``groups x C`` words
in HBM, summed after the launch); its group axis is ``"parallel"`` and its
client axis ``"arbitrary"``, because the counter block (and the fused
kernel's accumulator) is revisited across the clients. A larger payload
(the per-group blocks of a 568M-float payload would take 284 MB) keeps
one block resident in VMEM for the whole grid and sums the groups into it, so
its counter output is ``C`` words; both its grid axes are then
``"arbitrary"``.

The fused kernel's accumulator starts at zero, or at a running aggregate
passed in and aliased to the output: a round that streams its cohort in
waves folds each wave into the aggregate of the waves before it, in client
order, so any wave size gives one launch's sum of the same payload bit for
bit.

Fast path and exact recompute. Compiled for the TPU, ``jnp.sin`` and
``jnp.cos`` keep a full large-argument reduction and take about 60% of the
kernel's vector work, though the Box-Muller angle ``2*pi*u2`` always lies
in (0, 2*pi]. So the kernel first computes every symbol plane of a tile
(:func:`_fast_tile`) with the oracle's own hash, uniforms, ``log``,
``sqrt``, equaliser
and demapper, in the oracle's order, and :func:`sincos_2pi` in place of
``jnp.sin`` / ``jnp.cos``. A received word depends on the equalised sample
``y`` only through the demapper's decision ``round(t)``,
``t = (y*inv + L-1) / 2``, so a fast word equals the oracle's wherever its
``t`` lie farther than a proven margin from every decision threshold (the
half-integers 0.5 .. L-1.5). In a tile with a ``t`` within the margin,
each symbol plane (a ``(rows, 128)`` slab of one symbol per word) holding
one is computed again with ``ref.gauss_pair``, the platform's sine and
cosine, and its symbols replace the fast ones: the words stay the
oracle's, bit for bit. The tiles that do so are counted. Whether a grid
step needs that path at all is one value the scalar unit reads from the
vector unit, and that read stalls the step; a step of ``GROUP_TILES``
tiles shares it.

The margin, per symbol, in units of ``t``, with ``u = 2^-24`` and
``Z = |n/c|`` (bounded from above by ``|q_re| + |q_im|``, ``q = y - s``):
``_MARGIN_Z * Z*inv + _MARGIN_L * L``. Derivation: let the sine/cosine be
off the true values of the f32 angle by at most ``e``, absolutely. Then
the noise ``n`` and the fade ``c`` are off by ``|n| (e + 2u)`` and
``|c| (e + 3u)`` a component (with the roundings of their products), the
numerator ``n . conj(c)`` by ``|n||c| (sqrt(2) (2e + 5u) + 2u)``, ``|c|^2``
by ``|c|^2 (2 sqrt(2) (e + 3u) + 2u)``, and the quotient by
``Z (4 sqrt(2) e + 20.6u)``, with a correctly rounded divide; ``s + q``
adds ``u (|s| + Z)``. The fast and the exact path are each that close to
the true ``y``, with ``e`` = ``SINCOS_ERR`` and ``PLATFORM_SINCOS_ERR``.
In units of ``t`` (``|s| * inv <= L - 1``; ``t`` is a monotone f32 map of
``y`` whose own rounding adds ``u (3L + 2 Z*inv)``) the two ``t`` then
differ by at most ``Z*inv (2 sqrt(2) (SINCOS_ERR + PLATFORM_SINCOS_ERR) +
23.6u) + u (4L - 1)``. ``_MARGIN_Z`` takes 28u for the 23.6u, which
leaves room for a divide up to 3 ulp off on each path; ``_MARGIN_L`` is
4u. ``SINCOS_ERR`` is asserted over all 2^24 angles by the tests;
``PLATFORM_SINCOS_ERR`` over the same angles on the CPU by the tests, and
on the chip, inside a kernel and in XLA, by ``chip_smoke.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

__all__ = [
    "approx_channel_pallas",
    "approx_channel_batch_pallas",
    "approx_channel_batch_aggregate_pallas",
]

_U32 = jnp.uint32


def approx_channel_pallas(
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
    interpret: bool = True,
):
    """Fused PHY pipeline. x: (N,) f32 (or bf16 with word_bits=16),
    N % block_words == 0. Returns (x_hat (N,), bit_errors () int32).

    One-client view of the batched kernel: the batch body restarts the
    symbol counter per client, so a C=1 grid is the single-client program.
    """
    x_hat, errs = approx_channel_batch_pallas(
        x[None, :],
        jnp.reshape(seed, (1,)),
        jnp.reshape(noise_power, (1,)),
        jnp.reshape(large_scale_gain, (1,)),
        bits_per_symbol=bits_per_symbol,
        fading=fading,
        fade_block=fade_block,
        clamp_mask=clamp_mask,
        block_words=block_words,
        word_bits=word_bits,
        valid_words=valid_words,
        interpret=interpret,
    )
    return x_hat[0], errs[0]


# Absolute error bounds, over every angle 2*pi*uniform01(h), of
# ``sincos_2pi`` and of the platform's ``jnp.sin`` / ``jnp.cos`` against the
# true sine and cosine of the f32 angle; and the margin they give (module
# docstring).
SINCOS_ERR = 2.0 * 2.0**-24
PLATFORM_SINCOS_ERR = 4.0 * 2.0**-24
_U = 2.0**-24
_MARGIN_Z = (2.0 * math.sqrt(2.0) * (SINCOS_ERR + PLATFORM_SINCOS_ERR) / _U
             + 28.0) * _U
_MARGIN_L = 4.0 * _U

# pi/2 in two parts: the first to 21 bits, so k * hi is exact for k <= 4.
_PIO2_HI = 1.570796012878418
_PIO2_LO = 3.1391647326017846e-07
# Minimax polynomials in z = r^2 of (sin(r) / r - 1) / z and
# (cos(r) - 1 + z / 2) / z^2, |r| <= pi/4 (Cephes sinf / cosf).
_SIN = (-1.6666654611e-1, 8.3321608736e-3, -1.9515295891e-4)
_COS = (4.166664568298827e-2, -1.388731625493765e-3, 2.443315711809948e-5)


def _poly(z, coeffs):
    acc = jnp.float32(coeffs[-1])
    for c in coeffs[-2::-1]:
        acc = jnp.float32(c) + z * acc
    return acc


def sincos_2pi(ang: jax.Array):
    """``(sin(ang), cos(ang))`` for f32 ``ang`` in (0, 2*pi], to within
    ``SINCOS_ERR`` absolutely: one quadrant reduction shared by both
    (``k``, ``ang * 2/pi`` rounded to the nearest integer, a two-constant
    Cody-Waite subtraction), the two polynomials in ``r^2``, then the
    quadrant's swap by selects and its signs into the sign bits.

    The clamp to the domain also keeps ``ang`` a rounded f32 value: a
    compiler that contracts the product that made ``ang`` into the
    reduction's subtraction (an fma, as LLVM does on the CPU) would
    otherwise reduce the unrounded angle, up to 4 x 2^-24 off."""
    ang = jnp.minimum(ang, jnp.float32(_ref._TWO_PI))
    q = (ang * jnp.float32(2.0 / math.pi) + jnp.float32(0.5)).astype(
        jnp.int32)
    k = q.astype(jnp.float32)
    r = ang - k * jnp.float32(_PIO2_HI)
    r = r - k * jnp.float32(_PIO2_LO)
    z = r * r
    sin_r = r + r * z * _poly(z, _SIN)
    cos_r = jnp.float32(1.0) - jnp.float32(0.5) * z + z * z * _poly(z, _COS)
    swap = (q & 1) != 0
    sin = jnp.where(swap, cos_r, sin_r)
    cos = jnp.where(swap, sin_r, cos_r)
    # The quadrant's signs, xor-ed into the sign bits.
    sin_sign = _ref._u32(q & 2) << _U32(30)
    cos_sign = _ref._u32((q + 1) & 2) << _U32(30)
    sin = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(sin, _U32) ^ sin_sign, jnp.float32)
    cos = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(cos, _U32) ^ cos_sign, jnp.float32)
    return sin, cos


def _gauss_pair(seed, idx, stream: int):
    """``ref.gauss_pair`` with :func:`sincos_2pi`."""
    u1 = _ref.uniform01(_ref.hash_u32(seed, idx, stream))
    u2 = _ref.uniform01(_ref.hash_u32(seed, idx, stream ^ _ref._STREAM_PHASE))
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    sin, cos = sincos_2pi(jnp.float32(_ref._TWO_PI) * u2)
    return r * cos, r * sin


def _gray_decode(g, bits: int):
    """``ref.gray_decode`` of a ``bits``-bit code: the shifts that reach."""
    shift = 1
    while shift < bits:
        g = g ^ (g >> _U32(shift))
        shift *= 2
    return g


def _decisions(u, seed, base_sym, nscale, gain_sqrt, planes, gauss, *,
               bits_per_symbol: int, fading: str, fade_block: int,
               word_bits: int):
    """``(t_re, t_im, margin)`` of the symbol planes ``planes`` (``(first,
    count)``, ``first`` possibly traced) of one tile:
    ``ref.channel_tile``'s arithmetic, in its
    order, up to the demapper's rounding of ``t``, with the Box-Muller
    pair ``gauss``; and each symbol's margin (module docstring).
    ``nscale`` and ``gain_sqrt`` are the oracle's ``sqrt(noise_power *
    0.5)`` and ``sqrt(large_scale_gain)``, as tile-shaped vectors."""
    k = bits_per_symbol
    p = k // 2
    L = 1 << p
    amp = math.sqrt(3.0 / (2.0 * (L * L - 1)))
    shape = (planes[1],) + u.shape

    j = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + planes[0]
    shifts = _ref._u32(word_bits - k * (j + 1))
    stream = (jnp.broadcast_to(u[None], shape) >> shifts) & _U32((1 << k) - 1)
    gi = jnp.zeros_like(stream)
    gq = jnp.zeros_like(stream)
    for b in range(p):
        bi = (stream >> _U32(k - 1 - 2 * b)) & _U32(1)
        bq = (stream >> _U32(k - 2 - 2 * b)) & _U32(1)
        gi = gi | (bi << _U32(p - 1 - b))
        gq = gq | (bq << _U32(p - 1 - b))
    li = _ref._i32(_gray_decode(gi, p)).astype(jnp.float32)
    lq = _ref._i32(_gray_decode(gq, p)).astype(jnp.float32)
    s_re = (2.0 * li - (L - 1)) * jnp.float32(amp)
    s_im = (2.0 * lq - (L - 1)) * jnp.float32(amp)

    gidx = _ref._u32(base_sym + j * u.size + _ref.tile_word_index(shape))
    n_re, n_im = gauss(seed, gidx, _ref._STREAM_NOISE)
    n_re = n_re * nscale
    n_im = n_im * nscale
    if fading == "awgn":
        c_re = gain_sqrt * jnp.ones_like(s_re)
        c_im = jnp.zeros_like(s_re)
    else:
        fidx = gidx // _U32(fade_block) if fading == "block_rayleigh" else gidx
        h_re, h_im = gauss(seed, fidx, _ref._STREAM_FADE)
        hs = jnp.sqrt(jnp.float32(0.5))
        c_re = gain_sqrt * h_re * hs
        c_im = gain_sqrt * h_im * hs
    c2 = jnp.maximum(c_re * c_re + c_im * c_im, jnp.float32(1e-20))
    q_re = (n_re * c_re + n_im * c_im) / c2
    q_im = (n_im * c_re - n_re * c_im) / c2
    y_re = s_re + q_re
    y_im = s_im + q_im

    inv = jnp.float32(1.0 / amp)
    t_re = (y_re * inv + (L - 1)) * 0.5
    t_im = (y_im * inv + (L - 1)) * 0.5
    margin = ((jnp.abs(q_re) + jnp.abs(q_im)) * jnp.float32(_MARGIN_Z / amp)
              + jnp.float32(_MARGIN_L * L))
    return t_re, t_im, margin


def _symbols(t_re, t_im, bits_per_symbol: int):
    """The demapper: each symbol's ``k`` received bits, MSB-first, as
    ``ref.channel_tile`` assembles them."""
    k = bits_per_symbol
    p = k // 2
    L = 1 << p

    def axis_level(t):
        return _ref._u32(jnp.clip(jnp.round(t), 0, L - 1).astype(jnp.int32))

    gi_hat = _ref.gray_encode(axis_level(t_re))
    gq_hat = _ref.gray_encode(axis_level(t_im))
    rx = jnp.zeros_like(gi_hat)
    for b in range(p):
        bi = (gi_hat >> _U32(p - 1 - b)) & _U32(1)
        bq = (gq_hat >> _U32(p - 1 - b)) & _U32(1)
        rx = rx | (bi << _U32(k - 1 - 2 * b))
        rx = rx | (bq << _U32(k - 2 - 2 * b))
    return rx


def _near(t, margin, bits_per_symbol: int):
    """Whether ``t`` lies within ``margin`` of a decision threshold."""
    L = 1 << (bits_per_symbol // 2)
    lo = jnp.clip(jnp.floor(t), 0, L - 2) if L > 2 else 0.0
    return jnp.abs(t - lo - 0.5) <= margin


# The largest per-group counter output (``groups x rows x 128`` int32) a
# launch writes; past it the counter is one block summed over the groups.
PER_TILE_COUNTER_BYTES = 64 << 20


def _fast_tile(u, seed, base_sym, nscale, gain_sqrt, *, bits_per_symbol: int,
               fading: str, fade_block: int, word_bits: int):
    """``(u_hat, near)`` of one tile on the fast path: the received words,
    and an int32 tile whose bit ``s`` marks a word whose symbol in plane
    ``s`` has a decision within the margin of a threshold."""
    k = bits_per_symbol
    s_per_word = word_bits // k
    t_re, t_im, margin = _decisions(
        u, seed, base_sym, nscale, gain_sqrt, (0, s_per_word), _gauss_pair,
        bits_per_symbol=k, fading=fading, fade_block=fade_block,
        word_bits=word_bits)
    rx = _symbols(t_re, t_im, k)
    near = _near(t_re, margin, k) | _near(t_im, margin, k)
    u_hat = rx[0] << _U32(word_bits - k)
    bits = jnp.where(near[0], 1, 0)
    for s in range(1, s_per_word):
        u_hat = u_hat | (rx[s] << _U32(word_bits - k * (s + 1)))
        bits = bits | jnp.where(near[s], 1 << s, 0)
    return u_hat, bits


def _exact_planes(u_hat, planes, u, seed, base_sym, nscale, gain_sqrt, *,
                  bits_per_symbol: int, fading: str, fade_block: int,
                  word_bits: int):
    """``u_hat`` with the symbols of each plane set in the scalar bit mask
    ``planes`` computed again by ``ref.gauss_pair`` (the platform's
    ``jnp.sin`` / ``jnp.cos``). The planes are a loop, not unrolled: the
    platform's sine and cosine are long, and the kernel's compile time
    grows with each copy."""
    k = bits_per_symbol
    channel = dict(bits_per_symbol=k, fading=fading, fade_block=fade_block,
                   word_bits=word_bits)

    def plane(s, u_hat):
        t_re, t_im, _ = _decisions(u, seed, base_sym, nscale, gain_sqrt,
                                   (s, 1), _ref.gauss_pair, **channel)
        shift = _ref._u32(jnp.full(u.shape, word_bits - k) - k * s)
        field = _U32((1 << k) - 1) << shift
        return (u_hat & ~field) | (_symbols(t_re, t_im, k)[0] << shift)

    def redo(s, u_hat):
        return jax.lax.cond((planes >> s) & 1 != 0,
                            functools.partial(plane, s), lambda u_hat: u_hat,
                            u_hat)

    return jax.lax.fori_loop(0, word_bits // k, redo, u_hat)


def _plane_mask(near, s_per_word: int):
    """The scalar OR of an int32 tile of plane bits (one read by the
    scalar unit)."""
    planes = sum(jnp.max((near >> s) & 1, keepdims=True) << s
                 for s in range(s_per_word))
    return planes[0, 0]


# Tiles a grid step transmits. A step's fast path ends in one value the
# scalar unit reads from the vector unit (whether any of its tiles needs
# the exact path), which stalls the step on the TPU; a group of tiles
# shares that stall and the step's fixed cost.
GROUP_TILES = 8


def _make_kernel(aggregate: bool, masked: bool, *, valid_words: int,
                 tiles: int, group: int, accumulate: bool = False,
                 resident_counter: bool = False, **params):
    """Grid body over ``(groups, clients)``, client axis innermost; a step
    transmits ``group`` consecutive tiles of one client (fewer in a last,
    partial group of the ``tiles``).

    Each tile takes the fast path (module docstring); its words and plane
    bits wait in VMEM scratch. If any tile of the step has a decision near
    a threshold, each such tile's flagged planes are computed again on
    the exact path, and counted. Then each tile's words are clamped and
    committed: ``aggregate=False`` writes each client's demapped tile
    (batch kernel); ``aggregate=True`` folds ``w * x_hat`` into an f32
    accumulator block that stays resident in VMEM across the client sweep
    of a group and is flushed to HBM once — a separate multiply then add,
    never an fma, so the sum is bit-identical to
    ``aggregation.fedsgd_aggregate_batch`` over the batch kernel's rows.
    Bit errors count only the first ``valid_words`` global words
    (transmitted pad words are exactly 0).

    ``masked`` adds a leading ``num_active`` scalar: clients at or beyond it
    skip the PHY chain, count no errors, and write zeros (batch) or leave
    the accumulator untouched (aggregate) — the partial-batch grid the
    adaptive dispatch's padded buckets ride. ``accumulate`` starts the
    accumulator from an aggregate input (aliased to the output) instead of
    zero. ``resident_counter`` sums every group's counts into one block
    zeroed at the first grid step, instead of one block a group. A second
    counter block of the same layout counts the tiles that took the exact
    path.
    """
    block_words = params["block_words"]
    word_bits = params["word_bits"]
    k = params["bits_per_symbol"]
    s_per_word = word_bits // k
    rows = block_words // _ref.LANES
    channel = dict(bits_per_symbol=k, fading=params["fading"],
                   fade_block=params["fade_block"], word_bits=word_bits)

    def kernel(*refs):
        refs = list(refs)
        na_ref = refs.pop(0) if masked else None
        w_ref = refs.pop(0) if aggregate else None
        acc_ref = refs.pop(4) if accumulate else None
        (seed_ref, noise_ref, gain_ref, x_ref, out_ref, err_ref, exact_ref,
         hat_ref, near_ref) = refs
        # Grid coordinates are read here, outside any pl.when branch, where
        # the interpret-mode evaluator can resolve them.
        step = pl.program_id(0)
        client = pl.program_id(1)

        first = client == 0
        if resident_counter:
            first = jnp.logical_and(step == 0, first)

        @pl.when(first)
        def _():
            err_ref[...] = jnp.zeros_like(err_ref)
            exact_ref[...] = jnp.zeros_like(exact_ref)

        if aggregate:
            @pl.when(client == 0)
            def _():
                out_ref[...] = (acc_ref[...] if accumulate
                                else jnp.zeros_like(out_ref))

        slot = _ref.tile_word_index(err_ref.shape)

        def count(block, value):
            if resident_counter:
                block[...] = block[...] + jnp.where(slot == client, value, 0)
            else:
                block[...] = jnp.where(slot == client, value, block[...])

        def transmit():
            # The symbol counter restarts per client and the RNG is keyed
            # by the client's own seed (its segment's, past 2^32 symbols),
            # so each client reproduces the single-client kernel's stream.
            n_sub = (group if tiles % group == 0
                     else jnp.minimum(group, tiles - step * group))
            shape = (rows, _ref.LANES)
            nscale = jnp.sqrt(jnp.broadcast_to(noise_ref[client], shape)
                              * 0.5)
            gain_sqrt = jnp.sqrt(jnp.broadcast_to(gain_ref[client], shape))

            def tile_args(b):
                tile = step * group + b
                if params["n_segments"] > 1:
                    seed = seed_ref[client * params["n_segments"]
                                    + (tile >> params["tile_shift"])]
                else:
                    seed = seed_ref[client]
                at = pl.ds(pl.multiple_of(b * rows, rows), rows)
                return (at, tile, (x_ref[at, :], seed,
                                   tile * (block_words * s_per_word), nscale,
                                   gain_sqrt))

            def fast(b, any_near):
                _, _, args = tile_args(b)
                hat_ref[b], near_ref[b] = _fast_tile(*args, **channel)
                return any_near | near_ref[b]

            any_near = jax.lax.fori_loop(
                0, n_sub, fast, jnp.zeros(shape, jnp.int32))

            @pl.when(jnp.max(any_near) > 0)
            def _():
                def redo(b, n_exact):
                    _, _, args = tile_args(b)
                    planes = _plane_mask(near_ref[b], s_per_word)

                    @pl.when(planes != 0)
                    def _():
                        hat_ref[b] = _exact_planes(hat_ref[b], planes, *args,
                                                   **channel)

                    return n_exact + (planes != 0).astype(jnp.int32)

                count(exact_ref, jax.lax.fori_loop(0, n_sub, redo, 0))

            def commit(b, flips):
                at, tile, (u, *_) = tile_args(b)
                u_hat = hat_ref[b] & _U32(params["clamp_mask"])
                if aggregate:
                    # A bf16 value is the high half of the f32 with its bits.
                    f32_bits = u_hat << 16 if word_bits == 16 else u_hat
                    x_hat = jax.lax.bitcast_convert_type(f32_bits, jnp.float32)
                    out_ref[at, :] = out_ref[at, :] + w_ref[client] * x_hat
                else:
                    out_ref[at, :] = u_hat
                gidx = tile * block_words + _ref.tile_word_index(shape)
                return flips + jnp.where(gidx < valid_words,
                                         _ref.bit_flips(u, u_hat), 0)

            flips = jax.lax.fori_loop(0, n_sub, commit,
                                      jnp.zeros(shape, jnp.int32))
            # The count stays a (1, 1) vector: no read by the scalar unit.
            count(err_ref, jnp.sum(flips, keepdims=True))

        if not masked:
            transmit()
            return
        active = client < na_ref[0]
        pl.when(active)(transmit)
        if not aggregate:
            @pl.when(jnp.logical_not(active))
            def _():
                out_ref[...] = jnp.zeros_like(out_ref)

    return kernel


def _uplink_call(x, seeds, noise_powers, large_scale_gains, weights, *,
                 bits_per_symbol, fading, fade_block, clamp_mask, block_words,
                 word_bits, valid_words, interpret, num_active, acc=None):
    """Shared launch of both kernels; ``weights=None`` is the batch kernel.

    Returns ``(out, bit_errors (C,) int32, exact_tiles (C,) int32)``
    where ``out`` is the demapped ``(C, N)`` wire payload, or the ``(N,)``
    f32 weighted sum (added to ``acc`` when given), and ``exact_tiles``
    counts each client's tiles that took the exact path.
    """
    c, n = x.shape
    if n % block_words or block_words % _ref.LANES:
        raise ValueError(
            f"N={n} must be a multiple of block_words={block_words}, itself "
            f"a multiple of {_ref.LANES}")
    rows = block_words // _ref.LANES
    tiles = n // block_words
    group = min(GROUP_TILES, tiles)
    groups = pl.cdiv(tiles, group)
    aggregate = weights is not None
    masked = num_active is not None
    accumulate = acc is not None
    n_segments, tile_shift = _ref.segments(
        tiles, block_words, word_bits // bits_per_symbol, fading, fade_block)
    # Lane-dense counters: client c of a group at flat slot c of its block.
    err_rows = 8 * pl.cdiv(c, 8 * _ref.LANES)
    resident = groups * err_rows * _ref.LANES * 4 > PER_TILE_COUNTER_BYTES
    kernel = _make_kernel(
        aggregate, masked, accumulate=accumulate, resident_counter=resident,
        valid_words=n if valid_words is None else valid_words, tiles=tiles,
        group=group, bits_per_symbol=bits_per_symbol, fading=fading,
        fade_block=fade_block, clamp_mask=clamp_mask, block_words=block_words,
        word_bits=word_bits, n_segments=n_segments, tile_shift=tile_shift)

    seeds = seeds.reshape(c).astype(_U32)
    if n_segments > 1:
        seeds = _ref.segment_seed(
            seeds[:, None], jnp.arange(n_segments, dtype=jnp.int32)[None, :]
        ).reshape(-1)
    scalars = [seeds,
               noise_powers.reshape(c).astype(jnp.float32),
               large_scale_gains.reshape(c).astype(jnp.float32)]
    if aggregate:
        scalars.insert(0, weights.reshape(c).astype(jnp.float32))
    if masked:
        scalars.insert(0, jnp.reshape(jnp.asarray(num_active, jnp.int32), (1,)))
    # Inside shard_map the outputs vary over every mesh axis an operand does.
    vma = frozenset().union(*(jax.typeof(a).vma for a in (x, *scalars)))

    # A partial last group reads past the payload; those rows are never
    # transmitted, and never written back.
    payload = pl.BlockSpec((None, group * rows, _ref.LANES),
                           lambda gi, ci, *_: (ci, gi, 0))
    if aggregate:
        out_spec = pl.BlockSpec((group * rows, _ref.LANES),
                                lambda gi, ci, *_: (gi, 0))
        out_shape = jax.ShapeDtypeStruct((n // _ref.LANES, _ref.LANES),
                                         jnp.float32, vma=vma)
    else:
        out_spec = payload
        out_shape = jax.ShapeDtypeStruct((c, n // _ref.LANES, _ref.LANES),
                                         _U32, vma=vma)
    operands = [_ref.wire_words(x, word_bits).reshape(c, n // _ref.LANES,
                                                      _ref.LANES)]
    in_specs = [payload]
    aliases = {}
    if accumulate:
        operands.append(acc.astype(jnp.float32).reshape(n // _ref.LANES,
                                                        _ref.LANES))
        in_specs.append(out_spec)
        aliases = {len(scalars) + 1: 0}
    if resident:
        err_spec = pl.BlockSpec((err_rows, _ref.LANES),
                                lambda gi, ci, *_: (0, 0))
        err_shape = (err_rows, _ref.LANES)
        semantics = ("arbitrary", "arbitrary")
    else:
        err_spec = pl.BlockSpec((None, err_rows, _ref.LANES),
                                lambda gi, ci, *_: (gi, 0, 0))
        err_shape = (groups, err_rows, _ref.LANES)
        semantics = ("parallel", "arbitrary")
    counter = jax.ShapeDtypeStruct(err_shape, jnp.int32, vma=vma)
    out, errs, exact = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(groups, c),
            in_specs=in_specs,
            out_specs=[out_spec, err_spec, err_spec],
            scratch_shapes=[pltpu.VMEM((group, rows, _ref.LANES), _U32),
                            pltpu.VMEM((group, rows, _ref.LANES), jnp.int32)],
        ),
        out_shape=[out_shape, counter, counter],
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        input_output_aliases=aliases,
        interpret=interpret,
    )(*scalars, *operands)
    if resident:
        errs, exact = (a.reshape(-1)[:c] for a in (errs, exact))
    else:
        errs, exact = (jnp.sum(a.reshape(groups, -1)[:, :c], axis=0)
                       for a in (errs, exact))
    if aggregate:
        return out.reshape(n), errs, exact
    return _ref.wire_values(out.reshape(c, n), word_bits), errs, exact


_STATIC = (
    "bits_per_symbol",
    "fading",
    "fade_block",
    "clamp_mask",
    "block_words",
    "word_bits",
    "valid_words",
    "interpret",
)


@functools.partial(jax.jit, static_argnames=_STATIC)
def approx_channel_batch_aggregate_pallas(
    x: jax.Array,
    seeds: jax.Array,
    noise_powers: jax.Array,
    large_scale_gains: jax.Array,
    weights: jax.Array,
    *,
    bits_per_symbol: int = 2,
    fading: str = "rayleigh",
    fade_block: int = 64,
    clamp_mask: int = 0xBFFFFFFF,
    block_words: int = 1024,
    word_bits: int = 32,
    valid_words: int | None = None,
    interpret: bool = True,
    num_active=None,
    acc=None,
):
    """Fused modulate -> channel -> demodulate -> accumulate, one launch.

    Runs the same per-client PHY chain as ``approx_channel_batch_pallas``
    but never materializes the ``(C, N)`` demapped payload in HBM: a
    ``(groups, clients)`` grid (client axis innermost) folds each client's
    received tiles into a single f32 accumulator block that is written once
    per group of tiles. HBM traffic drops from ``C*N`` wire words out +
    ``C*N`` f32 read back (plus the aggregation write) to ``N`` f32 out.

    Args:
      x: ``(C, N)`` f32 (or bf16 with ``word_bits=16``),
        ``N % block_words == 0``.
      seeds / noise_powers / large_scale_gains: ``(C,)`` per-client link
        params, exactly as in ``approx_channel_batch_pallas``.
      weights: ``(C,)`` f32 aggregation weights (pre-normalized by the
        caller; masked rows' weights are ignored).
      valid_words: count only bit errors in the first ``valid_words`` words
        of each row (``None`` = all of N). The accumulator always covers
        all N words — callers slice off their padding.
      num_active: optional scalar — rows at or beyond it skip the PHY chain
        and contribute nothing to the sum (padded adaptive buckets).
      acc: optional ``(N,)`` f32 running aggregate the sum starts from; its
        buffer is aliased to the output.

    Returns:
      ``(agg (N,) float32, bit_errors (C,) int32, exact_tiles (C,) int32)``
      with ``agg == acc + sum_c weights[c] * x_hat[c]`` accumulated in
      client order, bit-identical to ``fedsgd_aggregate_batch`` over the
      batched kernel; ``exact_tiles`` counts each client's tiles that took
      the exact path.
    """
    return _uplink_call(
        x, seeds, noise_powers, large_scale_gains, weights,
        bits_per_symbol=bits_per_symbol, fading=fading, fade_block=fade_block,
        clamp_mask=clamp_mask, block_words=block_words, word_bits=word_bits,
        valid_words=valid_words, interpret=interpret, num_active=num_active,
        acc=acc)


@functools.partial(jax.jit, static_argnames=_STATIC)
def approx_channel_batch_pallas(
    x: jax.Array,
    seeds: jax.Array,
    noise_powers: jax.Array,
    large_scale_gains: jax.Array,
    *,
    bits_per_symbol: int = 2,
    fading: str = "rayleigh",
    fade_block: int = 64,
    clamp_mask: int = 0xBFFFFFFF,
    block_words: int = 1024,
    word_bits: int = 32,
    valid_words: int | None = None,
    interpret: bool = True,
    num_active=None,
):
    """Batched fused PHY pipeline over a 2-D ``(groups, clients)`` grid.

    Args:
      x: ``(C, N)`` f32 (or bf16 with ``word_bits=16``), ``N % block_words == 0``.
      seeds: ``(C,)`` uint32 — one independent RNG stream per client.
      noise_powers / large_scale_gains: ``(C,)`` f32 per-client link params
        (heterogeneous SNR = varying ``noise_powers``).
      valid_words: count only bit errors in the first ``valid_words`` words
        of each row (``None`` = all of N); the output covers all N words.
      num_active: optional scalar (may be traced): only the first
        ``num_active`` client rows are computed; rows beyond it are masked —
        zero output, zero error count, no PHY work. This is the
        partial-batch grid the adaptive dispatch's padded buckets ride;
        ``None`` computes every row.

    Returns:
      ``(x_hat (C, N), bit_errors (C,) int32)``. Active row ``i`` is
      bit-identical to ``approx_channel_pallas(x[i], seeds[i], ...)``.
    """
    x_hat, errs, _ = _uplink_call(
        x, seeds, noise_powers, large_scale_gains, None,
        bits_per_symbol=bits_per_symbol, fading=fading, fade_block=fade_block,
        clamp_mask=clamp_mask, block_words=block_words, word_bits=word_bits,
        valid_words=valid_words, interpret=interpret, num_active=num_active)
    return x_hat, errs
