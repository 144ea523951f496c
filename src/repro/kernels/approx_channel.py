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
128 lanes, one f32 vreg tile) over a ``(tiles, clients)`` grid; the
single-client entry point is the C=1 view. Each tile expands to
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
position ``c``. A launch whose per-tile blocks stay under
``PER_TILE_COUNTER_BYTES`` writes one block a tile (``tiles x C`` words in
HBM, summed after the launch); its tile axis is ``"parallel"`` and its
client axis ``"arbitrary"``, because the counter block (and the fused
kernel's accumulator) is revisited across the clients. A larger payload
(the per-tile blocks of a 568M-float payload would take 2.27 GB) keeps one
block resident in VMEM for the whole grid and sums the tiles into it, so
its counter output is ``C`` words; both its grid axes are then
``"arbitrary"``.

The fused kernel's accumulator starts at zero, or at a running aggregate
passed in and aliased to the output: a round that streams its cohort in
waves folds each wave into the aggregate of the waves before it, in client
order, so any wave size gives one launch's sum of the same payload bit for
bit.
"""

from __future__ import annotations

import functools

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


def _phy_tile(tile, client, seed_ref, noise_ref, gain_ref, x_ref, *,
              bits_per_symbol: int, fading: str, fade_block: int,
              clamp_mask: int, block_words: int, word_bits: int,
              n_segments: int, tile_shift: int):
    """``(u, u_hat)`` words of one (client, tile): sent and received-and-
    clamped. The symbol counter restarts per client and the RNG is keyed by
    the client's own seed (its segment's, past 2^32 symbols), so each
    client reproduces the single-client kernel's stream bit-for-bit."""
    s_per_word = word_bits // bits_per_symbol
    u = x_ref[...]
    if n_segments > 1:
        seed = seed_ref[client * n_segments + (tile >> tile_shift)]
    else:
        seed = seed_ref[client]
    u_hat = _ref.channel_tile(
        u,
        seed,
        tile * (block_words * s_per_word),
        noise_ref[client],
        gain_ref[client],
        bits_per_symbol=bits_per_symbol,
        fading=fading,
        fade_block=fade_block,
        word_bits=word_bits,
    )
    return u, u_hat & _U32(clamp_mask)


# The largest per-tile counter output (``tiles x rows x 128`` int32) a
# launch writes; past it the counter is one block summed over the tiles.
PER_TILE_COUNTER_BYTES = 64 << 20


def _make_kernel(aggregate: bool, masked: bool, *, valid_words: int,
                 accumulate: bool = False, resident_counter: bool = False,
                 **params):
    """Grid body over ``(tiles, clients)``, client axis innermost.

    ``aggregate=False`` writes each client's demapped tile (batch kernel);
    ``aggregate=True`` folds ``w * x_hat`` into an f32 accumulator block
    that stays resident in VMEM across the client sweep of a tile and is
    flushed to HBM once — a separate multiply then add, never an fma, so
    the sum is bit-identical to ``aggregation.fedsgd_aggregate_batch`` over
    the batch kernel's rows. Bit errors count only the first
    ``valid_words`` global words (transmitted pad words are exactly 0).

    ``masked`` adds a leading ``num_active`` scalar: clients at or beyond it
    skip the PHY chain, count no errors, and write zeros (batch) or leave
    the accumulator untouched (aggregate) — the partial-batch grid the
    adaptive dispatch's padded buckets ride. ``accumulate`` starts the
    accumulator from an aggregate input (aliased to the output) instead of
    zero. ``resident_counter`` sums every tile's counts into one block
    zeroed at the first grid step, instead of one block a tile.
    """
    block_words = params["block_words"]

    def kernel(*refs):
        refs = list(refs)
        na_ref = refs.pop(0) if masked else None
        w_ref = refs.pop(0) if aggregate else None
        acc_ref = refs.pop(4) if accumulate else None
        seed_ref, noise_ref, gain_ref, x_ref, out_ref, err_ref = refs
        # Grid coordinates are read here, outside any pl.when branch, where
        # the interpret-mode evaluator can resolve them.
        tile = pl.program_id(0)
        client = pl.program_id(1)

        first = client == 0
        if resident_counter:
            first = jnp.logical_and(tile == 0, first)

        @pl.when(first)
        def _():
            err_ref[...] = jnp.zeros_like(err_ref)

        if aggregate:
            @pl.when(client == 0)
            def _():
                out_ref[...] = (acc_ref[...] if accumulate
                                else jnp.zeros_like(out_ref))

        def transmit():
            u, u_hat = _phy_tile(tile, client, seed_ref, noise_ref, gain_ref,
                                 x_ref, **params)
            if aggregate:
                # A bf16 value is the high half of the f32 with its bits.
                f32_bits = u_hat << 16 if params["word_bits"] == 16 else u_hat
                x_hat = jax.lax.bitcast_convert_type(f32_bits, jnp.float32)
                out_ref[...] = out_ref[...] + w_ref[client] * x_hat
            else:
                out_ref[...] = u_hat
            gidx = tile * block_words + _ref.tile_word_index(u.shape)
            flips = jnp.where(gidx < valid_words, _ref.bit_flips(u, u_hat), 0)
            count = jnp.sum(flips)
            slot = _ref.tile_word_index(err_ref.shape)
            if resident_counter:
                err_ref[...] = err_ref[...] + jnp.where(slot == client,
                                                        count, 0)
            else:
                err_ref[...] = jnp.where(slot == client, count, err_ref[...])

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

    Returns ``(out, bit_errors (C,) int32)`` where ``out`` is the demapped
    ``(C, N)`` wire payload, or the ``(N,)`` f32 weighted sum (added to
    ``acc`` when given).
    """
    c, n = x.shape
    if n % block_words or block_words % _ref.LANES:
        raise ValueError(
            f"N={n} must be a multiple of block_words={block_words}, itself "
            f"a multiple of {_ref.LANES}")
    rows = block_words // _ref.LANES
    tiles = n // block_words
    aggregate = weights is not None
    masked = num_active is not None
    accumulate = acc is not None
    n_segments, tile_shift = _ref.segments(
        tiles, block_words, word_bits // bits_per_symbol, fading, fade_block)
    # Lane-dense counters: client c of a tile at flat slot c of its block.
    err_rows = 8 * pl.cdiv(c, 8 * _ref.LANES)
    resident = tiles * err_rows * _ref.LANES * 4 > PER_TILE_COUNTER_BYTES
    kernel = _make_kernel(
        aggregate, masked, accumulate=accumulate, resident_counter=resident,
        valid_words=n if valid_words is None else valid_words,
        bits_per_symbol=bits_per_symbol, fading=fading, fade_block=fade_block,
        clamp_mask=clamp_mask, block_words=block_words, word_bits=word_bits,
        n_segments=n_segments, tile_shift=tile_shift)

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

    payload = pl.BlockSpec((None, rows, _ref.LANES),
                           lambda ti, ci, *_: (ci, ti, 0))
    if aggregate:
        out_spec = pl.BlockSpec((rows, _ref.LANES), lambda ti, ci, *_: (ti, 0))
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
                                lambda ti, ci, *_: (0, 0))
        err_shape = (err_rows, _ref.LANES)
        semantics = ("arbitrary", "arbitrary")
    else:
        err_spec = pl.BlockSpec((None, err_rows, _ref.LANES),
                                lambda ti, ci, *_: (ti, 0, 0))
        err_shape = (tiles, err_rows, _ref.LANES)
        semantics = ("parallel", "arbitrary")
    out, errs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(tiles, c),
            in_specs=in_specs,
            out_specs=[out_spec, err_spec],
        ),
        out_shape=[
            out_shape,
            jax.ShapeDtypeStruct(err_shape, jnp.int32, vma=vma),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        input_output_aliases=aliases,
        interpret=interpret,
    )(*scalars, *operands)
    if resident:
        errs = errs.reshape(-1)[:c]
    else:
        errs = jnp.sum(errs.reshape(tiles, -1)[:, :c], axis=0)
    if aggregate:
        return out.reshape(n), errs
    return _ref.wire_values(out.reshape(c, n), word_bits), errs


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
    ``(tiles, clients)`` grid (client axis innermost) folds each client's
    received tile into a single f32 accumulator block that is written once
    per tile. HBM traffic drops from ``C*N`` wire words out + ``C*N`` f32
    read back (plus the aggregation write) to ``N`` f32 out.

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
      ``(agg (N,) float32, bit_errors (C,) int32)`` with
      ``agg == acc + sum_c weights[c] * x_hat[c]`` accumulated in client
      order, bit-identical to ``fedsgd_aggregate_batch`` over the batched
      kernel.
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
    """Batched fused PHY pipeline over a 2-D ``(tiles, clients)`` grid.

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
    return _uplink_call(
        x, seeds, noise_powers, large_scale_gains, None,
        bits_per_symbol=bits_per_symbol, fading=fading, fade_block=fade_block,
        clamp_mask=clamp_mask, block_words=block_words, word_bits=word_bits,
        valid_words=valid_words, interpret=interpret, num_active=num_active)
