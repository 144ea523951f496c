"""Composable gradient-transport pipeline (the paper's Sec. IV protocol).

Modes
-----
``perfect``  error-free delivery (genie; used as the no-wireless reference).
``naive``    raw float bits through the fading channel, no prior — the
             paper's collapse-to-10%-accuracy baseline.
``approx``   the paper's proposed scheme: MSB-first packing + Gray-QAM
             unequal protection + symbol interleaving + bit-30 clamp at the
             receiver (optionally a tighter certified exponent mask).
``ecrt``     rate-1/2 LDPC FEC + retransmission until every codeword decodes
             (bits exact at the PS, >= 2x airtime). ``simulate_fec=False``
             swaps the real min-sum decoder for the calibrated analytic
             model (bits exact + measured E[tx]) — used inside long FL loops
             where decoding every round would only re-measure a constant.

The entry points operate on flat float32 vectors or whole pytrees and return
``(values_hat, TxStats)``; ``TxStats`` carries what the latency model needs.

Single-client vs batched
------------------------
``transmit_flat`` carries one client's payload. ``transmit_batch`` carries a
``(num_clients, payload)`` matrix through per-client *independent* fading
channels in one fused computation (vmap in the jnp paths, a 2-D grid in the
Pallas kernel path) and returns per-client ``TxStats`` with ``(num_clients,)``
fields. The key schedule is ``fold_in``-based (:func:`client_keys`): client
``i`` uses ``jax.random.fold_in(key, client_offset + i)``, so a batched call
is bit-identical to a Python loop of ``transmit_flat`` calls over the same
schedule, and a sharded batch (``launch.sharding.shard_transmit_batch``)
reproduces the unsharded batch exactly. Heterogeneous link quality is
expressed either via a per-client ``ChannelConfig.snr_db`` sequence or the
``snr_db`` override argument.

Mixed-mode dispatch
-------------------
``transmit_batch_adaptive`` carries a cohort where client ``i`` uses
``cfgs[mode_idx[i]]`` (the link-adaptation hook). Two dispatch strategies:

``bucketed`` (default when ``mode_idx`` is concrete)
    Stable-argsort clients by mode, gather payload rows into contiguous
    per-mode buckets, run each mode **once** as a fused single-mode batch on
    its bucket, scatter results back to original client order. Total work is
    O(num_clients) payload pipelines instead of O(modes x num_clients), and
    each bucket may take the fused Pallas kernel path (``cfg.use_kernel``).
    Bucket capacities round up on a quarter-octave schedule (masked tail
    rows, outputs discarded; see ``_bucket_capacity``) so the per-mode jit
    traces are bounded (``~4 log2(num_clients)`` shapes per mode for any
    sequence of mode mixes) and reused as the mix changes round to round.
    The fold_in key rides the *client index*, not the bucket slot, so the
    result is bit-identical to the select path and to per-client
    ``transmit_flat`` calls.

``select`` (default when ``mode_idx`` is traced)
    One ``lax.switch`` over the config table, vmapped over clients: a single
    fused XLA program, but the switch lowers to a select over **all**
    branches, so every client pays every mode's FLOPs (~``len(cfgs)``x) and
    the Pallas kernel path cannot lower. Kept for fully-traced contexts
    (``jax.jit`` round steps with a traced mode vector, ``shard_map``
    bodies).

Downlink broadcast
------------------
``transmit_broadcast`` (and the ``_adaptive``/``_pytree`` variants) carry
**one** payload — the PS's global model — through ``num_clients``
independent *downlink* channels: the broadcast leg of an FL round, where
each client receives its own corrupted copy of the same bits. The engine is
the same ``_batch_with_keys`` as the uplink; only the key schedule differs:
client ``i`` draws ``fold_in(key, DOWNLINK_KEY_LANE + i)`` instead of
``fold_in(key, i)``, so a round may feed its *uplink* base key to the
broadcast leg and the two legs' fading/noise realizations stay independent
— and, critically, adding a downlink leg leaves every uplink draw of an
existing run untouched (no extra ``jax.random.split`` is consumed).

Sparse uplinks
--------------
``transmit_sparse`` / ``transmit_sparse_batch`` carry a *compressed* payload:
``k`` selected values plus their coordinate indices (see
:mod:`repro.compress`). The value payload rides the existing pipeline
(MSB-first/Gray-QAM for uncoded modes, LDPC for ECRT) under the client's
transport key; the index header rides protected bits (the constellation's
two most-protected Gray positions, an ECRT-coded leg, or an error-free
control channel) under ``fold_in(client_key, HEADER_KEY_LANE)``. The
batched form shares :func:`client_keys`' fold_in schedule, so it is
bit-identical to a per-client loop of ``transmit_sparse`` — the same
contract as the dense engine. These entry points delegate to
``repro.compress.framing`` (imported lazily to keep ``core`` free of an
upward dependency).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import channel as channel_lib
from repro.core import keylanes
from repro.core import ecrt as ecrt_lib
from repro.core import float_codec as fc
from repro.core import modulation as mod_lib

__all__ = [
    "DOWNLINK_KEY_LANE",
    "TransportConfig",
    "TxStats",
    "clear_kernel_rows",
    "client_keys",
    "transmit_flat",
    "transmit_pytree",
    "transmit_batch",
    "transmit_pytree_batch",
    "transmit_batch_adaptive",
    "transmit_pytree_batch_adaptive",
    "transmit_batch_aggregate",
    "aggregate_words",
    "transmit_pytree_batch_aggregate",
    "transmit_batch_adaptive_aggregate",
    "transmit_pytree_batch_adaptive_aggregate",
    "transmit_sparse",
    "transmit_sparse_batch",
    "transmit_broadcast",
    "transmit_broadcast_adaptive",
    "transmit_pytree_broadcast",
    "transmit_pytree_broadcast_adaptive",
]

# fold_in lane where downlink-broadcast client keys live: uplink client i
# draws fold_in(key, i), downlink client i draws fold_in(key, LANE + i), so
# one round key serves both legs with independent channel realizations.
# Cohorts must stay below the lane width (~1M clients) or the two schedules
# would collide; transmit_broadcast validates this. Declared centrally in
# repro.core.keylanes (overlap-checked at import); re-exported here with
# the historical value (1 << 20), which the goldens pin.
DOWNLINK_KEY_LANE = keylanes.DOWNLINK_KEY_LANE


@dataclasses.dataclass(frozen=True)
class TransportConfig:
    """One uplink transport: wire mode, modulation, channel, and FEC knobs."""

    mode: str = "approx"  # perfect | naive | approx | ecrt
    modulation: str = "qpsk"
    channel: channel_lib.ChannelConfig = dataclasses.field(
        default_factory=channel_lib.ChannelConfig
    )
    interleave: bool = True
    clamp_bound: float = 2.0  # paper: |g| < 2 -> clear bit 30 only
    # Wire format: "float32" (paper) or "bfloat16" (beyond-paper: bf16 shares
    # the f32 exponent layout, so the bit-clamp prior applies verbatim while
    # halving airtime and, in the distributed uplink, psum bytes).
    wire_dtype: str = "float32"
    # Process the payload in chunks of this many floats (0 = whole payload).
    # The uncoded pipeline materializes ~36 B of intermediates per 4 B float
    # (symbols + complex stream + noise); chunking via lax.map bounds the
    # live set to chunk_elems x 36 B — required for multi-GB gradients.
    chunk_elems: int = 0
    ldpc: ecrt_lib.LdpcCode = dataclasses.field(default_factory=ecrt_lib.LdpcCode)
    max_tx: int = 8  # ECRT retransmission cap
    simulate_fec: bool = True
    ecrt_expected_tx: float = 1.0  # analytic model (calibrated; see latency)
    use_kernel: bool = False  # route through the fused Pallas kernel

    @property
    def scheme(self) -> mod_lib.ModScheme:
        """The resolved :class:`~repro.core.modulation.ModScheme`."""
        return mod_lib.MOD_SCHEMES[self.modulation]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TxStats:
    """Per-uplink transmission statistics.

    Unit conventions (the single source of truth — ``latency.round_airtime``
    and every benchmark consume these):

    * ``data_symbols`` — **complex modulation symbols** put on the air,
      including every ECRT retransmission and FEC parity. Airtime is
      ``data_symbols / symbol_rate``; this is *not* a bit count.
    * ``transmissions`` — PHY transmissions (preamble+ACK overheads paid).
      Exactly 1 for perfect/naive/approx; mean transmissions per codeword
      for ECRT (can be fractional for the analytic model).
    * ``bit_errors`` — residual flipped **payload bits** after the full
      receiver pipeline (post-clamp for approx); 0 for perfect/ECRT.
    * ``n_bits`` — **payload bits offered**, i.e. ``n_floats * wire_bits``
      (32 for float32 wire, 16 for bfloat16). FEC parity and retransmitted
      copies are *not* counted here — they show up in ``data_symbols`` only,
      so ``ber = bit_errors / n_bits`` is the end-to-end payload BER.
    * ``bits_on_air`` — bits actually put **on the air**:
      ``data_symbols * bits_per_symbol`` of the scheme, so FEC parity,
      retransmissions, and the sparse framing's index header all count,
      and the value is exactly proportional to data airtime. Equals
      ``n_bits`` for uncoded dense modes; ``2 * n_bits * E[tx]`` for ECRT;
      value + header bits for sparse uplinks — the telemetry axis the
      compression subsystem's 10–50x reduction is measured on.

    Fields are float32 jnp scalars for a single uplink (``transmit_flat``),
    or ``(num_clients,)`` arrays for a batched one (``transmit_batch``) —
    every formula above applies elementwise.

    ``mode_idx`` is the link-adaptation extension: ``None`` for single-mode
    calls, or the ``(num_clients,)`` int32 vector of per-client mode choices
    for :func:`transmit_batch_adaptive` — indices into the config table the
    caller dispatched over, so ``latency.round_airtime_adaptive`` can price
    each client's airtime under its own mode.

    ``exact_tiles`` is set by the fused uplink-and-aggregate kernel alone:
    each client's count of tiles that its exact recompute path redid
    (``kernels/approx_channel.py``); ``None`` elsewhere.
    """

    data_symbols: jax.Array  # symbols of payload actually sent (incl. retx)
    transmissions: jax.Array  # number of PHY transmissions (1 unless ECRT)
    bit_errors: jax.Array  # residual bit errors after the receiver pipeline
    n_bits: jax.Array
    mode_idx: Any = None  # (num_clients,) int32 for adaptive batches
    bits_on_air: Any = None  # total bits on air (payload + header + parity)
    exact_tiles: Any = None  # (num_clients,) int32, fused kernel launches

    @property
    def ber(self) -> jax.Array:
        """End-to-end payload bit-error rate (``bit_errors / n_bits``)."""
        return self.bit_errors / jnp.maximum(self.n_bits, 1)

    def round_summary(self) -> dict:
        """Cohort-level aggregates as plain Python floats — the
        ``uplink_*`` field group of :class:`repro.obs.records.RoundRecord`.

        Sums/means the per-client fields to the host once (a device
        transfer), so the observability layer calls this only when a sink
        is attached; all units follow the class docstring (``uplink_ber``
        is the cohort's pooled payload BER, total errors over total offered
        bits).
        """
        # Host-side stats accumulator — never touches the wire format.
        f64 = np.float64  # lint: ignore[dtype-discipline]
        symbols = np.asarray(self.data_symbols, f64)
        bits = np.asarray(self.n_bits, f64)
        errors = np.asarray(self.bit_errors, f64)
        out = {
            "uplink_symbols": float(symbols.sum()),
            "uplink_bits": float(bits.sum()),
            "uplink_bit_errors": float(errors.sum()),
            "uplink_ber": float(errors.sum() / max(bits.sum(), 1.0)),
            "uplink_mean_tx": float(
                np.mean(np.asarray(self.transmissions, f64))),
        }
        if self.bits_on_air is not None:
            out["uplink_bits_on_air"] = float(
                np.asarray(self.bits_on_air, f64).sum())
        return out

    def client_metrics(self) -> dict:
        """Per-client *device* arrays for the sketch layer, keyed by the
        metric names of ``repro.obs.metrics.DEFAULT_LAYOUTS``.

        Unlike :meth:`round_summary` this never syncs to the host — the
        values feed ``RoundSketcher.round_group``'s jitted reduction, so
        the only host transfer is the fixed-size bucket counts.
        """
        out = {"ber": self.ber, "transmissions": self.transmissions,
               "n_bits": self.n_bits}
        if self.bits_on_air is not None:
            out["bits_on_air"] = self.bits_on_air
        return out


def _stats(data_symbols, transmissions, bit_errors, n_bits,
           bits_on_air=None) -> TxStats:
    f = lambda v: jnp.asarray(v, jnp.float32)
    return TxStats(f(data_symbols), f(transmissions), f(bit_errors), f(n_bits),
                   bits_on_air=None if bits_on_air is None else f(bits_on_air))


def _through_channel(sym_stream: jax.Array, key: jax.Array, cfg: TransportConfig,
                     snr_db=None):
    tx = mod_lib.modulate(sym_stream, cfg.scheme)
    r, c = channel_lib.transmit(tx, key, cfg.channel, snr_db=snr_db)
    y = channel_lib.equalize(r, c)
    return y, c


def _uncoded(x: jax.Array, key: jax.Array, cfg: TransportConfig, clamp: bool,
             snr_db=None):
    """Shared path for naive/approx: bits -> QAM -> channel -> bits."""
    k = cfg.scheme.bits_per_symbol
    n = x.shape[0]
    wb = 16 if cfg.wire_dtype == "bfloat16" else 32
    s_per_word = wb // k
    u = fc.bf16_to_bits(x) if wb == 16 else fc.f32_to_bits(x)
    sym = fc.words_to_symbols(u, k, wb)  # (N, S)
    stream = fc.interleave(sym) if cfg.interleave else sym.reshape(-1)
    y, _ = _through_channel(stream, key, cfg, snr_db)
    rx_stream = mod_lib.demod_hard(y, cfg.scheme)
    rx = (
        fc.deinterleave(rx_stream, n, s_per_word)
        if cfg.interleave
        else rx_stream.reshape(n, s_per_word)
    )
    u_hat = fc.symbols_to_words(rx, k, wb)
    if clamp:
        u_hat = (fc.clamp_exponent_bits16(u_hat, cfg.clamp_bound) if wb == 16
                 else fc.clamp_exponent_bits(u_hat, cfg.clamp_bound))
    bit_errors = jnp.sum(mod_lib.popcount(u.astype(jnp.uint32) ^ u_hat.astype(jnp.uint32)))
    # NOTE: bit_errors counts *post-clamp* discrepancies vs the true words —
    # the clamp can only reduce this count since the true exponent MSB is 0.
    out = fc.bits_to_bf16(u_hat).astype(jnp.float32) if wb == 16 else fc.bits_to_f32(u_hat)
    return out, _stats(n * s_per_word, 1, bit_errors, n * wb, n * wb)


def _ecrt_real(x: jax.Array, key: jax.Array, cfg: TransportConfig, snr_db=None):
    """Real LDPC + retransmission loop (fixed max_tx rounds, masked)."""
    code = cfg.ldpc
    k_info = code.k
    u = fc.f32_to_bits(x)
    n_words = u.shape[0]
    # words -> bit matrix (n_bits,)
    shifts = jnp.uint32(31 - jnp.arange(32, dtype=jnp.uint32))
    bits = ((u[:, None] >> shifts) & jnp.uint32(1)).reshape(-1)
    pad = (-bits.shape[0]) % k_info
    bits_p = jnp.pad(bits, (0, pad))
    msgs = bits_p.reshape(-1, k_info)  # (C, k)
    cw = ecrt_lib.encode(msgs, code)  # (C, n)
    n_cw, n_code = cw.shape
    k_mod = cfg.scheme.bits_per_symbol
    assert n_code % k_mod == 0
    sym_per_cw = n_code // k_mod

    def tx_round(carry, kr):
        decoded, ok, tx_count = carry
        # Map codeword bits to symbols (k_mod bits per symbol, MSB-first).
        b = cw.reshape(n_cw, sym_per_cw, k_mod)
        weights = jnp.uint32(1) << jnp.uint32(k_mod - 1 - jnp.arange(k_mod))
        sym = jnp.sum(b * weights, axis=-1, dtype=jnp.uint32).reshape(-1)
        y, c = _through_channel(sym, kr, cfg, snr_db)
        nv = channel_lib.noise_var_post_eq(c, cfg.channel, snr_db=snr_db)
        llr = mod_lib.bit_llrs(y, nv, cfg.scheme).reshape(n_cw, n_code)
        hard, ok_new = ecrt_lib.decode(llr, code)
        take = (~ok) & ok_new
        decoded = jnp.where(take[:, None], hard, decoded)
        tx_count = tx_count + (~ok).astype(jnp.int32)
        ok = ok | ok_new
        return (decoded, ok, tx_count), None

    init = (
        jnp.zeros_like(cw),
        jnp.zeros((n_cw,), dtype=bool),
        jnp.zeros((n_cw,), dtype=jnp.int32),
    )
    keys = jax.random.split(key, cfg.max_tx)
    (decoded, ok, tx_count), _ = jax.lax.scan(tx_round, init, keys)
    # Failed codewords after max_tx: fall back to their last hard decision --
    # in practice ok -> all True at sane SNRs; tests assert this.
    decoded = jnp.where(ok[:, None], decoded, cw)  # genie fallback, counted
    info = decoded[:, :k_info].reshape(-1)[: bits.shape[0]]
    u_hat = jnp.sum(
        (info.reshape(n_words, 32).astype(jnp.uint32)) << shifts, axis=-1,
        dtype=jnp.uint32,
    )
    bit_errors = jnp.sum(mod_lib.popcount(u ^ u_hat))
    total_tx = jnp.sum(tx_count)
    return fc.bits_to_f32(u_hat), _stats(
        total_tx * sym_per_cw, jnp.mean(tx_count.astype(jnp.float32)),
        bit_errors, n_words * 32, total_tx * sym_per_cw * k_mod,
    )


def _ecrt_analytic(x: jax.Array, cfg: TransportConfig):
    """Calibrated ECRT model: exact bits, measured expected transmissions.

    Note: the model is SNR-blind by construction — ``ecrt_expected_tx`` is a
    single constant calibrated for one link quality, so per-client ``snr_db``
    does not vary these stats. Heterogeneous-SNR ECRT airtime needs the real
    chain (``simulate_fec=True``) or per-client calibration upstream.
    """
    n_words = x.shape[0]
    n_bits = n_words * 32
    k_mod = cfg.scheme.bits_per_symbol
    coded_bits = 2 * n_bits  # rate 1/2
    sym = coded_bits / k_mod * cfg.ecrt_expected_tx
    return x, _stats(sym, cfg.ecrt_expected_tx, 0, n_bits,
                     coded_bits * cfg.ecrt_expected_tx)


def _uncoded_chunked(x: jax.Array, key: jax.Array, cfg: TransportConfig,
                     clamp: bool, snr_db=None):
    """lax.map over fixed-size chunks: bounds the 36 B/float live set."""
    n = x.shape[0]
    chunk = cfg.chunk_elems
    pad = (-n) % chunk
    xp = jnp.pad(x, (0, pad)).reshape(-1, chunk)
    n_chunks = xp.shape[0]
    # chunk indices ride the client-space chunk lane of the client key
    keylanes.check_range(0, n_chunks, space="client")
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n_chunks))

    def one(args):
        xc, kc = args
        return _uncoded(xc, kc, cfg, clamp=clamp, snr_db=snr_db)

    x_hat, stats = jax.lax.map(one, (xp, keys))
    x_hat = x_hat.reshape(-1)
    # The chunk pipeline counts errors over the padding too; the transmitted
    # pad words are exactly 0, so every set bit in a received pad word is a
    # counted error — subtract them so stats cover only the true payload.
    wb = 16 if cfg.wire_dtype == "bfloat16" else 32
    pad_bits = (fc.bf16_to_bits(x_hat[n:]).astype(jnp.uint32) if wb == 16
                else fc.f32_to_bits(x_hat[n:]))
    pad_errs = jnp.sum(mod_lib.popcount(pad_bits))
    k = cfg.scheme.bits_per_symbol
    return x_hat[:n], _stats(
        n * (wb // k), 1, jnp.sum(stats.bit_errors) - pad_errs, n * wb, n * wb
    )


def transmit_flat(x: jax.Array, key: jax.Array, cfg: TransportConfig, *,
                  snr_db=None):
    """Transmit one client's flat float vector.

    Args:
      x: ``(N,)`` payload (cast to float32; wire format per ``cfg.wire_dtype``).
      key: PRNG key for this uplink's fading + noise realization.
      cfg: transport configuration (mode, modulation, channel, ...).
      snr_db: optional scalar override of ``cfg.channel.snr_db`` (may be a
        traced scalar — this is the per-client hook ``transmit_batch`` vmaps
        over).

    Returns:
      ``(x_hat, stats)``: the received ``(N,)`` float32 payload and scalar
      :class:`TxStats`.
    """
    x = x.astype(jnp.float32)
    n = x.shape[0]
    wb = 16 if cfg.wire_dtype == "bfloat16" else 32
    if cfg.mode == "perfect":
        k = cfg.scheme.bits_per_symbol
        return x, _stats(n * wb // k, 1, 0, n * wb, n * wb)
    if cfg.mode in ("naive", "approx") and cfg.use_kernel:
        from repro.kernels import ops as kernel_ops

        return kernel_ops.approx_channel_transmit(x, key, cfg, snr_db=snr_db)
    if cfg.mode in ("naive", "approx") and cfg.chunk_elems and n > cfg.chunk_elems:
        return _uncoded_chunked(x, key, cfg, clamp=cfg.mode == "approx",
                                snr_db=snr_db)
    if cfg.mode == "naive":
        return _uncoded(x, key, cfg, clamp=False, snr_db=snr_db)
    if cfg.mode == "approx":
        return _uncoded(x, key, cfg, clamp=True, snr_db=snr_db)
    if cfg.mode == "ecrt":
        if cfg.simulate_fec:
            return _ecrt_real(x, key, cfg, snr_db=snr_db)
        return _ecrt_analytic(x, cfg)
    raise ValueError(f"unknown transport mode {cfg.mode!r}")


def client_keys(key: jax.Array, num_clients: int, offset=0) -> jax.Array:
    """The batched uplink's key schedule: ``key_i = fold_in(key, offset + i)``.

    ``offset`` may be a traced int — ``shard_transmit_batch`` passes each
    shard's global client offset so sharded and unsharded batches agree
    (the key-lane span check only runs on concrete offsets).
    Returns ``(num_clients, key_size)`` keys.
    """
    keylanes.check_range(offset, num_clients)
    idx = jnp.arange(num_clients) + offset
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)


def _resolve_batch_snr(cfg: TransportConfig, num_clients: int, snr_db):
    """Per-client SNR column for a batch: explicit override > config > None.

    ``None`` means "homogeneous, use the config scalar" — that path is kept
    distinct so it stays bit-identical to ``transmit_flat`` (no dB->linear
    recomputation under trace). Shape validation happens up front in
    ``channel.snr_db_vector`` (the single shared rule): anything that is not
    a scalar, a single element, or exactly ``(num_clients,)`` raises
    ValueError naming both sizes.
    """
    if snr_db is not None:
        return channel_lib.snr_db_vector(snr_db, num_clients)
    return channel_lib.per_client_snr_db(cfg.channel, num_clients)


def _donation_supported() -> bool:
    """Whether this backend honours ``donate_argnums`` (XLA CPU ignores it
    with a warning, so the ``donate=`` plumbing silently no-ops there)."""
    return jax.default_backend() in ("gpu", "tpu")


def transmit_batch(x: jax.Array, key: jax.Array, cfg: TransportConfig, *,
                   snr_db=None, client_offset=0, donate: bool = False):
    """Transmit ``num_clients`` payloads through independent fading uplinks.

    One fused computation (single jittable call): the uncoded/ECRT paths vmap
    the per-client pipeline; the kernel path (``cfg.use_kernel``) lowers to a
    2-D ``(clients, tiles)`` Pallas grid.

    Args:
      x: ``(num_clients, N)`` payload matrix (cast to float32).
      key: base PRNG key; client ``i`` uses
        ``fold_in(key, client_offset + i)`` (see :func:`client_keys`), so the
        result is bit-identical to looping ``transmit_flat`` over that
        schedule.
      cfg: transport configuration. ``cfg.channel.snr_db`` may be a
        per-client sequence (heterogeneous links).
      snr_db: optional per-client SNR override — scalar or ``(num_clients,)``;
        takes precedence over the config. Varies the channel realization for
        every mode except the SNR-blind analytic ECRT model
        (``mode='ecrt', simulate_fec=False`` — see ``_ecrt_analytic``).
      client_offset: global index of row 0 (used by the sharded dispatch).
      donate: release the ``x`` buffer into the kernel launch (the uplink
        payload is dead after transmission). Honoured on the kernel path on
        backends that support donation (gpu/tpu); a no-op elsewhere.

    Returns:
      ``(x_hat, stats)``: ``(num_clients, N)`` float32 received payloads and
      :class:`TxStats` with ``(num_clients,)`` fields.
    """
    x = jnp.asarray(x, jnp.float32)
    if x.ndim != 2:
        raise ValueError(f"transmit_batch wants (num_clients, N); got {x.shape}")
    num_clients = x.shape[0]
    snr_vec = _resolve_batch_snr(cfg, num_clients, snr_db)
    keys = client_keys(key, num_clients, client_offset)

    return _batch_with_keys(x, keys, cfg, snr_vec, donate=donate)


def _batch_with_keys(x: jax.Array, keys: jax.Array, cfg: TransportConfig,
                     snr_vec, *, num_active=None, donate: bool = False):
    """Single-mode batch over explicit per-client keys.

    The shared engine under ``transmit_batch`` (keys from the fold_in
    schedule) and each bucket of the bucketed adaptive dispatch (keys
    gathered by client index). ``num_active`` masks the tail of a padded
    bucket on the kernel path (masked rows skip the grid work); the jnp
    paths compute padded rows and the caller discards them.
    """
    if cfg.mode in ("naive", "approx") and cfg.use_kernel:
        from repro.kernels import ops as kernel_ops

        return kernel_ops.approx_channel_transmit_batch(
            x, keys, cfg, snr_vec, num_active=num_active,
            donate=donate and _donation_supported())

    # All jnp paths (perfect/naive/approx/ecrt, chunked or not) are one vmap
    # over the single-client pipeline — batch semantics == loop semantics by
    # construction (vmap broadcasts the constant stats of perfect/analytic).
    if snr_vec is None:
        return jax.vmap(lambda xc, kc: transmit_flat(xc, kc, cfg))(x, keys)
    return jax.vmap(lambda xc, kc, s: transmit_flat(xc, kc, cfg, snr_db=s))(
        x, keys, snr_vec)


def _scan_weighted_sum(rows, weights, num_active=None, acc=None):
    """``acc + sum_c weights[c] * rows[c]`` as a ``lax.scan`` over the
    client axis (``acc`` defaults to zero).

    The arithmetic contract of the fused path: one multiply + one add per
    client per element, in client order — the same shape as the Pallas
    kernel's grid-loop accumulation and ``aggregation.fedsgd_aggregate_batch``
    (an unrolled sum is NOT bit-identical: LLVM contracts the first multiply
    of an add chain into an fma). ``num_active`` masks tail rows by carrying
    the accumulator through unchanged (a select, not a zero weight — a zero
    weight would still turn NaN payload lanes into NaN aggregates).
    """
    w = jnp.asarray(weights, jnp.float32)
    rows = rows.astype(jnp.float32)
    zero = (jnp.zeros(rows.shape[1:], jnp.float32) if acc is None
            else jnp.asarray(acc, jnp.float32))
    if num_active is None:
        def body(acc, wx):
            wc, xc = wx
            return acc + wc * xc, None

        agg, _ = jax.lax.scan(body, zero, (w, rows))
        return agg
    na = jnp.asarray(num_active, jnp.int32)

    def body_masked(acc, iwx):
        i, wc, xc = iwx
        return jnp.where(i < na, acc + wc * xc, acc), None

    agg, _ = jax.lax.scan(
        body_masked, zero, (jnp.arange(rows.shape[0]), w, rows))
    return agg


def _batch_aggregate_with_keys(x, keys, cfg, snr_vec, weights, *,
                               num_active=None, donate=False, acc=None):
    """Single-mode batch + weighted aggregation over explicit keys.

    The fused-round engine under :func:`transmit_batch_aggregate` and each
    bucket of :func:`transmit_batch_adaptive_aggregate`. On the kernel path
    the weighted sum happens *inside* the Pallas grid (the per-client
    demapped payload never reaches HBM); every other mode layers
    :func:`_scan_weighted_sum` over the standard batch — bit-identical to
    the kernel accumulator by the scan contract. ``weights`` are applied as
    given (normalize first: :func:`repro.core.aggregation.normalize_weights`).
    ``acc`` is a running ``(N,)`` aggregate the sum starts from.
    Returns ``(agg (N,) float32, stats)`` with per-client ``(C,)`` stats.
    """
    if cfg.mode in ("naive", "approx") and cfg.use_kernel:
        from repro.kernels import ops as kernel_ops

        return kernel_ops.approx_channel_transmit_batch_aggregate(
            x, keys, cfg, snr_vec, weights, num_active=num_active,
            donate=donate and _donation_supported(), acc=acc)
    x_hat, stats = _batch_with_keys(x, keys, cfg, snr_vec)
    return _scan_weighted_sum(x_hat, weights, num_active, acc), stats


def _same_channel(a: channel_lib.ChannelConfig,
                  b: channel_lib.ChannelConfig) -> bool:
    """ChannelConfig equality that tolerates array-valued ``snr_db``.

    Plain dataclass ``==`` on two distinct configs with per-client snr_db
    arrays evaluates an ambiguous-truth array comparison, and a bare
    ``np.array_equal`` on the snr_db values is shape-sensitive: a scalar, a
    0-d array, and a length-1 sequence all mean "one homogeneous SNR" but
    compare unequal. Normalize both sides to flat vectors first; a size-1
    value equals any vector it would broadcast to.
    """
    if a is b:
        return True
    if dataclasses.replace(a, snr_db=0.0) != dataclasses.replace(b, snr_db=0.0):
        return False
    sa = np.asarray(a.snr_db, np.float32).reshape(-1)
    sb = np.asarray(b.snr_db, np.float32).reshape(-1)
    if sa.size != sb.size and sa.size != 1 and sb.size != 1:
        return False
    if sa.size == 0 or sb.size == 0:
        return sa.size == sb.size
    return bool(np.all(sa == sb))


def clear_kernel_rows(cfgs):
    """A mode table with every ``use_kernel`` flag cleared.

    The single transform behind every select-pinned consumer (the fused FL
    round, ``shard_map`` dispatch): the Pallas grid cannot lower inside a
    vmapped switch, and the jnp rows draw their own — equally valid, but
    *different* — channel realization, so the engine refuses to swap the
    flag silently and callers opt in through this helper instead.
    """
    return tuple(
        dataclasses.replace(c, use_kernel=False) if c.use_kernel else c
        for c in cfgs
    )


def _bucket_capacity(count: int) -> int:
    """Static bucket capacity for ``count`` clients: quarter-octave rounding.

    Rounds up to the next multiple of ``2^(floor(log2 count) - 2)`` (counts
    <= 4 are exact), i.e. at most 4 capacities per power-of-two octave. This
    bounds the number of distinct bucket shapes — and therefore per-mode jit
    traces — at ``~4 log2(num_clients)`` per mode, whatever sequence of mode
    mixes the policy produces, while wasting at most 25% of a bucket's work
    on masked padding (so total work stays O(num_clients) across modes, vs
    O(modes x num_clients) for the select lowering).
    """
    if count <= 4:
        return max(count, 1)
    granule = 1 << (count.bit_length() - 3)
    return -(-count // granule) * granule


@functools.lru_cache(maxsize=256)
def _cached_mode_batch_fn(cfg: TransportConfig, with_snr: bool,
                          donate: bool = False):
    """One jitted single-mode batch per (config, snr-arity) — jax caches per
    bucket shape underneath, so repeated rounds with the same mode mix reuse
    their traces. ``donate`` twins release the bucket payload buffer (always
    a fresh gather) into the launch."""
    kwargs = {"donate_argnums": (0,)} if donate else {}
    if with_snr:
        return jax.jit(lambda x, k, s, na: _batch_with_keys(
            x, k, cfg, s, num_active=na), **kwargs)
    return jax.jit(lambda x, k, na: _batch_with_keys(
        x, k, cfg, None, num_active=na), **kwargs)


def _mode_batch_fn(cfg: TransportConfig, with_snr: bool,
                   donate: bool = False):
    try:
        return _cached_mode_batch_fn(cfg, with_snr,
                                     donate and _donation_supported())
    except TypeError:
        # Unhashable config (e.g. an array-valued channel snr_db): fall back
        # to an unjitted call — correct, just not trace-cached.
        if with_snr:
            return lambda x, k, s, na: _batch_with_keys(
                x, k, cfg, s, num_active=na)
        return lambda x, k, na: _batch_with_keys(x, k, cfg, None, num_active=na)


@functools.lru_cache(maxsize=256)
def _cached_mode_aggregate_fn(cfg: TransportConfig, with_snr: bool,
                              donate: bool = False):
    """The :func:`_cached_mode_batch_fn` twin for the fused-aggregate path:
    one jitted single-mode batch+aggregate per (config, snr-arity). This jit
    is the *outermost* boundary of a bucket launch, so ``donate`` twins
    declare the payload donation here (inner jits inline)."""
    kwargs = {"donate_argnums": (0,)} if donate else {}
    if with_snr:
        return jax.jit(lambda x, k, s, w, na: _batch_aggregate_with_keys(
            x, k, cfg, s, w, num_active=na), **kwargs)
    return jax.jit(lambda x, k, w, na: _batch_aggregate_with_keys(
        x, k, cfg, None, w, num_active=na), **kwargs)


def _mode_aggregate_fn(cfg: TransportConfig, with_snr: bool,
                       donate: bool = False):
    try:
        return _cached_mode_aggregate_fn(cfg, with_snr,
                                         donate and _donation_supported())
    except TypeError:
        # Unhashable config: unjitted fallback, as in _mode_batch_fn.
        if with_snr:
            return lambda x, k, s, w, na: _batch_aggregate_with_keys(
                x, k, cfg, s, w, num_active=na)
        return lambda x, k, w, na: _batch_aggregate_with_keys(
            x, k, cfg, None, w, num_active=na)


def _scatter_stats(parts_st, order, num_clients):
    """Scatter per-bucket :class:`TxStats` back to client order.

    Concatenates the per-mode stat fields in sorted order and gathers them
    through the inverse of the stable ``order`` permutation. Returns
    ``(stats, inv)`` — ``stats`` without ``mode_idx`` (callers attach their
    own), and ``inv`` so callers can scatter extra per-bucket arrays the
    same way.
    """
    inv = np.empty(num_clients, np.int64)
    inv[order] = np.arange(num_clients)
    inv = jnp.asarray(inv)
    ds, tx, be, nb, boa = (
        jnp.take(jnp.concatenate([getattr(st, f) for st in parts_st]), inv)
        for f in ("data_symbols", "transmissions", "bit_errors", "n_bits",
                  "bits_on_air")
    )
    return TxStats(ds, tx, be, nb, bits_on_air=boa), inv


def _scatter_bucket_parts(parts_x, parts_st, order, num_clients):
    """Scatter per-bucket outputs back to client order.

    The shared tail of every bucketed dispatch (dense adaptive, sparse
    adaptive, the engine's compressed uplink): the payload rows ride the
    same inverse permutation as the :func:`_scatter_stats` stat fields.
    Returns ``(x_hat, stats, inv)``.
    """
    stats, inv = _scatter_stats(parts_st, order, num_clients)
    x_hat = jnp.take(jnp.concatenate(parts_x, axis=0), inv, axis=0)
    return x_hat, stats, inv


def _gather_bucket(x, keys, snr_vec, idx, count, n_payload):
    """Gather one mode bucket's rows and pad to its quarter-octave capacity.

    Payload pads with zero rows; keys/SNR broadcast row 0 (masked rows'
    outputs are discarded, the pads only keep shapes static). Returns
    ``(xb, kb, sb, cap)``.
    """
    xb = jnp.take(x, idx, axis=0)
    kb = jnp.take(keys, idx, axis=0)
    sb = None if snr_vec is None else jnp.take(snr_vec, idx)
    cap = _bucket_capacity(count)
    if cap > count:
        pad = cap - count
        xb = jnp.concatenate([xb, jnp.zeros((pad, n_payload), xb.dtype)])
        kb = jnp.concatenate(
            [kb, jnp.broadcast_to(kb[:1], (pad,) + kb.shape[1:])])
        if sb is not None:
            sb = jnp.concatenate([sb, jnp.broadcast_to(sb[:1], (pad,))])
    return xb, kb, sb, cap


def _slice_stats(st: "TxStats", count: int) -> "TxStats":
    """Drop a padded bucket's masked tail rows from every stat field."""
    return TxStats(st.data_symbols[:count], st.transmissions[:count],
                   st.bit_errors[:count], st.n_bits[:count],
                   bits_on_air=st.bits_on_air[:count])


def _bucketed_adaptive(x, keys, cfgs, mode_np, snr_vec, donate=False):
    """Sort/gather/scatter mixed-mode dispatch over concrete mode counts.

    Clients are stable-argsorted by mode so each mode's clients form one
    contiguous bucket; every bucket runs the fused single-mode engine once
    (kernel path included) on a quarter-octave capacity with the tail
    masked, and outputs scatter back through the inverse permutation. Keys/SNR are
    gathered by client index, so each row is bit-identical to the select
    path and to ``transmit_flat`` under the fold_in schedule.
    """
    num_clients, n_payload = x.shape
    if num_clients == 0:
        # Degenerate empty cohort (e.g. every client dropped): agree with
        # the select dispatch's empty vmap output instead of concatenating
        # zero buckets.
        empty = jnp.zeros((0,), jnp.float32)
        return x, TxStats(empty, empty, empty, empty, bits_on_air=empty)
    order = np.argsort(mode_np, kind="stable")
    counts = np.bincount(mode_np, minlength=len(cfgs))
    starts = np.concatenate([[0], np.cumsum(counts)])
    parts_x, parts_st = [], []
    for m, cfg in enumerate(cfgs):
        count = int(counts[m])
        if count == 0:
            continue
        idx = jnp.asarray(order[starts[m] : starts[m] + count])
        xb, kb, sb, _ = _gather_bucket(x, keys, snr_vec, idx, count,
                                       n_payload)
        fn = _mode_batch_fn(cfg, sb is not None, donate)
        na = jnp.int32(count)
        xh, st = fn(xb, kb, na) if sb is None else fn(xb, kb, sb, na)
        parts_x.append(xh[:count])
        parts_st.append(_slice_stats(st, count))
    x_hat, stats, _ = _scatter_bucket_parts(parts_x, parts_st, order,
                                            num_clients)
    return x_hat, stats


def _bucketed_adaptive_aggregate(x, keys, cfgs, mode_np, snr_vec, weights,
                                 donate=False):
    """Bucketed mixed-mode dispatch with per-bucket fused aggregation.

    Each mode bucket produces its own weighted partial sum (kernel
    accumulator or scan fallback, masked padding excluded via
    ``num_active``); the partials add in increasing mode-index order — the
    documented summation-order contract of the adaptive aggregate (NOT the
    raw client order: a mixed-mode cohort regroups the sum by bucket).
    Weights must be pre-normalized *globally*, before the bucket split.
    """
    num_clients, n_payload = x.shape
    if num_clients == 0:
        empty = jnp.zeros((0,), jnp.float32)
        return (jnp.zeros((n_payload,), jnp.float32),
                TxStats(empty, empty, empty, empty, bits_on_air=empty))
    order = np.argsort(mode_np, kind="stable")
    counts = np.bincount(mode_np, minlength=len(cfgs))
    starts = np.concatenate([[0], np.cumsum(counts)])
    total = None
    parts_st = []
    for m, cfg in enumerate(cfgs):
        count = int(counts[m])
        if count == 0:
            continue
        idx = jnp.asarray(order[starts[m] : starts[m] + count])
        xb, kb, sb, cap = _gather_bucket(x, keys, snr_vec, idx, count,
                                         n_payload)
        wb = jnp.take(jnp.asarray(weights, jnp.float32), idx)
        if cap > count:
            wb = jnp.concatenate(
                [wb, jnp.zeros((cap - count,), jnp.float32)])
        fn = _mode_aggregate_fn(cfg, sb is not None, donate)
        na = jnp.int32(count)
        agg, st = (fn(xb, kb, wb, na) if sb is None
                   else fn(xb, kb, sb, wb, na))
        total = agg if total is None else total + agg
        parts_st.append(_slice_stats(st, count))
    stats, _ = _scatter_stats(parts_st, order, num_clients)
    return total, stats


def _vary_like(tree, ref):
    """Mark ``tree``'s leaves as varying over every manual mesh axis ``ref``
    varies over. A no-op outside ``shard_map``; inside it, the branches of
    one ``lax.switch`` then agree even where a mode's stat is a constant
    (uncoded and ECRT rows count no bit errors)."""
    vma = jax.typeof(ref).vma

    def cast(leaf):
        missing = tuple(sorted(vma - jax.typeof(leaf).vma))
        return jax.lax.pcast(leaf, missing, to="varying") if missing else leaf

    return jax.tree.map(cast, tree)


def _select_adaptive(x, keys, cfgs, mode_idx, snr_vec):
    """Per-client ``lax.switch`` over the table, vmapped over clients: one
    fused XLA program, but the switch lowers to a select over all branches
    (every client pays every mode's FLOPs)."""
    if snr_vec is None:
        branches = [
            lambda xc, kc, cfg=cfg: _vary_like(transmit_flat(xc, kc, cfg), xc)
            for cfg in cfgs
        ]
        return jax.vmap(
            lambda xc, kc, m: jax.lax.switch(m, branches, xc, kc)
        )(x, keys, mode_idx)
    branches = [
        lambda xc, kc, s, cfg=cfg: _vary_like(
            transmit_flat(xc, kc, cfg, snr_db=s), xc)
        for cfg in cfgs
    ]
    return jax.vmap(
        lambda xc, kc, s, m: jax.lax.switch(m, branches, xc, kc, s)
    )(x, keys, snr_vec, mode_idx)


def _adaptive_prologue(x, key, cfgs, mode_idx, snr_db, client_offset,
                       dispatch, caller):
    """Shared validation/normalization head of the adaptive dispatches.

    Validates the payload shape and the shared-channel invariant,
    canonicalizes array-valued snr_db configs to one hashable channel,
    resolves the dispatch strategy against mode concreteness, clamps the
    mode vector, and builds the fold_in key schedule. Returns
    ``(x, cfgs, mode_arr, snr_vec, keys, dispatch)``.
    """
    x = jnp.asarray(x, jnp.float32)
    if x.ndim != 2:
        raise ValueError(f"{caller} wants (num_clients, N); got {x.shape}")
    cfgs = tuple(cfgs)
    if not cfgs:
        raise ValueError(f"{caller} needs a non-empty config table")
    for cfg in cfgs:
        if not _same_channel(cfg.channel, cfgs[0].channel):
            raise ValueError(
                "all adaptive mode configs must share one ChannelConfig; "
                f"got {cfg.channel} vs {cfgs[0].channel}"
            )
    # Normalize representation differences (scalar vs 0-d vs length-1
    # snr_db) so every row resolves SNR identically, and canonicalize an
    # array-valued snr_db to a hashable tuple — otherwise the per-mode jit
    # cache (keyed on the config) falls back to eager per-op dispatch for
    # every bucket of every round.
    ch0 = cfgs[0].channel
    try:
        hash(ch0)
    except TypeError:
        try:
            ch0 = dataclasses.replace(ch0, snr_db=tuple(
                float(v)
                for v in np.asarray(ch0.snr_db, np.float32).reshape(-1)))
        except (TypeError, ValueError):
            pass  # e.g. a traced snr_db: the unjitted fallback still works
    cfgs = tuple(
        cfg if cfg.channel is ch0
        else dataclasses.replace(cfg, channel=ch0)
        for cfg in cfgs
    )
    num_clients = x.shape[0]
    mode_concrete = not isinstance(mode_idx, jax.core.Tracer)
    if dispatch == "auto":
        dispatch = "bucketed" if mode_concrete else "select"
    if dispatch not in ("bucketed", "select"):
        raise ValueError(f"unknown dispatch {dispatch!r}; use bucketed|select")
    if dispatch == "bucketed" and not mode_concrete:
        raise ValueError(
            "bucketed dispatch needs a concrete mode_idx (bucket sizes are "
            "host-side); inside jit/shard_map with a traced mode vector use "
            "dispatch='select'"
        )
    if dispatch == "select" and any(cfg.use_kernel for cfg in cfgs):
        raise ValueError(
            "use_kernel configs cannot take the select dispatch; the Pallas "
            "grid does not lower inside a vmapped lax.switch — use the "
            "bucketed dispatch (concrete mode_idx)"
        )
    if dispatch == "bucketed":
        mode_arr = np.asarray(mode_idx, np.int32)
    else:
        mode_arr = jnp.asarray(mode_idx, jnp.int32)
    if mode_arr.shape != (num_clients,):
        raise ValueError(
            f"mode_idx must be ({num_clients},) to match the batch; got "
            f"{mode_arr.shape}"
        )
    # Clamp once, up front: the dispatch and the recorded stats.mode_idx
    # must agree on the mode each client actually used — a stray -1 would
    # otherwise transmit as cfgs[0] (lax.switch clamps) yet price as the
    # *last* row downstream (jnp indexing wraps negatives).
    mode_arr = (np.clip if dispatch == "bucketed" else jnp.clip)(
        mode_arr, 0, len(cfgs) - 1)
    snr_vec = _resolve_batch_snr(cfgs[0], num_clients, snr_db)
    keys = client_keys(key, num_clients, client_offset)
    return x, cfgs, mode_arr, snr_vec, keys, dispatch


def transmit_batch_adaptive(x: jax.Array, key: jax.Array,
                            cfgs, mode_idx, *, snr_db=None, client_offset=0,
                            dispatch: str = "auto", donate: bool = False):
    """Mixed-mode batched uplink: client ``i`` uses ``cfgs[mode_idx[i]]``.

    The link-adaptation dispatch (paper Sec. I: deliver gradients with errors
    "when the channel quality is satisfactory", protect otherwise): a policy
    upstream picks a transport config per client per round and the whole
    cohort runs through the fused batched engine. See the module docstring
    for the two dispatch strategies; the short version:

    * ``"bucketed"`` — sort/gather/scatter per-mode buckets, each mode runs
      once, O(num_clients) total work, Pallas-kernel rows allowed. Needs a
      *concrete* (non-traced) ``mode_idx``.
    * ``"select"`` — vmapped ``lax.switch``: one XLA program even with a
      traced ``mode_idx``, but ~``len(cfgs)``x the FLOPs and no kernel rows.
    * ``"auto"`` (default) — bucketed when ``mode_idx`` is concrete, select
      otherwise.

    Args:
      x: ``(num_clients, N)`` payload matrix.
      key: base PRNG key; the :func:`client_keys` fold_in schedule is shared
        with :func:`transmit_batch`, so row ``i`` is bit-identical to
        ``transmit_flat(x[i], fold_in(key, client_offset + i), cfgs[m_i])``
        under **either** dispatch (the bucketed key rides the client index,
        not the bucket slot).
      cfgs: sequence of :class:`TransportConfig` — the mode table. All
        entries must share one ``ChannelConfig`` (the physical link does not
        depend on the chosen transport); equal-valued configs of different
        shapes (scalar vs length-1 snr_db) are normalized to ``cfgs[0]``'s.
        ``use_kernel`` rows are accepted on the bucketed path and rejected
        on the select path (the Pallas grid cannot lower inside a vmapped
        switch).
      mode_idx: ``(num_clients,)`` integer vector of table indices.
        Out-of-range values clamp (matching ``lax.switch``), and the
        *clamped* vector is what ``stats.mode_idx`` records — so airtime
        pricing always sees the mode that actually transmitted.
      snr_db: optional per-client SNR override (scalar or ``(num_clients,)``),
        resolved against the shared channel config.
      client_offset: global index of row 0 (as in :func:`transmit_batch`).
      dispatch: ``"auto" | "bucketed" | "select"``.
      donate: release bucket payload buffers (fresh gathers) into their
        launches on the bucketed dispatch; a no-op on select and on
        backends without donation.

    Returns:
      ``(x_hat, stats)`` as :func:`transmit_batch`; ``stats.mode_idx`` holds
      the per-client mode vector.
    """
    x, cfgs, mode_arr, snr_vec, keys, dispatch = _adaptive_prologue(
        x, key, cfgs, mode_idx, snr_db, client_offset, dispatch,
        "transmit_batch_adaptive")
    if dispatch == "bucketed":
        x_hat, stats = _bucketed_adaptive(x, keys, cfgs, mode_arr, snr_vec,
                                          donate)
    else:
        x_hat, stats = _select_adaptive(x, keys, cfgs, mode_arr, snr_vec)
    stats.mode_idx = jnp.asarray(mode_arr, jnp.int32)
    return x_hat, stats


def aggregate_words(n: int, cfg: TransportConfig) -> int:
    """The length a running aggregate of an ``n``-float payload is best
    carried at between :func:`transmit_batch_aggregate` calls: the kernel's
    padded length on the kernel path, ``n`` elsewhere."""
    if cfg.mode in ("naive", "approx") and cfg.use_kernel:
        from repro.kernels import ops as kernel_ops

        return kernel_ops.padded_words(n)
    return n


def transmit_batch_aggregate(x: jax.Array, key: jax.Array,
                             cfg: TransportConfig, weights, *, snr_db=None,
                             client_offset=0, donate: bool = False, acc=None):
    """Fused uplink + aggregation: ``sum_c weights[c] * x_hat[c]`` in one pass.

    The hot-path twin of :func:`transmit_batch` followed by
    ``aggregation.fedsgd_aggregate_batch``: on the kernel path
    (``cfg.use_kernel``) the weighted sum accumulates *inside* the Pallas
    grid over the client axis and the per-client demapped payload never
    materializes in HBM — only the ``(N,)`` f32 aggregate and the per-client
    bit-error side-output come back. Bit-identical to the layered
    composition (same kernel rows, same scan-shaped accumulation; pinned by
    ``tests/test_fused_aggregate.py``).

    Args:
      x: ``(num_clients, N)`` payload matrix.
      key / cfg / snr_db / client_offset: as :func:`transmit_batch` — the
        fold_in key schedule is shared, so the per-client channel
        realizations are exactly ``transmit_batch``'s.
      weights: ``(num_clients,)`` aggregation weights, applied as given —
        pass them through :func:`repro.core.aggregation.normalize_weights`
        first (``fedsgd_aggregate_batch`` normalizes the same way).
      donate: release the ``x`` buffer into the launch on backends that
        honour donation (the uplink payload is dead after transmission).
      acc: optional float32 aggregate of the clients before these (a wave
        of a streamed cohort): the sum continues from it, in client order,
        so streaming in waves gives the one-launch sum bit for bit. Of
        length ``N``, or :func:`aggregate_words` ``(N, cfg)``, which comes
        back at that length.

    Returns:
      ``(agg, stats)``: the ``(N,)`` float32 weighted aggregate and
      per-client :class:`TxStats` (``(num_clients,)`` fields — BER reporting
      survives the fusion via the kernel's error side-output).
    """
    x = jnp.asarray(x, jnp.float32)
    if x.ndim != 2:
        raise ValueError(
            f"transmit_batch_aggregate wants (num_clients, N); got {x.shape}")
    num_clients = x.shape[0]
    snr_vec = _resolve_batch_snr(cfg, num_clients, snr_db)
    keys = client_keys(key, num_clients, client_offset)
    return _batch_aggregate_with_keys(x, keys, cfg, snr_vec, weights,
                                      donate=donate, acc=acc)


def transmit_batch_adaptive_aggregate(x: jax.Array, key: jax.Array, cfgs,
                                      mode_idx, weights, *, snr_db=None,
                                      client_offset=0, donate: bool = False):
    """Mixed-mode fused uplink + aggregation (bucketed dispatch only).

    :func:`transmit_batch_adaptive` with the aggregation folded into each
    mode bucket: bucket ``m`` reduces its clients to one weighted partial
    (kernel accumulator on ``use_kernel`` rows) and the partials add in
    increasing mode-index order. That bucket regrouping is the *documented*
    summation order — on a single-mode cohort it degenerates to the plain
    client-order scan and the result is bit-identical to
    :func:`transmit_batch_aggregate`. Needs a concrete ``mode_idx`` (the
    select lowering has no kernel rows and nothing to fuse); ``weights``
    must be pre-normalized globally (before the bucket split — per-bucket
    renormalization would change the estimator).

    Returns ``(agg (N,) float32, stats)``; ``stats.mode_idx`` holds the
    per-client mode vector, stats fields are in client order.
    """
    x, cfgs, mode_arr, snr_vec, keys, _ = _adaptive_prologue(
        x, key, cfgs, mode_idx, snr_db, client_offset, "bucketed",
        "transmit_batch_adaptive_aggregate")
    agg, stats = _bucketed_adaptive_aggregate(x, keys, cfgs, mode_arr,
                                              snr_vec, weights, donate)
    stats.mode_idx = jnp.asarray(mode_arr, jnp.int32)
    return agg, stats


def transmit_pytree(tree: Any, key: jax.Array, cfg: TransportConfig):
    """Transmit every leaf of a pytree as one flat uplink payload."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sizes = [l.size for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    flat_hat, stats = transmit_flat(flat, key, cfg)
    out, off = [], 0
    for leaf, size in zip(leaves, sizes):
        out.append(flat_hat[off : off + size].reshape(leaf.shape).astype(leaf.dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, out), stats


def _flatten_client_tree(tree: Any):
    """Stack a ``(num_clients, ...)``-leaved pytree into one (C, D) matrix."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    num_clients = leaves[0].shape[0]
    sizes = [l.size // num_clients for l in leaves]
    flat = jnp.concatenate(
        [l.reshape(num_clients, -1).astype(jnp.float32) for l in leaves], axis=1
    )
    return flat, (leaves, treedef, sizes)


def _unflatten_client_tree(flat_hat: jax.Array, spec) -> Any:
    leaves, treedef, sizes = spec
    out, off = [], 0
    for leaf, size in zip(leaves, sizes):
        out.append(
            flat_hat[:, off : off + size].reshape(leaf.shape).astype(leaf.dtype)
        )
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)


def transmit_pytree_batch(tree: Any, key: jax.Array, cfg: TransportConfig, *,
                          snr_db=None):
    """Batched :func:`transmit_pytree`: every leaf has a leading client dim.

    Args:
      tree: pytree whose leaves are ``(num_clients, ...)`` — e.g. the output
        of ``jax.vmap(client_grad)``. Each client's leaves are flattened into
        one ``(num_clients, D)`` payload matrix.
      key / cfg / snr_db: as in :func:`transmit_batch`.

    Returns:
      ``(tree_hat, stats)`` with the input structure/shapes/dtypes restored
      and per-client :class:`TxStats` (``(num_clients,)`` fields).
    """
    with jax.named_scope("fl_uplink"):
        flat, spec = _flatten_client_tree(tree)
        flat_hat, stats = transmit_batch(flat, key, cfg, snr_db=snr_db)
        return _unflatten_client_tree(flat_hat, spec), stats


def transmit_pytree_batch_adaptive(tree: Any, key: jax.Array, cfgs, mode_idx,
                                   *, snr_db=None, dispatch: str = "auto"):
    """Pytree front-end of :func:`transmit_batch_adaptive`.

    Same flatten/transmit/unflatten contract as :func:`transmit_pytree_batch`
    with a per-client mode table dispatch — the entry point the
    scenario-driven FL loops feed each round's gradients through.
    """
    with jax.named_scope("fl_uplink"):
        flat, spec = _flatten_client_tree(tree)
        flat_hat, stats = transmit_batch_adaptive(
            flat, key, cfgs, mode_idx, snr_db=snr_db, dispatch=dispatch)
        return _unflatten_client_tree(flat_hat, spec), stats


def _unflatten_aggregate_tree(flat_agg: jax.Array, spec) -> Any:
    """Restore an aggregated ``(D,)`` payload to the client-tree structure
    with the leading client axis reduced away (leaf ``(C, ...)`` -> ``(...)``).
    The aggregate stays float32 regardless of leaf dtype — it feeds the f32
    optimizer update, and a bf16 round-trip would throw away accumulator
    precision the fused kernel just paid for."""
    leaves, treedef, sizes = spec
    out, off = [], 0
    for leaf, size in zip(leaves, sizes):
        out.append(flat_agg[off : off + size].reshape(leaf.shape[1:]))
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)


def transmit_pytree_batch_aggregate(tree: Any, key: jax.Array,
                                    cfg: TransportConfig, weights, *,
                                    snr_db=None, donate: bool = False):
    """Pytree front-end of :func:`transmit_batch_aggregate`.

    Flattens the ``(num_clients, ...)``-leaved payload tree into one
    ``(C, D)`` matrix, runs the fused uplink+aggregation, and restores the
    aggregate to the tree structure with the client axis reduced away —
    the shape ``algo.apply`` expects from the layered
    ``fedsgd_aggregate_batch`` tail.
    """
    with jax.named_scope("fl_uplink"):
        flat, spec = _flatten_client_tree(tree)
        agg, stats = transmit_batch_aggregate(
            flat, key, cfg, weights, snr_db=snr_db, donate=donate)
        return _unflatten_aggregate_tree(agg, spec), stats


def transmit_pytree_batch_adaptive_aggregate(tree: Any, key: jax.Array, cfgs,
                                             mode_idx, weights, *,
                                             snr_db=None,
                                             donate: bool = False):
    """Pytree front-end of :func:`transmit_batch_adaptive_aggregate` — the
    entry point the scenario-driven fused FL rounds feed each round's
    gradients through (bucketed dispatch, globally pre-normalized weights).
    """
    with jax.named_scope("fl_uplink"):
        flat, spec = _flatten_client_tree(tree)
        agg, stats = transmit_batch_adaptive_aggregate(
            flat, key, cfgs, mode_idx, weights, snr_db=snr_db, donate=donate)
        return _unflatten_aggregate_tree(agg, spec), stats


def _broadcast_payload(x: jax.Array, num_clients: int) -> jax.Array:
    """Validate + tile one flat payload to a ``(num_clients, N)`` batch."""
    x = jnp.asarray(x, jnp.float32)
    if x.ndim != 1:
        raise ValueError(f"broadcast wants a flat (N,) payload; got {x.shape}")
    keylanes.check_cohort(DOWNLINK_KEY_LANE, num_clients)
    return jnp.broadcast_to(x, (num_clients, x.shape[0]))


def transmit_broadcast(x: jax.Array, key: jax.Array, cfg: TransportConfig,
                       num_clients: int, *, snr_db=None):
    """Broadcast one payload through ``num_clients`` independent downlinks.

    The downlink leg of an FL round: the PS transmits the global model once
    and every client hears it over its *own* fading channel — same bits in,
    per-client corrupted copies out. Runs the shared ``_batch_with_keys``
    engine on the tiled payload; client ``i``'s key is
    ``fold_in(key, DOWNLINK_KEY_LANE + i)`` (see :data:`DOWNLINK_KEY_LANE`),
    so the caller may reuse the round's uplink base key and the two legs
    stay decorrelated, with uplink draws unchanged vs a downlink-free run.

    Args:
      x: ``(N,)`` global payload (cast to float32).
      key: base PRNG key — typically the same key the round's uplink uses.
      cfg: downlink transport configuration.
      num_clients: number of receiving clients.
      snr_db: optional per-client downlink SNR (scalar or ``(num_clients,)``),
        overriding ``cfg.channel.snr_db``.

    Returns:
      ``(x_hat, stats)``: ``(num_clients, N)`` received copies and
      :class:`TxStats` with ``(num_clients,)`` fields. Note the broadcast is
      transmitted *once* — ``latency.broadcast_airtime`` prices the round
      from these per-client stats.
    """
    xb = _broadcast_payload(x, num_clients)
    snr_vec = _resolve_batch_snr(cfg, num_clients, snr_db)
    keys = client_keys(key, num_clients, DOWNLINK_KEY_LANE)
    return _batch_with_keys(xb, keys, cfg, snr_vec)


def transmit_broadcast_adaptive(x: jax.Array, key: jax.Array, cfgs, mode_idx,
                                *, snr_db=None, dispatch: str = "auto"):
    """Mixed-mode broadcast: client ``i`` *receives* via ``cfgs[mode_idx[i]]``.

    The downlink counterpart of :func:`transmit_batch_adaptive` — e.g. a
    policy table picks a protected transport for clients whose downlink CSI
    is poor. Same dispatch strategies and validation; keys ride the
    downlink lane (``client_offset=DOWNLINK_KEY_LANE``).
    """
    num_clients = int(np.shape(mode_idx)[0])
    xb = _broadcast_payload(x, num_clients)
    return transmit_batch_adaptive(
        xb, key, cfgs, mode_idx, snr_db=snr_db,
        client_offset=DOWNLINK_KEY_LANE, dispatch=dispatch)


def _flatten_global_tree(tree: Any):
    """Flatten a client-dim-free pytree into one ``(D,)`` payload vector."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sizes = [l.size for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    return flat, (leaves, treedef, sizes)


def _unflatten_broadcast_tree(flat_hat: jax.Array, spec) -> Any:
    """Restore a broadcast ``(num_clients, D)`` matrix to a stacked pytree."""
    leaves, treedef, sizes = spec
    num_clients = flat_hat.shape[0]
    out, off = [], 0
    for leaf, size in zip(leaves, sizes):
        out.append(flat_hat[:, off : off + size]
                   .reshape((num_clients,) + leaf.shape).astype(leaf.dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)


def transmit_pytree_broadcast(tree: Any, key: jax.Array, cfg: TransportConfig,
                              num_clients: int, *, snr_db=None):
    """Broadcast a whole pytree (e.g. the global model) to every client.

    Flattens the client-dim-free ``tree`` into one payload, broadcasts it via
    :func:`transmit_broadcast`, and returns a pytree whose leaves grew a
    leading ``(num_clients,)`` dimension — client ``i``'s received copy is
    ``tree_map(lambda l: l[i], out)``. ``stats`` fields are per-client.
    """
    with jax.named_scope("fl_downlink"):
        flat, spec = _flatten_global_tree(tree)
        flat_hat, stats = transmit_broadcast(flat, key, cfg, num_clients,
                                             snr_db=snr_db)
        return _unflatten_broadcast_tree(flat_hat, spec), stats


def transmit_pytree_broadcast_adaptive(tree: Any, key: jax.Array, cfgs,
                                       mode_idx, *, snr_db=None,
                                       dispatch: str = "auto"):
    """Pytree front-end of :func:`transmit_broadcast_adaptive`."""
    with jax.named_scope("fl_downlink"):
        flat, spec = _flatten_global_tree(tree)
        flat_hat, stats = transmit_broadcast_adaptive(
            flat, key, cfgs, mode_idx, snr_db=snr_db, dispatch=dispatch)
        return _unflatten_broadcast_tree(flat_hat, spec), stats


def transmit_sparse(values: jax.Array, indices: jax.Array, dim: int,
                    key: jax.Array, cfg: TransportConfig, compression=None, *,
                    snr_db=None):
    """Transmit one client's sparse ``(values, indices)`` payload.

    The compressed uplink (see the module docstring's "Sparse uplinks"):
    the ``(k,)`` value payload rides the configured transport under ``key``
    and the ``(k,)`` index header rides protected bits on the header key
    lane; the receiver scatters the values back to a dense ``(dim,)``
    vector. ``compression`` is a
    :class:`repro.compress.sparsify.CompressionConfig` choosing the header
    protection (default config if ``None``). Returns ``(x_hat_dense,
    stats)`` with combined header+payload :class:`TxStats` (including
    ``bits_on_air``). Delegates to :func:`repro.compress.framing.transmit_sparse`.
    """
    from repro.compress import framing as framing_lib

    return framing_lib.transmit_sparse(values, indices, dim, key, cfg,
                                       compression, snr_db=snr_db)


def transmit_sparse_batch(values: jax.Array, indices: jax.Array, dim: int,
                          key: jax.Array, cfg: TransportConfig,
                          compression=None, *, snr_db=None, client_offset=0):
    """Batched :func:`transmit_sparse` under the shared fold_in key schedule.

    Client ``i`` uses ``fold_in(key, client_offset + i)`` — bit-identical
    to a per-client loop of :func:`transmit_sparse`, exactly as
    :func:`transmit_batch` is to :func:`transmit_flat`. Delegates to
    :func:`repro.compress.framing.transmit_sparse_batch`.
    """
    from repro.compress import framing as framing_lib

    return framing_lib.transmit_sparse_batch(
        values, indices, dim, key, cfg, compression, snr_db=snr_db,
        client_offset=client_offset)
