"""Bring-up smoke run of the approximate-wireless FL round on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the cross-chip path only, on four chips

One chip: the sine and cosine of every Box-Muller angle (all 2^24 values of
``2*pi*uniform01(h)``), by ``jnp.sin`` / ``jnp.cos`` in XLA and inside a
Pallas kernel and by the kernel's fast ``sincos_2pi``, against float64,
within the bounds the kernel's exact-recompute margin assumes; both Pallas
uplink kernels (batch and fused-aggregate, masked and unmasked) against the
``kernels/ref.py`` oracle for QPSK / 16-QAM / 256-QAM on the f32 and bf16
wires, at the paper's cohort (100 clients) and CNN width (21,840
parameters, padded to 22 tiles of 1,024 words), with the share of tiles
that took the kernel's exact path; then 5 FedSGD rounds
of ``repro.fl.loop.run_fl`` with the fused round, compared with the layered
round from the same seed, on the ``vehicular`` scenario and on the paper's
static single-mode uplink.

Four chips: ``launch.sharding.shard_transmit_batch`` (kernel rows on) and
``shard_transmit_batch_adaptive`` over a 4-device ``data`` mesh, compared
bit for bit with the unsharded calls on one device.

Runs in one process and starts none. Exits non-zero, printing no result,
when JAX finds no TPU. The last stdout line is the JSON result
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import aggregation as agg_lib  # noqa: E402
from repro.core import channel as channel_lib  # noqa: E402
from repro.core import float_codec  # noqa: E402
from repro.core import transport as transport_lib  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.kernels.ops import default_interpret  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

kernels = importlib.import_module("repro.kernels.approx_channel")

CLIENTS = 100  # the paper's cohort
PAYLOAD = 21_840  # parameters of the paper's CNN (configs/mnist_cnn.py)
BLOCK_WORDS = 1024
G0 = 1e-3  # large-scale gain: tx power x d^-alpha at d=10, alpha=3
MODULATIONS = (("qpsk", 2), ("16qam", 4), ("256qam", 8))
N_ACTIVE = 77  # masked grids compute this many of the CLIENTS rows
BER_SIGMAS = 5.0  # kernel BER must sit within this many standard errors
MAX_DIFF_FRACTION = 1e-2  # of words, when kernel and oracle are not identical
LOSS_RTOL = 1e-3  # fused vs layered FL rounds: relative test-loss agreement


def log(msg: str) -> None:
    print(msg, flush=True)


def _bits(a) -> np.ndarray:
    """Raw bit patterns, so NaN payloads compare exactly."""
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def _timed(fn, *args):
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


# ---------------------------------------------------------------- one chip


def _cohort(key, c: int, n: int):
    """A gradient-like cohort: payload in the clamp's range, per-client
    seeds and SNR spread over 5..25 dB."""
    kx, ks, kw = jax.random.split(key, 3)
    x = jax.random.uniform(kx, (c, n), minval=-1.9, maxval=1.9)
    npad = -(-n // BLOCK_WORDS) * BLOCK_WORDS
    x = jnp.pad(x, ((0, 0), (0, npad - n)))
    seeds = jax.random.randint(ks, (c,), 0, 2**31 - 1).astype(jnp.uint32)
    snr_db = jnp.linspace(5.0, 25.0, c)
    noise = (G0 / 10.0 ** (snr_db / 10.0)).astype(jnp.float32)
    gains = jnp.full((c,), G0, jnp.float32)
    weights = agg_lib.normalize_weights(
        jax.random.uniform(kw, (c,), minval=0.5, maxval=1.5))
    return x, seeds, noise, gains, weights


def _flips_per_row(sent: np.ndarray, received: np.ndarray) -> np.ndarray:
    """Flipped bits per row, counted on the host (the counters' referee)."""
    diff = np.ascontiguousarray(sent ^ received)
    return np.unpackbits(diff.view(np.uint8), axis=-1).reshape(
        diff.shape[0], -1).sum(axis=1)


def _ber_agrees(err_k: int, err_r: int, n_bits: int) -> tuple[bool, float]:
    """Two estimates of one BER over ``n_bits`` bits: is their difference
    within ``BER_SIGMAS`` standard errors of a difference of two binomial
    proportions? Returns (ok, z)."""
    p = max((err_k + err_r) / (2 * n_bits), 1.0 / n_bits)
    sigma = math.sqrt(2.0 * p * (1.0 - p) / n_bits)
    z = abs(err_k - err_r) / n_bits / sigma
    return z <= BER_SIGMAS, z


def bf16_subnormal_probe() -> dict:
    """Where, if anywhere, a bf16 subnormal loses its bits on this backend.

    The bf16 wire leaves the kernel (and the oracle) as uint32 words that
    ``kref.wire_values`` narrows to uint16 and bitcasts to bf16. Returns, for
    each stage, whether subnormal and ordinary bf16 bit patterns survive."""
    pats = np.array([0x0001, 0x8005, 0x007F, 0x0040, 0x3F80, 0xBF80, 0x0000],
                    np.uint32)
    u = jnp.asarray(pats)
    stages = {
        "u16": jax.jit(lambda v: v.astype(jnp.uint16)),
        "bf16": jax.jit(lambda v: kref.wire_values(v, 16)),
        "bf16_to_u16": jax.jit(lambda v: jax.lax.bitcast_convert_type(
            kref.wire_values(v, 16), jnp.uint16)),
    }
    return {name: bool((_bits(fn(u)).astype(np.uint32) == pats).all())
            for name, fn in stages.items()}


def _angle_tables(interpret: bool, rows: int = 512):
    """``(sin, cos, fast_sin, fast_cos)`` of all 2^24 Box-Muller angles,
    computed inside a Pallas kernel: ``jnp.sin`` / ``jnp.cos`` and the
    uplink kernel's ``sincos_2pi``."""
    from jax.experimental import pallas as pl

    n_rows = (1 << 24) // kref.LANES

    def body(sin_ref, cos_ref, fsin_ref, fcos_ref):
        i = pl.program_id(0) * (rows * kref.LANES) + kref.tile_word_index(
            sin_ref.shape)
        ang = jnp.float32(kref._TWO_PI) * kref.uniform01(
            kref._u32(i) << jnp.uint32(8))
        sin_ref[...] = jnp.sin(ang)
        cos_ref[...] = jnp.cos(ang)
        fsin_ref[...], fcos_ref[...] = kernels.sincos_2pi(ang)

    spec = pl.BlockSpec((rows, kref.LANES), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct((n_rows, kref.LANES), jnp.float32)
    return pl.pallas_call(body, grid=(n_rows // rows,),
                          out_specs=[spec] * 4, out_shape=[shape] * 4,
                          interpret=interpret)()


def sincos_vs_float64() -> bool:
    """The platform's sine and cosine, in XLA and inside a kernel, and the
    kernel's fast pair, over every angle ``2*pi*uniform01(h)`` (2^24 of
    them), against float64: the largest absolute errors, in units of
    2^-24, against ``PLATFORM_SINCOS_ERR`` and ``SINCOS_ERR``."""
    unit = 2.0**-24
    i = jnp.arange(1 << 24, dtype=jnp.int32)
    ang = jax.jit(lambda i: jnp.float32(kref._TWO_PI) * kref.uniform01(
        kref._u32(i) << jnp.uint32(8)))(i)
    xla = jax.jit(lambda a: (jnp.sin(a), jnp.cos(a)))(ang)
    tables = _angle_tables(default_interpret())
    a64 = np.asarray(ang, np.float64)
    true = (np.sin(a64), np.cos(a64))
    limit = {"xla": kernels.PLATFORM_SINCOS_ERR,
             "kernel": kernels.PLATFORM_SINCOS_ERR,
             "fast": kernels.SINCOS_ERR}
    got = {"xla": xla, "kernel": tables[:2], "fast": tables[2:]}
    ok = True
    for name, (sin, cos) in got.items():
        err = [float(np.max(np.abs(np.asarray(v, np.float64).reshape(-1) - t)))
               for v, t in zip((sin, cos), true)]
        within = max(err) <= limit[name]
        ok &= within
        log(f"sincos_vs_float64 {name:6s}: max_abs_error sin="
            f"{err[0] / unit:.4g} cos={err[1] / unit:.4g} x 2^-24, bound "
            f"{limit[name] / unit:g} x 2^-24 -> {'ok' if within else 'FAIL'}")
    return ok


def kernels_vs_ref(c: int = CLIENTS, n: int = PAYLOAD) -> bool:
    """Both uplink kernels, masked and unmasked, against the oracle.

    Three error counts meet here: the kernel's in-kernel counter, the
    oracle's own (XLA) counter, both over the uint32 words before they are
    narrowed to the wire dtype, and a host recount over the delivered rows.
    The kernel must equal the oracle's counter exactly when the rows are
    bit-identical; the host recount must equal it on the f32 wire, and on
    the bf16 wire where the backend keeps bf16 subnormals. Where the probe
    shows them flushed, the recount may differ by at most the mantissa bits
    of the zero-exponent words."""
    interpret = default_interpret()
    probe = bf16_subnormal_probe()
    bf16_exact = all(probe.values())
    log(f"bf16_subnormal_probe: bits survive {probe}")
    x32, seeds, noise, gains, weights = _cohort(jax.random.PRNGKey(11), c, n)
    n_active = min(N_ACTIVE, c - 1)
    ok = True
    identical_all = True
    for name, k in MODULATIONS:
        for wire, wb in (("float32", 32), ("bfloat16", 16)):
            x = x32.astype(jnp.bfloat16 if wb == 16 else jnp.float32)
            params = dict(
                bits_per_symbol=k, fading="rayleigh", fade_block=64,
                clamp_mask=(float_codec.exponent_clamp_mask16(2.0) if wb == 16
                            else float_codec.exponent_clamp_mask(2.0)),
                block_words=BLOCK_WORDS, word_bits=wb)
            ref_fn = jax.jit(jax.vmap(
                lambda xr, s, p_, g: kref.ref_approx_channel(
                    xr, s, p_, g, valid_words=n, **params)))
            ref_rows, ref_errs = ref_fn(x, seeds, noise, gains)
            ref_errs = np.asarray(ref_errs)
            host_flips = _flips_per_row(_bits(x)[:, :n],
                                        _bits(ref_rows)[:, :n])
            for masked in (False, True):
                na = n_active if masked else c
                extra = {"num_active": jnp.int32(na)} if masked else {}
                batch = jax.jit(lambda *a: kernels.approx_channel_batch_pallas(
                    *a, valid_words=n, interpret=interpret, **params, **extra))
                fused = jax.jit(
                    lambda *a: kernels.approx_channel_batch_aggregate_pallas(
                        *a, valid_words=n, interpret=interpret, **params,
                        **extra))
                (rows, errs), t_first = _timed(batch, x, seeds, noise, gains)
                _, t_steady = _timed(batch, x, seeds, noise, gains)
                (agg, agg_errs, exact), _ = _timed(fused, x, seeds, noise,
                                                   gains, weights)

                kb, rb = _bits(rows), _bits(ref_rows)
                n_diff = int((kb[:na] != rb[:na]).sum())
                identical = n_diff == 0
                identical_all &= identical
                errs, agg_errs = np.asarray(errs), np.asarray(agg_errs)
                n_bits = na * n * wb
                counts_equal = bool((errs[:na] == ref_errs[:na]).all())
                host_equal = bool((errs[:na] == host_flips[:na]).all())
                ber_ok, z = _ber_agrees(int(errs[:na].sum()),
                                        int(ref_errs[:na].sum()), n_bits)
                # Delivered words with a zero exponent field, and those of
                # them that kept a mantissa (subnormals).
                man_bits = wb - 9  # sign + 8 exponent bits on both wires
                words = kb[:na, :n].astype(np.uint32)
                zero_exp = ((words >> man_bits) & 0xFF) == 0
                n_zero_exp = int(zero_exp.sum())
                n_subnormal = int((zero_exp & (
                    (words & ((1 << man_bits) - 1)) != 0)).sum())
                tail_zero = (not masked or (
                    not kb[na:].any() and not errs[na:].any()
                    and not agg_errs[na:].any()))
                # The fused kernel's contract: the client-order scan of the
                # batch kernel's own rows, and the same error counts.
                layered = transport_lib._scan_weighted_sum(
                    rows[:na].astype(jnp.float32), weights[:na])
                fused_identical = bool(
                    (_bits(agg) == _bits(layered)).all()
                    and (agg_errs[:na] == errs[:na]).all())
                exact_share = float(np.asarray(exact)[:na].sum()) / (
                    na * (x.shape[1] // BLOCK_WORDS))
                finite = bool(np.isfinite(np.asarray(
                    rows[:na], np.float32)).all())
                # Identical rows must give identical counters; otherwise the
                # counts may differ, within the BER bound. The host recount
                # sees the delivered words: a flushed bf16 subnormal changes
                # only its own mantissa bits.
                within_flush = bool((np.abs(host_flips[:na] - errs[:na])
                                     <= man_bits * zero_exp.sum(axis=1)).all())
                host_ok = host_equal or (
                    wb == 16 and not bf16_exact and within_flush)
                case_ok = (finite and tail_zero and fused_identical and (
                    counts_equal and host_ok if identical else
                    ber_ok and n_diff <= MAX_DIFF_FRACTION * na * x.shape[1]))
                ok &= case_ok
                log(f"kernel_vs_ref {name:6s} {wire:8s} "
                    f"{'masked' if masked else 'full':6s} rows={na}: "
                    f"differing_words={n_diff} of {na * x.shape[1]} "
                    f"({n_diff / (na * x.shape[1]):.3g}) "
                    f"bit_identical={identical} "
                    f"error_counts_equal_to_oracle={counts_equal} "
                    f"ber_kernel={errs[:na].sum() / n_bits:.6g} "
                    f"ber_oracle={ref_errs[:na].sum() / n_bits:.6g} z={z:.3g} "
                    f"host_recount_equal={host_equal} "
                    f"host_within_flush_bound={within_flush} "
                    f"ber_host_recount={host_flips[:na].sum() / n_bits:.6g} "
                    f"rx_zero_exponent_words={n_zero_exp} "
                    f"rx_subnormal_words={n_subnormal} "
                    f"fused==layered_scan={fused_identical} "
                    f"exact_tile_share={exact_share:.4g} "
                    f"masked_tail_zero={tail_zero} "
                    f"batch_first_s={t_first:.3f} batch_steady_s={t_steady:.4f}"
                    f" -> {'ok' if case_ok else 'FAIL'}")
    log(f"kernel_vs_ref: all cases bit-identical to the oracle: "
        f"{identical_all}; BER bound {BER_SIGMAS} standard errors of a "
        f"binomial difference; differing words allowed "
        f"<= {MAX_DIFF_FRACTION:g} when not identical")
    return ok


def _fl_world(n_clients: int, per_client: int):
    from repro.data import synth_mnist
    from repro.fl import partition

    (img, lab), (ti, tl) = synth_mnist.train_test(600, 100, seed=0)
    parts = partition.non_iid_partition(img, lab, n_clients=n_clients, seed=0)
    cx, cy = partition.stack_clients(parts, per_client=per_client, seed=0)
    return cx, cy, ti, tl


def _uplink_lowerings(mode_cfgs, counts, payload: int) -> dict:
    """StableHLO of the round's uplink legs, fused and layered, for one
    round's mode mix (bucketed dispatch, kernel rows included)."""
    mode_np = np.repeat(np.arange(len(counts)), counts).astype(np.int32)
    c = len(mode_np)
    x = jax.ShapeDtypeStruct((c, payload), jnp.float32)
    snr = jax.ShapeDtypeStruct((c,), jnp.float32)
    key = jax.random.PRNGKey(0)
    fused = jax.jit(lambda x, s, w: transport_lib.transmit_batch_adaptive_aggregate(
        x, key, mode_cfgs, mode_np, w, snr_db=s))
    layered = jax.jit(lambda x, s: transport_lib.transmit_batch_adaptive(
        x, key, mode_cfgs, mode_np, snr_db=s, dispatch="bucketed"))
    return {"fused": fused.lower(x, snr, snr).as_text(),
            "layered": layered.lower(x, snr).as_text()}


def _same_bits(a, b) -> tuple[bool, float]:
    """Whether two params pytrees are bit-identical, and their max |diff|."""
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    same = all(bool((_bits(x) == _bits(y)).all()) for x, y in zip(la, lb))
    diff = max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(la, lb))
    return same, diff


def fl_rounds(n_clients: int = CLIENTS, per_client: int = 60,
              n_rounds: int = 5, check_kernel: bool = True) -> bool:
    """``run_fl``, fused vs layered round, on the vehicular scenario (the
    bucketed mixed-mode round) and on the paper's static single-mode uplink
    (``scenario=None``)."""
    from repro.configs import mnist_cnn
    from repro.fl import cnn
    from repro.fl.loop import resolve_scenario, run_fl
    from repro.obs.timers import PhaseTimers

    cfg = mnist_cnn.config()
    cx, cy, ti, tl = _fl_world(n_clients, per_client)
    tcfg = transport_lib.TransportConfig(mode="approx", modulation="qpsk",
                                         use_kernel=True)
    ok = True
    for scenario in ("vehicular", None):
        results = {}
        for fused in (True, False):
            timers = PhaseTimers()
            t = time.perf_counter()
            res = run_fl(cfg, tcfg, cx, cy, ti, tl, n_rounds=n_rounds, seed=0,
                         scenario=scenario, fused_aggregate=fused,
                         phase_timers=timers)
            jax.block_until_ready(res.params)
            wall = time.perf_counter() - t
            loss = float(cnn.loss_fn(res.params, jnp.asarray(ti),
                                     jnp.asarray(tl)))
            n_params = sum(p.size for p in
                           jax.tree_util.tree_leaves(res.params))
            finite = (math.isfinite(loss)
                      and all(map(math.isfinite, res.accuracy)))
            ok &= finite and n_params == PAYLOAD
            rnd = timers.summary()["round"]
            label = "fused" if fused else "layered"
            log(f"fl_rounds {scenario or 'static'} {label}: "
                f"rounds={n_rounds} clients={n_clients} params={n_params} "
                f"test_loss={loss:.6g} accuracy={res.accuracy} "
                f"finite={finite} wall_s={wall:.3f} "
                f"round_first_s={rnd['first_s']:.3f} "
                f"round_steady_median_s={rnd['steady_median_s']:.4f} "
                f"mode_counts={[r['mode_counts'] for r in res.link]}")
            results[label] = (res, loss)
        (f, f_loss), (l, l_loss) = results["fused"], results["layered"]
        same, diff = _same_bits(f.params, l.params)
        # The two rounds sum the cohort differently: by mode bucket
        # (vehicular), or as a client-order scan of (1/M) x_c vs jnp.mean
        # (static); training then amplifies the ULPs. Their exact agreement
        # is checked per launch in kernels_vs_ref.
        close = abs(f_loss - l_loss) <= LOSS_RTOL * abs(l_loss)
        ok &= close
        log(f"fl_rounds {scenario or 'static'}: fused round bit-identical to "
            f"layered round: params={same} (max |diff| {diff:.3g}) "
            f"accuracy={f.accuracy == l.accuracy} "
            f"test_loss_within_{LOSS_RTOL:g}={close}")
        if scenario is not None and check_kernel:
            resolved = resolve_scenario(scenario, tcfg)
            kernel_rows = [i for i, c in enumerate(resolved.mode_cfgs)
                           if c.use_kernel]
            used = int(sum(r["mode_counts"][i] for r in f.link
                           for i in kernel_rows))
            texts = _uplink_lowerings(
                resolved.mode_cfgs, np.asarray(f.link[-1]["mode_counts"]),
                PAYLOAD)
            calls = {k: v.count("tpu_custom_call") for k, v in texts.items()}
            ok &= used > 0 and all(calls.values())
            log(f"fl_rounds {scenario}: client-rounds on kernel rows={used}; "
                f"tpu_custom_call in the lowered uplink of the last round's "
                f"mode mix: {calls}")
    return ok


# ------------------------------------------------------------- four chips


def _uplink_diff(a, b, mode) -> tuple[bool, str]:
    """Whether two ``(x_hat, TxStats)`` uplink results are bit-identical,
    and where they differ: payload words per mode and the error counts."""
    (xa, sa), (xb, sb) = a, b
    words = _bits(xa) != _bits(xb)
    errs_equal = bool((np.asarray(sa.bit_errors)
                       == np.asarray(sb.bit_errors)).all())
    per_mode = [int(words[mode == m].sum()) for m in range(int(mode.max()) + 1)]
    max_diff = float(np.max(np.abs(np.asarray(xa) - np.asarray(xb))))
    same = not words.any() and errs_equal
    return same, (f"differing_words={int(words.sum())} per_mode={per_mode} "
                  f"max_abs_diff={max_diff:.3g} bit_errors_equal={errs_equal}")


def mesh_path(n_dev: int = 4, c: int = CLIENTS, n: int = PAYLOAD) -> bool:
    """Sharded uplinks over an ``n_dev``-device data mesh vs one device."""
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import (shard_transmit_batch,
                                       shard_transmit_batch_adaptive)
    from repro.link import policy as policy_lib

    mesh = make_mesh((n_dev,), ("data",), devices=jax.devices()[:n_dev])
    kx, km = jax.random.split(jax.random.PRNGKey(5))
    x_host = np.asarray(jax.random.uniform(kx, (c, n), minval=-1.9,
                                           maxval=1.9))
    snr_host = np.linspace(5.0, 25.0, c, dtype=np.float32)
    # The same cohort on one device (reference) and split over the mesh.
    one = SingleDeviceSharding(jax.devices()[0])
    x1, snr1 = jax.device_put(x_host, one), jax.device_put(snr_host, one)
    xm = jax.device_put(x_host, NamedSharding(mesh, PartitionSpec("data")))
    snrm = jax.device_put(snr_host, NamedSharding(mesh, PartitionSpec("data")))
    key = jax.random.PRNGKey(7)
    ok = True

    cfg = transport_lib.TransportConfig(
        mode="approx", modulation="qpsk", use_kernel=True,
        channel=channel_lib.ChannelConfig(snr_db=10.0))
    for label, s1, sm in (("homogeneous_snr", None, None),
                          ("per_client_snr", snr1, snrm)):
        ref, rst = jax.jit(lambda x, s: transport_lib.transmit_batch(
            x, key, cfg, snr_db=s))(x1, s1)
        out, ost = jax.jit(lambda x, s: shard_transmit_batch(
            x, key, cfg, mesh, snr_db=s))(xm, sm)
        same = bool((_bits(ref) == _bits(out)).all()) and bool(
            (np.asarray(rst.bit_errors) == np.asarray(ost.bit_errors)).all())
        ok &= same
        log(f"mesh shard_transmit_batch kernel {label}: devices={n_dev} "
            f"clients={c} payload={n} bit_identical_to_one_device={same} "
            f"sharding={out.sharding}")

    cfgs = policy_lib.build_mode_cfgs(
        transport_lib.TransportConfig(
            channel=channel_lib.ChannelConfig(snr_db=10.0)),
        policy_lib.PolicyConfig(), ecrt_expected_tx=2.0)
    mode = np.asarray(jax.random.randint(km, (c,), 0, len(cfgs)), np.int32)
    counts = np.bincount(mode, minlength=len(cfgs)).tolist()

    def select(x, s):
        return transport_lib.transmit_batch_adaptive(
            x, key, cfgs, mode, snr_db=s, dispatch="select")

    ref = jax.jit(select)(x1, snr1)
    out = shard_transmit_batch_adaptive(xm, key, cfgs, mode, mesh,
                                        snr_db=snrm)
    same, detail = _uplink_diff(ref, out, mode)
    ok &= same
    log(f"mesh shard_transmit_batch_adaptive select: devices={n_dev} "
        f"clients={c} modes={counts} bit_identical_to_one_device={same} "
        f"{detail}")
    # Not a check: the same program run op by op on one device.
    _, eager_detail = _uplink_diff(ref, select(x1, snr1), mode)
    log(f"mesh adaptive select, jit vs eager on one device: {eager_detail}")
    return ok


# ------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cross-chip path")
    args = ap.parse_args()

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    log(f"device kind={dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__}")

    phases = ([("mesh_path", mesh_path)] if args.chips == 4 else
              [("sincos_vs_float64", sincos_vs_float64),
               ("kernels_vs_ref", kernels_vs_ref), ("fl_rounds", fl_rounds)])
    ok = True
    for name, phase in phases:
        t = time.perf_counter()
        passed = phase()
        log(f"phase {name}: {'ok' if passed else 'FAILED'} "
            f"in {time.perf_counter() - t:.1f} s")
        ok &= passed
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
