"""Fused uplink+aggregation (the in-kernel accumulator hot path).

Pins the tentpole invariant: ``transmit_batch_aggregate`` (and its
adaptive / pytree / engine wrappers) is **bit-identical** to the layered
``fedsgd_aggregate_batch``-over-``transmit_batch`` composition — same
per-client key schedule, same weight normalization (applied exactly once
on either path), same accumulation order (a client-order scan; the Pallas
grid loop and ``lax.scan`` contract identically). Covered here:

  * all five scenario presets x both wire dtypes, heterogeneous SNR
  * masked partial batches (``num_active < C`` zero-pads, does not alias)
  * adaptive mixed-mode cohorts vs the documented per-bucket order
    (increasing mode index, client-order within a bucket)
  * the scan fallback for non-kernel configs (perfect / ecrt / jnp paths)
  * naive mode's NaN contract: bitwise on finite lanes, identical NaN
    positions (the kernel preserves noisy NaN payloads, XLA canonicalizes)
  * donation safety on backends that ignore donation (CPU: same result,
    input stays live)
  * engine-level goldens: sync driverless, scenario bucketed, and the
    degenerate buffered-async config all reproduce their layered twins,
    and the fused/incompatible-feature guards raise.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.mnist_cnn import config as cnn_config
from repro.core import aggregation as A
from repro.core import channel as CH
from repro.core import transport as T
from repro.kernels import ops as O

M, N = 8, 2048

PRESETS = ["static", "pedestrian", "vehicular", "shadowed-urban", "bursty"]


def _cfg(**kw):
    ch = kw.pop("channel", CH.ChannelConfig(snr_db=10.0))
    return T.TransportConfig(channel=ch, **kw)


@pytest.fixture(scope="module")
def payloads():
    return jax.random.uniform(
        jax.random.PRNGKey(1), (M, N), minval=-0.99, maxval=0.99)


@pytest.fixture(scope="module")
def weights():
    return jax.random.uniform(
        jax.random.PRNGKey(7), (M,), minval=0.2, maxval=2.0)


def _preset_snr(preset: str, num_clients: int):
    """A heterogeneous per-client SNR vector drawn from the preset's
    dynamics (stable across processes)."""
    import zlib

    from repro.link import dynamics as D

    seed = zlib.crc32(preset.encode()) % 2**31
    return D.trajectory(
        jax.random.PRNGKey(seed), D.DYNAMICS_PRESETS[preset], num_clients, 2)[-1]


def _layered(x, key, cfg, weights, snr_db=None):
    """The reference composition: batched transport, then the PS scan."""
    x_hat, stats = T.transmit_batch(x, key, cfg, snr_db=snr_db)
    return A.fedsgd_aggregate_batch(x_hat, weights), stats


def assert_bits_equal(a, b):
    np.testing.assert_array_equal(
        np.asarray(a).view(np.uint32), np.asarray(b).view(np.uint32))


def assert_stats_equal(sa, sb):
    for f in ("data_symbols", "transmissions", "bit_errors", "n_bits",
              "bits_on_air"):
        np.testing.assert_array_equal(
            np.asarray(getattr(sa, f)), np.asarray(getattr(sb, f)))


@pytest.mark.parametrize("preset", PRESETS)
@pytest.mark.parametrize("wire_dtype", ["float32", "bfloat16"])
def test_fused_equals_layered_across_presets(payloads, weights, preset,
                                             wire_dtype):
    """Kernel fused round == layered round, bit for bit, on heterogeneous
    SNR vectors drawn from every scenario preset, for both wire dtypes
    (approx mode with the paper's clamp prior is NaN-free, so full bitwise
    identity holds)."""
    cfg = _cfg(mode="approx", use_kernel=True, wire_dtype=wire_dtype)
    key = jax.random.PRNGKey(11)
    snr = _preset_snr(preset, M)
    agg_f, st_f = T.transmit_batch_aggregate(
        payloads, key, cfg, A.normalize_weights(weights), snr_db=snr)
    agg_l, st_l = _layered(payloads, key, cfg, weights, snr_db=snr)
    assert_bits_equal(agg_f, agg_l)
    assert_stats_equal(st_f, st_l)


def test_fused_masked_partial_batch(payloads, weights):
    """``num_active < C`` at the ops layer: padded clients contribute
    nothing and the active prefix reproduces the layered truncated round
    (weights pre-normalized over the active slice, zero-padded)."""
    cfg = _cfg(mode="approx", use_kernel=True)
    key = jax.random.PRNGKey(12)
    keys = T.client_keys(key, M)
    na = 5
    w_act = A.normalize_weights(weights[:na])
    w_pad = jnp.concatenate([w_act, jnp.zeros((M - na,), jnp.float32)])
    agg_m, _ = O.approx_channel_transmit_batch_aggregate(
        payloads, keys, cfg, None, w_pad, num_active=na)
    # layered truncated reference: same per-client keys for the prefix
    x_hat, _ = T.transmit_batch(payloads[:na], key, cfg)
    agg_l = A.fedsgd_aggregate_batch(x_hat, weights[:na])
    assert_bits_equal(agg_m, agg_l)


@pytest.mark.parametrize("preset", ["pedestrian", "vehicular", "bursty"])
def test_adaptive_fused_equals_bucketed_layered(payloads, weights, preset):
    """Mixed-mode fused aggregation matches the documented order: globally
    normalized weights, per-bucket client-order partial sums added in
    increasing mode index."""
    from repro.link import policy as P

    snr = _preset_snr(preset, M)
    mode = np.asarray(P.initial_mode(snr, P.PolicyConfig()))
    cfgs = P.build_mode_cfgs(_cfg(use_kernel=True), P.PolicyConfig(),
                             ecrt_expected_tx=2.0)
    key = jax.random.PRNGKey(13)
    w_norm = A.normalize_weights(weights)
    agg_f, st_f = T.transmit_batch_adaptive_aggregate(
        payloads, key, cfgs, mode, w_norm, snr_db=snr)
    x_hat, st_l = T.transmit_batch_adaptive(
        payloads, key, cfgs, mode, snr_db=snr, dispatch="bucketed")
    total = jnp.zeros((N,), jnp.float32)
    for m in sorted(set(mode.tolist())):
        idx = np.flatnonzero(mode == m)
        part, _ = jax.lax.scan(
            lambda acc, wx: (acc + wx[0] * wx[1], None),
            jnp.zeros((N,), jnp.float32),
            (w_norm[idx], x_hat[idx].astype(jnp.float32)))
        total = total + part
    assert_bits_equal(agg_f, total)
    assert_stats_equal(st_f, st_l)
    np.testing.assert_array_equal(np.asarray(st_f.mode_idx), mode)


def test_adaptive_fused_single_mode_equals_plain(payloads, weights):
    """A single-mode cohort degenerates to the plain fused batch (one
    client-order scan — no bucket reordering)."""
    cfg = _cfg(mode="approx", use_kernel=True)
    cfgs = (cfg, _cfg(mode="naive", use_kernel=True))
    key = jax.random.PRNGKey(14)
    w_norm = A.normalize_weights(weights)
    agg_a, _ = T.transmit_batch_adaptive_aggregate(
        payloads, key, cfgs, np.zeros((M,), np.int32), w_norm)
    agg_p, _ = T.transmit_batch_aggregate(payloads, key, cfg, w_norm)
    assert_bits_equal(agg_a, agg_p)


@pytest.mark.parametrize(
    "kw",
    [
        {"mode": "approx"},                # layered jnp pipeline
        {"mode": "approx", "chunk_elems": 512},
        {"mode": "perfect"},
        {"mode": "ecrt", "simulate_fec": False, "ecrt_expected_tx": 1.25},
    ],
    ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()),
)
def test_scan_fallback_equals_layered(payloads, weights, kw):
    """Non-kernel configs take the jnp scan fallback — still bit-identical
    to transmit_batch + fedsgd_aggregate_batch."""
    cfg = _cfg(**kw)
    key = jax.random.PRNGKey(15)
    agg_f, st_f = T.transmit_batch_aggregate(
        payloads, key, cfg, A.normalize_weights(weights))
    agg_l, st_l = _layered(payloads, key, cfg, weights)
    assert_bits_equal(agg_f, agg_l)
    assert_stats_equal(st_f, st_l)


def test_naive_nan_contract(payloads, weights):
    """Naive mode decodes NaNs; the kernel keeps noisy NaN payload bits
    while XLA's scan canonicalizes them. Contract: identical NaN positions,
    bitwise identity on every finite lane."""
    cfg = _cfg(mode="naive", use_kernel=True,
               channel=CH.ChannelConfig(snr_db=0.0))
    key = jax.random.PRNGKey(16)
    agg_f, _ = T.transmit_batch_aggregate(
        payloads, key, cfg, A.normalize_weights(weights))
    agg_l, _ = _layered(payloads, key, cfg, weights)
    f, l = np.asarray(agg_f), np.asarray(agg_l)
    np.testing.assert_array_equal(np.isnan(f), np.isnan(l))
    ok = ~np.isnan(l)
    np.testing.assert_array_equal(f[ok].view(np.uint32),
                                  l[ok].view(np.uint32))


def test_pytree_fused_equals_flat(weights):
    """The pytree wrapper flattens, fuses, and unflattens without touching
    the numerics (leaves come back float32, shaped like the leaf suffix)."""
    tree = {
        "w": jax.random.uniform(jax.random.PRNGKey(20), (M, 32, 8),
                                minval=-0.9, maxval=0.9),
        "b": jax.random.uniform(jax.random.PRNGKey(21), (M, 8),
                                minval=-0.9, maxval=0.9),
    }
    cfg = _cfg(mode="approx", use_kernel=True)
    key = jax.random.PRNGKey(22)
    w_norm = A.normalize_weights(weights)
    agg_tree, st_t = T.transmit_pytree_batch_aggregate(tree, key, cfg, w_norm)
    flat = jnp.concatenate(
        [tree["b"].reshape(M, -1), tree["w"].reshape(M, -1)], axis=1)
    agg_flat, st_f = T.transmit_batch_aggregate(flat, key, cfg, w_norm)
    assert agg_tree["w"].shape == (32, 8) and agg_tree["b"].shape == (8,)
    got = jnp.concatenate(
        [agg_tree["b"].ravel(), agg_tree["w"].ravel()])
    assert_bits_equal(got, agg_flat)
    assert_stats_equal(st_t, st_f)


def test_donation_noop_on_cpu(payloads, weights):
    """``donate=True`` must not change results, and on backends that ignore
    donation (CPU) the donated input stays readable afterwards."""
    cfg = _cfg(mode="approx", use_kernel=True)
    key = jax.random.PRNGKey(17)
    w_norm = A.normalize_weights(weights)
    x = payloads + 0.0  # fresh buffer we could legally donate
    agg_d, _ = T.transmit_batch_aggregate(x, key, cfg, w_norm, donate=True)
    agg_p, _ = T.transmit_batch_aggregate(payloads, key, cfg, w_norm)
    assert_bits_equal(agg_d, agg_p)
    if not O.donation_supported():  # CPU: buffer must still be live
        np.testing.assert_array_equal(np.asarray(x), np.asarray(payloads))


def test_ops_bit_errors_match_batch_kernel(payloads):
    """The fused kernel's in-kernel error side-output equals the batch
    kernel's per-client error counts (pad words transmit as exact zeros,
    masked in-kernel)."""
    cfg = _cfg(mode="approx", use_kernel=True)
    keys = T.client_keys(jax.random.PRNGKey(18), M)
    w = jnp.full((M,), 1.0 / M, jnp.float32)
    _, st_f = O.approx_channel_transmit_batch_aggregate(
        payloads, keys, cfg, None, w)
    _, st_b = O.approx_channel_transmit_batch(payloads, keys, cfg)
    np.testing.assert_array_equal(np.asarray(st_f.bit_errors),
                                  np.asarray(st_b.bit_errors))


# ---------------------------------------------------------------- engine


@pytest.fixture(scope="module")
def world():
    from repro.data import synth_mnist
    from repro.fl import partition

    (img, lab), (ti, tl) = synth_mnist.train_test(60, 16, seed=0)
    parts = partition.non_iid_partition(img, lab, n_clients=4)
    cx, cy = partition.stack_clients(parts, per_client=24)
    return cx, cy, ti, tl


@pytest.fixture(scope="module")
def mcfg():
    return dataclasses.replace(cnn_config(), lr=0.1)


def _assert_same_run(a, b):
    assert a.rounds == b.rounds
    assert a.accuracy == b.accuracy  # float lists: exact equality intended
    assert a.final_accuracy == b.final_accuracy
    assert a.link == b.link


def test_engine_sync_fused_golden(mcfg, world):
    """Driverless FedSGD with ``fused_aggregate=True`` reproduces the
    layered engine exactly (same key schedule, same normalized-uniform
    weights, same accumulation order)."""
    from repro.fl.loop import run_fl

    cx, cy, ti, tl = world
    tc = _cfg(mode="approx", use_kernel=True)
    kw = dict(n_rounds=3, batch_per_round=8, eval_every=1, seed=3)
    _assert_same_run(run_fl(mcfg, tc, cx, cy, ti, tl, **kw),
                     run_fl(mcfg, tc, cx, cy, ti, tl,
                            fused_aggregate=True, **kw))


def test_engine_fused_round_counts_the_kernels_exact_tiles(mcfg, world):
    """Each fused round's counters hold the uplink kernel's count of
    (client, tile) pairs that took its exact path, read with the
    airtime; the layered round has no such kernel."""
    from repro.fl.loop import run_fl

    cx, cy, ti, tl = world
    tc = _cfg(mode="approx", use_kernel=True,
              channel=CH.ChannelConfig(snr_db=0.0))
    kw = dict(n_rounds=2, batch_per_round=8, eval_every=1, seed=3)
    fused = run_fl(mcfg, tc, cx, cy, ti, tl, fused_aggregate=True, **kw)
    layered = run_fl(mcfg, tc, cx, cy, ti, tl, **kw)
    steps = cx.shape[0] * O.padded_words(21_840) // 1024
    counts = [r.counters["uplink_exact_tiles"] for r in fused.records]
    assert all(0 < n <= steps for n in counts)
    assert not any((r.counters or {}).get("uplink_exact_tiles")
                   for r in layered.records)


@pytest.mark.slow
def test_engine_scenario_fused_golden(mcfg, world):
    """Scenario-driven bucketed rounds (dropout included — dropped clients
    transmit with weight zero on both paths)."""
    from repro.fl.loop import run_fl
    from repro.link import scenario as S

    cx, cy, ti, tl = world
    scen = dataclasses.replace(S.get_scenario("vehicular"),
                               ecrt_expected_tx=2.0, dropout_prob=0.1)
    tc = _cfg(mode="approx", use_kernel=True)
    kw = dict(n_rounds=3, batch_per_round=8, eval_every=1, seed=5,
              scenario=scen)
    _assert_same_run(run_fl(mcfg, tc, cx, cy, ti, tl, **kw),
                     run_fl(mcfg, tc, cx, cy, ti, tl,
                            fused_aggregate=True, **kw))


@pytest.mark.slow
def test_engine_async_degenerate_fused_golden(mcfg, world):
    """Buffered-async with ``buffer_k == M`` (one wave in flight, staleness
    zero) is the only async config the fused path accepts — and there it
    reproduces the layered buffered engine exactly."""
    from repro.fl.async_engine import run_fl_buffered

    cx, cy, ti, tl = world
    tc = _cfg(mode="approx", use_kernel=True)
    kw = dict(n_rounds=3, eval_every=1, seed=6, buffer_k=4)
    a = run_fl_buffered(mcfg, tc, cx, cy, ti, tl, **kw)
    b = run_fl_buffered(mcfg, tc, cx, cy, ti, tl, fused_aggregate=True, **kw)
    _assert_same_run(a, b)
    assert a.event_s == b.event_s


def test_engine_fused_guards(mcfg, world):
    """Configurations the fused path cannot reproduce bit-identically are
    rejected up front, not silently degraded."""
    from repro.compress import CompressionConfig
    from repro.fl.async_engine import run_fl_buffered
    from repro.fl.fedavg import run_fedavg
    from repro.fl.loop import run_fl

    cx, cy, ti, tl = world
    tc = _cfg(mode="approx", use_kernel=True)
    with pytest.raises(ValueError, match="compressed"):
        run_fl(mcfg, tc, cx, cy, ti, tl, n_rounds=1, fused_aggregate=True,
               compression=CompressionConfig(method="topk", ratio=0.1))
    with pytest.raises(ValueError, match="max_abs"):
        run_fedavg(mcfg, tc, cx, cy, ti, tl, n_rounds=1,
                   fused_aggregate=True, scale_mode="max_abs")
    with pytest.raises(ValueError, match="bucketed"):
        run_fl(mcfg, tc, cx, cy, ti, tl, n_rounds=1, fused_aggregate=True,
               scenario="pedestrian", adaptive_dispatch="select")
    with pytest.raises(ValueError, match="buffer_k"):
        run_fl_buffered(mcfg, tc, cx, cy, ti, tl, n_rounds=1,
                        fused_aggregate=True, buffer_k=2)


def test_engine_fused_manifest_fingerprint(mcfg, world, tmp_path):
    """A fused run declares itself in the ledger manifest and re-derives
    its config fingerprint, so layered runs keep their historical ones."""
    import json

    from repro.fl.loop import run_fl

    cx, cy, ti, tl = world
    tc = _cfg(mode="approx", use_kernel=True)
    kw = dict(n_rounds=1, batch_per_round=8, eval_every=1, seed=3)
    p_lay, p_fus = tmp_path / "lay.jsonl", tmp_path / "fus.jsonl"
    run_fl(mcfg, tc, cx, cy, ti, tl, ledger=str(p_lay), **kw)
    run_fl(mcfg, tc, cx, cy, ti, tl, ledger=str(p_fus),
           fused_aggregate=True, **kw)
    man_l = json.loads(p_lay.read_text().splitlines()[0])
    man_f = json.loads(p_fus.read_text().splitlines()[0])
    assert "fused_aggregate" not in man_l
    assert man_f["fused_aggregate"] is True
    assert man_f["fingerprint"] != man_l["fingerprint"]
