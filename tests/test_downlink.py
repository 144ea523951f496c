"""Downlink broadcast leg: transport primitives, key-lane schedule, airtime
pricing, policy mapping, and the FL integration (driver-less + scenario,
both dispatches) — plus the FedAvg ``max_abs`` x scenario x bucketed
coverage the pre-engine loops never exercised."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.mnist_cnn import config as cnn_config
from repro.core import channel as CH
from repro.core import latency as LAT
from repro.core import transport as T
from repro.data import synth_mnist
from repro.fl import cnn, partition
from repro.fl.fedavg import run_fedavg
from repro.fl.loop import run_fl
from repro.link import policy as P
from repro.link import scenario as S

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------ transport primitives


@settings(max_examples=20, deadline=None)
@given(st.lists(st.floats(min_value=-1.899999976158142,
                          max_value=1.899999976158142, width=32),
                min_size=1, max_size=64))
def test_perfect_downlink_is_exact_identity(values):
    """Property: a perfect downlink channel is the identity on the broadcast
    payload — every client's received copy equals the transmitted bits."""
    x = jnp.asarray(values, jnp.float32)
    x_hat, stats = T.transmit_broadcast(x, KEY, T.TransportConfig(mode="perfect"),
                                        num_clients=3)
    assert x_hat.shape == (3, x.shape[0])
    np.testing.assert_array_equal(
        np.asarray(x_hat).view(np.uint32),
        np.tile(np.asarray(x).view(np.uint32), (3, 1)))
    assert np.all(np.asarray(stats.bit_errors) == 0)


def test_perfect_pytree_broadcast_identity_on_model():
    """The pytree front-end: a CNN params tree survives a perfect broadcast
    bit-exactly, with every leaf growing a leading client dim."""
    params = cnn.init_params(KEY, cnn_config())
    out, stats = T.transmit_pytree_broadcast(
        params, KEY, T.TransportConfig(mode="perfect"), num_clients=4)
    for name, leaf in params.items():
        got = out[name]
        assert got.shape == (4,) + leaf.shape and got.dtype == leaf.dtype
        for i in range(4):
            np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(leaf))
    assert stats.data_symbols.shape == (4,)


def test_broadcast_rides_the_downlink_key_lane():
    """Client ``i``'s broadcast draw is ``fold_in(key, LANE + i)`` — so the
    downlink is reproducible per client AND decorrelated from the uplink's
    ``fold_in(key, i)`` schedule under the same base key."""
    cfg = T.TransportConfig(mode="approx", channel=CH.ChannelConfig(snr_db=8.0))
    x = jax.random.normal(jax.random.PRNGKey(3), (300,)) * 0.5
    x_hat, _ = T.transmit_broadcast(x, KEY, cfg, num_clients=4)
    for i in range(4):
        ref, _ = T.transmit_flat(
            x, jax.random.fold_in(KEY, T.DOWNLINK_KEY_LANE + i), cfg)
        np.testing.assert_array_equal(np.asarray(x_hat[i]), np.asarray(ref))
    # Same base key on the uplink lane draws a different realization.
    up_hat, _ = T.transmit_batch(jnp.tile(x, (4, 1)), KEY, cfg)
    assert not np.array_equal(np.asarray(x_hat), np.asarray(up_hat))


def test_broadcast_validation():
    cfg = T.TransportConfig(mode="perfect")
    with pytest.raises(ValueError, match="flat"):
        T.transmit_broadcast(jnp.zeros((2, 8)), KEY, cfg, num_clients=2)
    with pytest.raises(ValueError, match="num_clients"):
        T.transmit_broadcast(jnp.zeros((8,)), KEY, cfg, num_clients=0)
    with pytest.raises(ValueError, match="num_clients"):
        T.transmit_broadcast(jnp.zeros((8,)), KEY, cfg,
                             num_clients=T.DOWNLINK_KEY_LANE + 1)


def test_broadcast_adaptive_bucketed_equals_select():
    """The mixed-mode broadcast inherits the uplink engine's dispatch
    equivalence: bucketed == select bit-for-bit on a kernel-free table."""
    base = T.TransportConfig(channel=CH.ChannelConfig(snr_db=10.0))
    cfgs = P.build_mode_cfgs(base, P.PolicyConfig(), ecrt_expected_tx=2.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (512,)) * 0.5
    mode = np.array([0, 1, 2, 3, 1, 1, 2, 0], np.int32)
    snr = jnp.linspace(2.0, 28.0, 8)
    a, sa = T.transmit_broadcast_adaptive(x, KEY, cfgs, mode, snr_db=snr,
                                          dispatch="bucketed")
    b, sb = T.transmit_broadcast_adaptive(x, KEY, cfgs, jnp.asarray(mode),
                                          snr_db=snr, dispatch="select")
    np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                  np.asarray(b).view(np.uint32))
    for f in ("data_symbols", "transmissions", "bit_errors", "n_bits",
              "mode_idx"):
        np.testing.assert_array_equal(np.asarray(getattr(sa, f)),
                                      np.asarray(getattr(sb, f)))


# --------------------------------------------------------------- airtime


def test_broadcast_airtime_prices_one_transmission_per_mode():
    air = np.array([3.0, 1.0, 2.0, 2.5], np.float32)
    # Single-mode broadcast: the PS transmits once -> max, not sum.
    assert LAT.broadcast_airtime(air) == pytest.approx(3.0)
    # Mixed modes: one transmission per distinct mode (per-mode max).
    modes = np.array([0, 1, 1, 0])
    assert LAT.broadcast_airtime(air, modes) == pytest.approx(3.0 + 2.0)
    assert LAT.broadcast_airtime(np.zeros((0,))) == 0.0


# ----------------------------------------------------------------- policy


def test_downlink_mode_uses_policy_table_at_shifted_csi():
    pc = P.PolicyConfig()  # thresholds (6, 16, 26)
    est = jnp.array([0.0, 5.0, 15.0, 25.0])
    np.testing.assert_array_equal(
        np.asarray(P.downlink_mode(est, pc)), [0, 0, 1, 2])
    # +3 dB downlink offset pushes each client over its next threshold.
    np.testing.assert_array_equal(
        np.asarray(P.downlink_mode(est, pc, snr_offset_db=3.0)), [0, 1, 2, 3])


# ----------------------------------------------------------- FL integration


@pytest.fixture(scope="module")
def fl_world():
    (img, lab), (ti, tl) = synth_mnist.train_test(60, 16, seed=0)
    parts = partition.non_iid_partition(img, lab, n_clients=4)
    cx, cy = partition.stack_clients(parts, per_client=24)
    return cx, cy, ti, tl


CFG = dataclasses.replace(cnn_config(), lr=0.1)
TCFG = T.TransportConfig(mode="approx", channel=CH.ChannelConfig(snr_db=10.0))


def test_run_fl_driverless_downlink_smoke(fl_world):
    """Driver-less noisy downlink: telemetry records appear, airtime grows
    by the broadcast leg, and the run stays finite."""
    cx, cy, ti, tl = fl_world
    kw = dict(n_rounds=3, batch_per_round=8, eval_every=2, seed=1)
    clean = run_fl(CFG, TCFG, cx, cy, ti, tl, **kw)
    noisy = run_fl(CFG, TCFG, cx, cy, ti, tl,
                   downlink=S.DownlinkConfig(mode="approx"), **kw)
    assert clean.link == []
    assert len(noisy.link) == 3
    for rec in noisy.link:
        assert rec["downlink_airtime_s"] > 0.0
        assert 0.0 <= rec["downlink_ber"] < 0.5
    assert noisy.airtime_s[-1] > clean.airtime_s[-1]
    assert np.isfinite(noisy.final_accuracy)


def test_run_fl_perfect_downlink_is_bitwise_noop(fl_world):
    """An explicitly error-free downlink leg must reproduce downlink=None
    exactly: the broadcast is the identity and the uplink keys are on a
    disjoint fold_in lane."""
    cx, cy, ti, tl = fl_world
    kw = dict(n_rounds=3, batch_per_round=8, eval_every=2, seed=2)
    a = run_fl(CFG, TCFG, cx, cy, ti, tl, **kw)
    b = run_fl(CFG, TCFG, cx, cy, ti, tl,
               downlink=S.DownlinkConfig(mode="perfect"), **kw)
    assert a.accuracy == b.accuracy
    # perfect broadcast still costs airtime (the PS transmits the model)
    assert b.airtime_s[-1] > a.airtime_s[-1]


def test_ecrt_downlink_prices_analytically_at_shifted_snr(fl_world,
                                                          monkeypatch):
    """Regression: an ECRT downlink must never trace the real LDPC decoder
    inside the round, and its analytic E[tx] must be calibrated at the
    *downlink's* operating point (uplink SNR + offset), not the uplink's."""
    from repro.core import latency as LATmod
    from repro.fl import engine as engine_lib

    cx, cy, ti, tl = fl_world
    profile_snrs, calib_anchors = [], []

    def fake_profile(snr_vec, modulation, **kw):
        snr = np.asarray(snr_vec, np.float32).reshape(-1)
        profile_snrs.append(snr.copy())
        return np.full(snr.shape, 1.7, np.float32)

    def fake_calibrate(snr_db, modulation="qpsk", **kw):
        calib_anchors.append(float(snr_db))
        return 1.7

    monkeypatch.setattr(LATmod, "ecrt_expected_tx_profile", fake_profile)
    monkeypatch.setattr(LATmod, "calibrate_ecrt", fake_calibrate)

    # Driver-less: approx uplink at 10 dB + ECRT downlink at +5 dB.
    dl = S.DownlinkConfig(mode="ecrt", snr_offset_db=5.0)
    eng = engine_lib.RoundEngine(
        engine_lib.FedSGD(CFG, batch_per_round=8), TCFG, cx, cy, ti, tl,
        n_rounds=1, eval_every=1, downlink=dl)
    assert not eng.dl_cfg.simulate_fec  # no LDPC decode inside the round
    assert eng.dl_cfg.ecrt_expected_tx == pytest.approx(1.7)
    assert profile_snrs and np.allclose(profile_snrs[-1], 15.0)  # 10 + 5

    # Scenario: the anchor is the fleet operating point + offset.
    scen = S.get_scenario("vehicular")
    eng2 = engine_lib.RoundEngine(
        engine_lib.FedSGD(CFG, batch_per_round=8), TCFG, cx, cy, ti, tl,
        n_rounds=1, eval_every=1,
        scenario=dataclasses.replace(scen, ecrt_expected_tx=2.0),
        downlink=dl)
    assert not eng2.dl_cfg.simulate_fec
    assert calib_anchors[-1] == pytest.approx(
        scen.dynamics.mean_snr_db + 5.0)

    # And the leg stays exact: ECRT delivers bits error-free.
    res = eng.run()
    assert res.link[0]["downlink_ber"] == 0.0
    assert res.link[0]["downlink_airtime_s"] > 0.0


@pytest.mark.slow
@pytest.mark.parametrize("preset", ["vehicular-noisy-dl", "static-noisy-dl"])
def test_scenario_downlink_presets_both_dispatches(fl_world, preset):
    """The downlink leg works across both dispatches on the registered
    noisy-downlink presets — and kernel-free tables stay bit-identical
    between bucketed and select, broadcast included."""
    cx, cy, ti, tl = fl_world
    scen = dataclasses.replace(S.get_scenario(preset), ecrt_expected_tx=2.0)
    assert scen.downlink is not None
    results = {}
    for disp in ("bucketed", "select"):
        res = run_fl(CFG, TCFG, cx, cy, ti, tl, n_rounds=3, batch_per_round=8,
                     eval_every=2, seed=4, scenario=scen,
                     adaptive_dispatch=disp)
        assert len(res.link) == 3
        for rec in res.link:
            assert rec["downlink_airtime_s"] > 0.0
            assert "downlink_ber" in rec
            if scen.downlink.adaptive:
                assert sum(rec["downlink_mode_counts"]) == 4
        results[disp] = res
    assert results["bucketed"].accuracy == results["select"].accuracy
    assert results["bucketed"].link == results["select"].link


@pytest.mark.slow
def test_fedavg_max_abs_scenario_bucketed_equals_select(fl_world):
    """FedAvg ``scale_mode="max_abs"`` under a scenario-driven *bucketed*
    dispatch (previously only exercised driver-less): the bucketed round
    must agree bit-for-bit with the fused select round on a kernel-free
    table — scaling, mixed-mode uplink, dropout-weighted aggregate and all."""
    cx, cy, ti, tl = fl_world
    scen = dataclasses.replace(S.get_scenario("vehicular"),
                               ecrt_expected_tx=2.0, dropout_prob=0.1)
    kw = dict(n_rounds=3, local_steps=2, batch_per_step=6, eval_every=1,
              seed=6, scale_mode="max_abs", scenario=scen)
    a = run_fedavg(CFG, TCFG, cx, cy, ti, tl, adaptive_dispatch="bucketed",
                   **kw)
    b = run_fedavg(CFG, TCFG, cx, cy, ti, tl, adaptive_dispatch="select",
                   **kw)
    assert a.accuracy == b.accuracy
    assert a.airtime_s == b.airtime_s
    assert a.link == b.link
    assert np.isfinite(a.final_accuracy)
