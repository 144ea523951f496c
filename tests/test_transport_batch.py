"""Batched multi-client uplink engine: loop equivalence, per-client stats,
heterogeneous SNR, kernel path, and sharded dispatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import channel as CH
from repro.core import transport as T
from repro.launch.mesh import make_mesh

M, N = 8, 2048


def _cfg(**kw):
    ch = kw.pop("channel", CH.ChannelConfig(snr_db=10.0))
    return T.TransportConfig(channel=ch, **kw)


@pytest.fixture(scope="module")
def payloads():
    return jax.random.uniform(
        jax.random.PRNGKey(1), (M, N), minval=-0.99, maxval=0.99)


def _loop(payloads, key, cfg):
    """Reference: per-client transmit_flat under the same fold_in schedule."""
    outs, stats = [], []
    for i in range(payloads.shape[0]):
        o, s = T.transmit_flat(payloads[i], jax.random.fold_in(key, i), cfg)
        outs.append(o)
        stats.append(s)
    return jnp.stack(outs), stats


@pytest.mark.parametrize(
    "kw",
    [
        {"mode": "approx"},
        {"mode": "naive"},
        {"mode": "approx", "use_kernel": True},
        {"mode": "approx", "chunk_elems": 512},
        {"mode": "approx", "wire_dtype": "bfloat16"},
        {"mode": "perfect"},
        {"mode": "ecrt", "simulate_fec": False, "ecrt_expected_tx": 1.25},
    ],
    ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()),
)
def test_batch_equals_per_client_loop(payloads, kw):
    """(a) one fused transmit_batch == M transmit_flat calls, bit-for-bit on
    the received floats and exactly on the error counts, under the shared
    fold_in key schedule."""
    cfg = _cfg(**kw)
    key = jax.random.PRNGKey(2)
    bh, bs = T.transmit_batch(payloads, key, cfg)
    lh, ls = _loop(payloads, key, cfg)
    if kw["mode"] == "naive":
        # naive decodes NaNs; compare the bit patterns, not float equality
        np.testing.assert_array_equal(
            np.asarray(bh).view(np.uint32), np.asarray(lh).view(np.uint32))
    else:
        np.testing.assert_array_equal(np.asarray(bh), np.asarray(lh))
    np.testing.assert_array_equal(
        np.asarray(bs.bit_errors),
        np.array([float(s.bit_errors) for s in ls], np.float32))
    np.testing.assert_array_equal(
        np.asarray(bs.data_symbols),
        np.array([float(s.data_symbols) for s in ls], np.float32))


def test_batch_stats_shapes_and_units(payloads):
    """(b) TxStats fields are (M,) and respect the documented units."""
    cfg = _cfg(mode="approx")
    _, st = T.transmit_batch(payloads, jax.random.PRNGKey(3), cfg)
    for field in (st.data_symbols, st.transmissions, st.bit_errors, st.n_bits):
        assert field.shape == (M,)
    k = cfg.scheme.bits_per_symbol
    np.testing.assert_array_equal(np.asarray(st.n_bits), np.full(M, N * 32))
    np.testing.assert_array_equal(
        np.asarray(st.data_symbols), np.full(M, N * 32 // k))
    np.testing.assert_array_equal(np.asarray(st.transmissions), np.ones(M))
    assert st.ber.shape == (M,)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_heterogeneous_snr_monotonic_ber(payloads, use_kernel):
    """(c) per-client SNR: better links must see strictly fewer bit errors
    (SNR 0..35 dB spans BER ~2e-1 .. ~1e-4 — far beyond noise)."""
    snr = tuple(float(s) for s in np.linspace(0.0, 35.0, M))
    cfg = _cfg(mode="approx", use_kernel=use_kernel,
               channel=CH.ChannelConfig(snr_db=snr))
    _, st = T.transmit_batch(payloads, jax.random.PRNGKey(4), cfg)
    ber = np.asarray(st.ber)
    assert (ber[:-1] > ber[1:]).all(), ber


def test_heterogeneous_snr_override_equals_config(payloads):
    """snr_db= argument and per-client ChannelConfig.snr_db agree."""
    snr = jnp.linspace(0.0, 30.0, M)
    base = _cfg(mode="approx")
    via_cfg = _cfg(mode="approx",
                   channel=CH.ChannelConfig(snr_db=tuple(np.asarray(snr))))
    key = jax.random.PRNGKey(5)
    a, sa = T.transmit_batch(payloads, key, base, snr_db=snr)
    b, sb = T.transmit_batch(payloads, key, via_cfg)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(sa.bit_errors), np.asarray(sb.bit_errors))


def test_batch_single_jitted_call(payloads):
    """The whole cohort runs inside one jit without retracing per client."""
    cfg = _cfg(mode="approx")
    fn = jax.jit(lambda x, k: T.transmit_batch(x, k, cfg))
    out, st = fn(payloads, jax.random.PRNGKey(6))
    assert out.shape == (M, N) and st.bit_errors.shape == (M,)
    assert bool(jnp.isfinite(out).all())
    assert float(jnp.abs(out).max()) < 2.0


def test_pytree_batch_roundtrip_structure():
    tree = {
        "a": jnp.ones((M, 3, 5)),
        "b": [jnp.zeros((M, 7)), jnp.full((M, 2, 2), 0.5)],
    }
    out, st = T.transmit_pytree_batch(tree, jax.random.PRNGKey(7),
                                      _cfg(mode="perfect"))
    assert (jax.tree_util.tree_structure(out)
            == jax.tree_util.tree_structure(tree))
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(tree)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert st.bit_errors.shape == (M,)


def test_ecrt_real_batched_is_exact():
    x = jax.random.uniform(jax.random.PRNGKey(8), (3, 64), minval=-1, maxval=1)
    cfg = _cfg(mode="ecrt", channel=CH.ChannelConfig(snr_db=12.0), max_tx=6)
    out, st = T.transmit_batch(x, jax.random.PRNGKey(9), cfg)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    assert st.transmissions.shape == (3,)
    assert float(jnp.sum(st.bit_errors)) == 0.0


def test_sharded_dispatch_matches_unsharded(payloads):
    """shard_map-over-mesh dispatch is bit-identical to the plain batch
    (globally-indexed fold_in keys), homogeneous and heterogeneous."""
    from repro.launch.sharding import shard_transmit_batch

    mesh = make_mesh((1,), ("data",))  # 1 CPU device in the test runner
    cfg = _cfg(mode="approx")
    key = jax.random.PRNGKey(10)
    ref, rst = T.transmit_batch(payloads, key, cfg)
    out, ost = shard_transmit_batch(payloads, key, cfg, mesh)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    np.testing.assert_array_equal(
        np.asarray(rst.bit_errors), np.asarray(ost.bit_errors))

    snr = jnp.linspace(0.0, 30.0, M)
    ref2, _ = T.transmit_batch(payloads, key, cfg, snr_db=snr)
    out2, _ = shard_transmit_batch(payloads, key, cfg, mesh, snr_db=snr)
    np.testing.assert_array_equal(np.asarray(ref2), np.asarray(out2))


def test_batch_snr_wrong_length_raises(payloads):
    """Regression: a per-client snr_db whose length != num_clients must fail
    loudly, naming both sizes — via the call override and the config path."""
    cfg = _cfg(mode="approx")
    key = jax.random.PRNGKey(20)
    with pytest.raises(ValueError, match=rf"{M - 1}.*{M} clients"):
        T.transmit_batch(payloads, key, cfg, snr_db=jnp.zeros((M - 1,)))
    bad_cfg = _cfg(mode="approx",
                   channel=CH.ChannelConfig(snr_db=tuple(range(M + 3))))
    with pytest.raises(ValueError, match=rf"{M + 3}.*{M}"):
        T.transmit_batch(payloads, key, bad_cfg)


def test_batch_snr_2d_raises(payloads):
    """A (2, M/2) grid flattens to M entries — it must be rejected, not
    silently reinterpreted as a per-client vector."""
    cfg = _cfg(mode="approx")
    with pytest.raises(ValueError, match="shape"):
        T.transmit_batch(payloads, jax.random.PRNGKey(21), cfg,
                         snr_db=jnp.zeros((2, M // 2)))


def _mode_table():
    ch = CH.ChannelConfig(snr_db=10.0)
    return (
        _cfg(mode="ecrt", channel=ch, simulate_fec=False, ecrt_expected_tx=2.2),
        _cfg(mode="approx", channel=ch),
        _cfg(mode="approx", modulation="16qam", channel=ch),
        _cfg(mode="approx", modulation="256qam", channel=ch),
    )


@pytest.mark.parametrize("dispatch", ["select", "bucketed"])
@pytest.mark.parametrize("with_snr", [False, True])
def test_adaptive_batch_equals_single_mode_calls(payloads, with_snr, dispatch):
    """A per-client mode vector is bit-identical to per-client single-mode
    ``transmit_flat`` calls under the shared fold_in key schedule — under
    either dispatch strategy (the bucketed key rides the client index, not
    the bucket slot)."""
    cfgs = _mode_table()
    key = jax.random.PRNGKey(22)
    mode = jnp.array([0, 1, 2, 3, 3, 2, 1, 0])
    snr = jnp.linspace(4.0, 30.0, M) if with_snr else None
    out, st = T.transmit_batch_adaptive(payloads, key, cfgs, mode, snr_db=snr,
                                        dispatch=dispatch)
    for i in range(M):
        cfg_i = cfgs[int(mode[i])]
        s_i = None if snr is None else snr[i]
        ref, rst = T.transmit_flat(payloads[i], jax.random.fold_in(key, i),
                                   cfg_i, snr_db=s_i)
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(ref))
        assert float(st.bit_errors[i]) == float(rst.bit_errors)
        assert float(st.data_symbols[i]) == float(rst.data_symbols)
    np.testing.assert_array_equal(np.asarray(st.mode_idx), np.asarray(mode))


def test_adaptive_uniform_mode_equals_plain_batch(payloads):
    """An all-one-mode vector reproduces transmit_batch exactly."""
    cfgs = _mode_table()
    key = jax.random.PRNGKey(23)
    for m in (1, 2):
        out, st = T.transmit_batch_adaptive(
            payloads, key, cfgs, jnp.full((M,), m, jnp.int32))
        ref, rst = T.transmit_batch(payloads, key, cfgs[m])
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        np.testing.assert_array_equal(
            np.asarray(st.bit_errors), np.asarray(rst.bit_errors))


def test_adaptive_single_jit_trace(payloads):
    """Mixed-mode cohorts re-dispatch without retracing: one XLA program."""
    cfgs = _mode_table()
    traces = []

    def fn(x, k, mode):
        traces.append(1)
        return T.transmit_batch_adaptive(x, k, cfgs, mode)

    jf = jax.jit(fn)
    for seed in (0, 1, 2):
        mode = jax.random.randint(jax.random.PRNGKey(seed), (M,), 0, len(cfgs))
        out, st = jf(payloads, jax.random.PRNGKey(24), mode)
        assert out.shape == (M, N)
    assert len(traces) == 1


def test_adaptive_validation_errors(payloads):
    cfgs = _mode_table()
    key = jax.random.PRNGKey(25)
    with pytest.raises(ValueError, match="mode_idx"):
        T.transmit_batch_adaptive(payloads, key, cfgs, jnp.zeros((M - 2,), jnp.int32))
    # Kernel rows are rejected only on the select dispatch (the Pallas grid
    # cannot lower inside a vmapped switch); bucketed accepts them.
    with pytest.raises(ValueError, match="use_kernel"):
        T.transmit_batch_adaptive(
            payloads, key, (_cfg(mode="approx", use_kernel=True),),
            jnp.zeros((M,), jnp.int32), dispatch="select")
    mixed_ch = (_cfg(mode="approx"),
                _cfg(mode="approx", channel=CH.ChannelConfig(snr_db=20.0)))
    with pytest.raises(ValueError, match="ChannelConfig"):
        T.transmit_batch_adaptive(payloads, key, mixed_ch,
                                  jnp.zeros((M,), jnp.int32))
    with pytest.raises(ValueError, match="dispatch"):
        T.transmit_batch_adaptive(payloads, key, cfgs,
                                  jnp.zeros((M,), jnp.int32), dispatch="warp")


def test_adaptive_kernel_rows_accepted_on_bucketed(payloads):
    """The un-banned Pallas path: use_kernel rows dispatch per client via
    mode buckets, each row bit-identical to the per-client kernel call."""
    ch = CH.ChannelConfig(snr_db=10.0)
    cfgs = (
        _cfg(mode="ecrt", channel=ch, simulate_fec=False,
             ecrt_expected_tx=2.2),
        _cfg(mode="approx", channel=ch, use_kernel=True),
        _cfg(mode="approx", modulation="16qam", channel=ch, use_kernel=True),
    )
    key = jax.random.PRNGKey(30)
    mode = jnp.array([0, 1, 2, 1, 2, 0, 1, 1])
    snr = jnp.linspace(5.0, 25.0, M)
    out, st = T.transmit_batch_adaptive(payloads, key, cfgs, mode, snr_db=snr)
    for i in range(M):
        ref, rst = T.transmit_flat(payloads[i], jax.random.fold_in(key, i),
                                   cfgs[int(mode[i])], snr_db=snr[i])
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(ref))
        assert float(st.bit_errors[i]) == float(rst.bit_errors)


@pytest.mark.parametrize("dispatch", ["select", "bucketed"])
def test_adaptive_out_of_range_modes_clamp_consistently(payloads, dispatch):
    """Out-of-range mode indices clamp for dispatch AND for the recorded
    stats.mode_idx — a stray -1 must not transmit as cfgs[0] yet price as
    the last row (negative jnp indexing wraps)."""
    cfgs = _mode_table()
    key = jax.random.PRNGKey(51)
    wild = np.array([-1, 0, 1, 2, 3, 9, -5, 2], np.int32)
    clamped = np.clip(wild, 0, len(cfgs) - 1)
    out, st = T.transmit_batch_adaptive(payloads, key, cfgs, wild,
                                        dispatch=dispatch)
    ref, rst = T.transmit_batch_adaptive(payloads, key, cfgs, clamped,
                                         dispatch=dispatch)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(st.mode_idx), clamped)


def test_adaptive_empty_cohort_agrees_across_dispatches():
    """A fully-dropped round (zero clients) must return empty results from
    both dispatches instead of crashing on zero buckets."""
    cfgs = _mode_table()
    x0 = jnp.zeros((0, 64), jnp.float32)
    m0 = np.zeros((0,), np.int32)
    for dispatch in ("select", "bucketed"):
        out, st = T.transmit_batch_adaptive(
            x0, jax.random.PRNGKey(50), cfgs, m0, dispatch=dispatch)
        assert out.shape == (0, 64)
        for f in (st.data_symbols, st.transmissions, st.bit_errors, st.n_bits):
            assert f.shape == (0,)
        assert st.mode_idx.shape == (0,)


def test_adaptive_bucketed_inside_jit_raises(payloads):
    """An explicitly-requested bucketed dispatch under a traced mode vector
    must fail loudly (bucket sizes are host-side), naming the escape hatch."""
    cfgs = _mode_table()

    @jax.jit
    def fn(x, k, m):
        return T.transmit_batch_adaptive(x, k, cfgs, m, dispatch="bucketed")

    with pytest.raises(ValueError, match="concrete mode_idx"):
        fn(payloads, jax.random.PRNGKey(31), jnp.zeros((M,), jnp.int32))


def test_adaptive_airtime_matches_static_pricing(payloads):
    """round_airtime_adaptive == round_airtime per mode on uniform batches."""
    from repro.core import latency as LAT

    cfgs = _mode_table()
    t = LAT.PhyTimings()
    key = jax.random.PRNGKey(26)
    for m, mode_name in ((0, "ecrt"), (1, "approx")):
        _, st = T.transmit_batch_adaptive(
            payloads, key, cfgs, jnp.full((M,), m, jnp.int32))
        adaptive = np.asarray(LAT.round_airtime_adaptive(st, t, cfgs))
        static = np.asarray(LAT.round_airtime(st, t, mode_name))
        np.testing.assert_allclose(adaptive, static, rtol=1e-6)
    _, st_plain = T.transmit_batch(payloads, key, cfgs[1])
    with pytest.raises(ValueError, match="mode_idx"):
        LAT.round_airtime_adaptive(st_plain, t, cfgs)


def test_client_offset_windows_the_schedule(payloads):
    """client_offset reproduces any contiguous slice of a larger batch —
    the property the sharded dispatch relies on."""
    cfg = _cfg(mode="approx")
    key = jax.random.PRNGKey(11)
    full, _ = T.transmit_batch(payloads, key, cfg)
    lo, _ = T.transmit_batch(payloads[: M // 2], key, cfg)
    hi, _ = T.transmit_batch(payloads[M // 2 :], key, cfg,
                             client_offset=M // 2)
    np.testing.assert_array_equal(
        np.asarray(full), np.concatenate([np.asarray(lo), np.asarray(hi)]))


def test_adaptive_client_offset_windows_the_schedule(payloads):
    """The bucketed dispatch keeps the fold_in key on the *global* client
    index: any contiguous slice with the matching offset reproduces the full
    batch (the invariant the sharded adaptive dispatch builds on)."""
    cfgs = _mode_table()
    key = jax.random.PRNGKey(32)
    mode = np.array([0, 1, 2, 3, 1, 2, 0, 3], np.int32)
    full, _ = T.transmit_batch_adaptive(payloads, key, cfgs, mode)
    lo, _ = T.transmit_batch_adaptive(payloads[: M // 2], key, cfgs,
                                      mode[: M // 2])
    hi, _ = T.transmit_batch_adaptive(payloads[M // 2 :], key, cfgs,
                                      mode[M // 2 :], client_offset=M // 2)
    np.testing.assert_array_equal(
        np.asarray(full), np.concatenate([np.asarray(lo), np.asarray(hi)]))


# ----------------------------------------------- bucketed ≡ select coverage


def _preset_round_modes(preset: str, num_clients: int):
    """Draw a (snr, mode) vector from a scenario preset's dynamics through
    the default threshold policy — realistic mixed-mode rounds per preset."""
    import zlib

    from repro.link import dynamics as D
    from repro.link import policy as P

    scen_dyn = D.DYNAMICS_PRESETS[preset]
    seed = zlib.crc32(preset.encode()) % 2**31  # stable across processes
    snr = D.trajectory(jax.random.PRNGKey(seed), scen_dyn, num_clients, 2)[-1]
    mode = np.asarray(P.initial_mode(snr, P.PolicyConfig()))
    return snr, mode


@pytest.mark.parametrize("preset", ["static", "pedestrian", "vehicular",
                                    "shadowed-urban", "bursty",
                                    "iot-lowrate"])
@pytest.mark.parametrize("wire_dtype", ["float32", "bfloat16"])
def test_bucketed_equals_select_across_presets(preset, wire_dtype):
    """Bucketed ≡ select, bit for bit, on mode mixes drawn from every
    scenario preset's dynamics, for both wire dtypes."""
    from repro.link import policy as P

    n, n_floats = 12, 256
    snr, mode = _preset_round_modes(preset, n)
    cfgs = P.build_mode_cfgs(
        _cfg(wire_dtype=wire_dtype), P.PolicyConfig(), ecrt_expected_tx=2.0)
    x = jax.random.uniform(jax.random.PRNGKey(33), (n, n_floats),
                           minval=-0.99, maxval=0.99)
    key = jax.random.PRNGKey(34)
    a, sa = T.transmit_batch_adaptive(x, key, cfgs, mode, snr_db=snr,
                                      dispatch="select")
    b, sb = T.transmit_batch_adaptive(x, key, cfgs, mode, snr_db=snr,
                                      dispatch="bucketed")
    np.testing.assert_array_equal(
        np.asarray(a).view(np.uint32), np.asarray(b).view(np.uint32))
    for f in ("data_symbols", "transmissions", "bit_errors", "n_bits"):
        np.testing.assert_array_equal(
            np.asarray(getattr(sa, f)), np.asarray(getattr(sb, f)))


def test_bucketed_equals_select_with_chunked_rows(payloads):
    """Mode tables whose rows chunk the payload (chunk_elems) dispatch
    identically under both strategies, including a payload length that does
    not divide the chunk size."""
    x = payloads[:, : 1500]  # 1500 % 512 != 0 -> padded chunked pipeline
    cfgs = (_cfg(mode="approx", chunk_elems=512), _cfg(mode="approx"))
    mode = np.array([0, 1, 0, 1, 1, 0, 0, 1], np.int32)
    key = jax.random.PRNGKey(35)
    a, sa = T.transmit_batch_adaptive(x, key, cfgs, mode, dispatch="select")
    b, sb = T.transmit_batch_adaptive(x, key, cfgs, mode, dispatch="bucketed")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(sa.bit_errors), np.asarray(sb.bit_errors))


# ------------------------------------------------- chunked-path equivalence


@pytest.mark.parametrize("n_payload", [1500, 2048, 513])
@pytest.mark.parametrize("wire_dtype", ["float32", "bfloat16"])
def test_chunked_batch_equals_chunked_flat_loop(n_payload, wire_dtype):
    """Chunked uplinks (incl. lengths not divisible by chunk_elems) stay
    bit-identical between the fused batch and a per-client flat loop."""
    cfg = _cfg(mode="approx", chunk_elems=512, wire_dtype=wire_dtype)
    x = jax.random.uniform(jax.random.PRNGKey(36), (4, n_payload),
                           minval=-0.99, maxval=0.99)
    key = jax.random.PRNGKey(37)
    bh, bs = T.transmit_batch(x, key, cfg)
    lh, ls = _loop(x, key, cfg)
    np.testing.assert_array_equal(np.asarray(bh), np.asarray(lh))
    np.testing.assert_array_equal(
        np.asarray(bs.bit_errors),
        np.array([float(s.bit_errors) for s in ls], np.float32))


@pytest.mark.parametrize("n_payload", [1500, 513])
def test_chunked_stats_consistent_with_direct_recount(n_payload):
    """The chunked pipeline's pad-error subtraction: reported bit_errors
    must equal a direct popcount of sent-vs-received words over the true
    payload only, for lengths that force padding."""
    from repro.core import float_codec as fc
    from repro.core import modulation as mod_lib

    cfg = _cfg(mode="naive", chunk_elems=512)  # no clamp: errors survive
    x = jax.random.uniform(jax.random.PRNGKey(38), (n_payload,),
                           minval=-0.99, maxval=0.99)
    x_hat, st = T.transmit_flat(x, jax.random.PRNGKey(39), cfg)
    direct = int(jnp.sum(mod_lib.popcount(
        fc.f32_to_bits(x) ^ fc.f32_to_bits(x_hat))))
    assert int(st.bit_errors) == direct
    assert int(st.n_bits) == n_payload * 32
    k = cfg.scheme.bits_per_symbol
    assert int(st.data_symbols) == n_payload * 32 // k


# -------------------------------------------------- _same_channel semantics


def test_same_channel_normalizes_snr_shapes():
    """Regression: scalar vs 0-d array vs length-1 sequence snr_db all mean
    one homogeneous SNR and must compare equal; genuinely different values
    or lengths must not."""
    same = [
        CH.ChannelConfig(snr_db=10.0),
        CH.ChannelConfig(snr_db=np.float32(10.0)),
        CH.ChannelConfig(snr_db=np.array(10.0)),
        CH.ChannelConfig(snr_db=(10.0,)),
        CH.ChannelConfig(snr_db=[10.0]),
    ]
    for a in same:
        for b in same:
            assert T._same_channel(a, b), (a.snr_db, b.snr_db)
    base = same[0]
    assert not T._same_channel(base, CH.ChannelConfig(snr_db=11.0))
    assert not T._same_channel(base, CH.ChannelConfig(snr_db=(10.0, 11.0)))
    assert not T._same_channel(
        CH.ChannelConfig(snr_db=(10.0, 11.0)),
        CH.ChannelConfig(snr_db=(10.0, 11.0, 12.0)))
    # size-1 broadcasts against a longer constant vector
    assert T._same_channel(base, CH.ChannelConfig(snr_db=(10.0, 10.0)))


def test_bucketed_canonicalizes_array_snr_for_jit_cache(payloads):
    """An array-valued channel snr_db must not silently disable the
    per-mode jit cache: it canonicalizes to a tuple, matching the
    tuple-configured table bit for bit and sharing its cache entry."""
    snr = np.linspace(0.0, 21.0, M).astype(np.float32)
    cfg_arr = _cfg(mode="approx",
                   channel=CH.ChannelConfig(snr_db=np.array(snr)))
    cfg_tup = _cfg(mode="approx",
                   channel=CH.ChannelConfig(snr_db=tuple(float(s) for s in snr)))
    key = jax.random.PRNGKey(52)
    mode = np.zeros((M,), np.int32)
    misses0 = T._cached_mode_batch_fn.cache_info().misses
    a, _ = T.transmit_batch_adaptive(payloads, key, (cfg_arr,), mode)
    b, _ = T.transmit_batch_adaptive(payloads, key, (cfg_tup,), mode)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    info = T._cached_mode_batch_fn.cache_info()
    # One shared entry: the array-config call populated it (miss), the
    # tuple-config call reused it (hit) — no TypeError fallback.
    assert info.misses == misses0 + 1


def test_adaptive_accepts_shape_normalized_channels(payloads):
    """A mode table mixing scalar and length-1 snr_db representations of the
    same channel must dispatch (and match the all-scalar table exactly)."""
    mixed = (_cfg(mode="approx"),
             _cfg(mode="approx", modulation="16qam",
                  channel=CH.ChannelConfig(snr_db=(10.0,))))
    uniform = (_cfg(mode="approx"),
               _cfg(mode="approx", modulation="16qam"))
    key = jax.random.PRNGKey(40)
    mode = np.array([0, 1] * (M // 2), np.int32)
    a, _ = T.transmit_batch_adaptive(payloads, key, mixed, mode)
    b, _ = T.transmit_batch_adaptive(payloads, key, uniform, mode)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_select_consumers_clear_kernel_rows(payloads):
    """The select-pinned consumers of a kernel-enabled mode table: the FL
    round clears ``use_kernel`` through ``select_mode_cfgs``; the sharded
    dispatch refuses the table (no silent swap to the jnp rows) and matches
    the unsharded call bit for bit once the caller clears it."""
    from repro.fl.loop import select_mode_cfgs
    from repro.launch.sharding import shard_transmit_batch_adaptive
    from repro.link import policy as P

    ch = CH.ChannelConfig(snr_db=10.0)
    kernel_cfgs = P.build_mode_cfgs(
        T.TransportConfig(channel=ch, use_kernel=True), P.PolicyConfig(),
        ecrt_expected_tx=2.0)
    assert any(c.use_kernel for c in kernel_cfgs)

    class FakeDriver:
        mode_cfgs = kernel_cfgs

    cleared = select_mode_cfgs(FakeDriver())
    assert all(not c.use_kernel for c in cleared)

    mode = np.array([0, 1, 2, 3, 3, 2, 1, 0], np.int32)
    key = jax.random.PRNGKey(42)
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="clear_kernel_rows"):
        shard_transmit_batch_adaptive(payloads, key, kernel_cfgs, mode, mesh)
    out, _ = shard_transmit_batch_adaptive(
        payloads, key, T.clear_kernel_rows(kernel_cfgs), mode, mesh)
    ref, _ = T.transmit_batch_adaptive(payloads, key, cleared, mode)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_sharded_adaptive_matches_unsharded(payloads):
    """shard_map adaptive dispatch == unsharded call, homogeneous and
    heterogeneous SNR, on a 1-device mesh."""
    from repro.launch.sharding import shard_transmit_batch_adaptive

    mesh = make_mesh((1,), ("data",))
    cfgs = _mode_table()
    key = jax.random.PRNGKey(41)
    mode = np.array([0, 1, 2, 3, 3, 2, 1, 0], np.int32)
    snr = jnp.linspace(2.0, 28.0, M)
    ref, rst = T.transmit_batch_adaptive(payloads, key, cfgs, mode,
                                         snr_db=snr)
    out, ost = shard_transmit_batch_adaptive(payloads, key, cfgs, mode, mesh,
                                             snr_db=snr)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    np.testing.assert_array_equal(
        np.asarray(rst.bit_errors), np.asarray(ost.bit_errors))
    np.testing.assert_array_equal(
        np.asarray(rst.mode_idx), np.asarray(ost.mode_idx))

    ref2, _ = T.transmit_batch_adaptive(payloads, key, cfgs, mode)
    out2, _ = shard_transmit_batch_adaptive(payloads, key, cfgs, mode, mesh)
    np.testing.assert_array_equal(np.asarray(ref2), np.asarray(out2))
