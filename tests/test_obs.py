"""Gates for the observability layer (``repro.obs``) across both engines.

Three invariants, in order of load-bearing-ness:

1. **Sinks are neutral.** Attaching a ledger / trace recorder / phase
   timers must not change a single numeric result — same accuracy
   trajectory, same airtime, same per-round telemetry dicts, bit for bit.
   The engines compute nothing extra for the sinks except the ``uplink_*``
   aggregates, which are derived (device->host reads) after the round's
   arithmetic is already fixed.

2. **Records ARE the telemetry.** ``FLResult.link`` is now a dict *view*
   of the typed ``RoundRecord`` list (``to_link_dict`` with the exact
   historical key order), and the pre-engine golden loop still matches the
   instrumented engine — the record refactor changed representation, not
   values.

3. **The ledger round-trips.** ``read_ledger`` on the JSONL file
   reproduces ``FLResult.link`` exactly (JSON float serialization is
   shortest-round-trip, so equality is bit-level), ``validate_ledger``
   passes on real ledgers and fails on broken ones, and the Chrome trace
   is loadable JSON with the required track types.

Runs are kept tiny (4 clients x 24 samples, 3-4 rounds) but cover the
arms the ISSUE names: scenario, compression, downlink, and buffered.
"""

import dataclasses
import json

import golden_pre_engine as golden
import jax
import numpy as np
import pytest

from repro.compress.sparsify import CompressionConfig
from repro.configs.mnist_cnn import config as cnn_config
from repro.core import channel as CH
from repro.core import transport as T
from repro.data import synth_mnist
from repro.fl import partition
from repro.fl.async_engine import run_fl_buffered
from repro.fl.loop import run_fl
from repro.link import scenario as S
from repro.obs import PhaseTimers, TraceRecorder
from repro.obs import ledger as L
from repro.obs import records as R
from repro.obs import timers as timers_lib


@pytest.fixture(scope="module")
def world():
    (img, lab), (ti, tl) = synth_mnist.train_test(60, 16, seed=0)
    parts = partition.non_iid_partition(img, lab, n_clients=4)
    cx, cy = partition.stack_clients(parts, per_client=24)
    return cx, cy, ti, tl


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(cnn_config(), lr=0.1)


def _tc():
    return T.TransportConfig(mode="approx",
                             channel=CH.ChannelConfig(snr_db=10.0))


def _scenario(**over):
    # Explicit ecrt_expected_tx skips LDPC calibration (fast); downlink and
    # compression arms layer onto the same vehicular dynamics.
    base = dataclasses.replace(S.get_scenario("vehicular"),
                               ecrt_expected_tx=2.0)
    return dataclasses.replace(base, **over) if over else base


_KW = dict(n_rounds=4, batch_per_round=8, eval_every=2, seed=3)


def _full_arm_kw():
    """The all-subsystems sync arm: scenario + noisy downlink + top-k."""
    scen = _scenario(downlink=S.DownlinkConfig(mode="approx",
                                               snr_offset_db=-3.0,
                                               adaptive=True))
    return dict(_KW, scenario=scen,
                compression=CompressionConfig(method="topk", ratio=0.1))


def _sync_pair(cfg, world, path, kw):
    """(instrumented run, bare twin, ledger path, timers) of one sync arm."""
    cx, cy, ti, tl = world
    timers = PhaseTimers()
    res = run_fl(cfg, _tc(), cx, cy, ti, tl, ledger=path,
                 phase_timers=timers, **kw)
    bare = run_fl(cfg, _tc(), cx, cy, ti, tl, **kw)
    return res, bare, path, timers


@pytest.fixture(scope="module")
def sync_pair(cfg, world, tmp_path_factory):
    """The full scenario+downlink+compression sync arm."""
    path = str(tmp_path_factory.mktemp("obs") / "sync.jsonl")
    return _sync_pair(cfg, world, path, _full_arm_kw())


@pytest.fixture(scope="module")
def static_pair(cfg, world, tmp_path_factory):
    """The driver-less fused round (no scenario), whose only host reads are
    the airtime, the eval and the ledger's summaries."""
    path = str(tmp_path_factory.mktemp("obs_static") / "static.jsonl")
    return _sync_pair(cfg, world, path, dict(_KW, fused_aggregate=True))


@pytest.fixture(scope="module")
def async_pair(cfg, world, tmp_path_factory):
    """(instrumented run, bare twin, ledger path, trace, timers) for the
    buffered metro-rush arm (compute-time skew => real event traffic)."""
    cx, cy, ti, tl = world
    tmp = tmp_path_factory.mktemp("obs_async")
    path = str(tmp / "async.jsonl")
    trace = TraceRecorder(str(tmp / "trace.json"))
    timers = PhaseTimers()
    scen = dataclasses.replace(S.get_scenario("metro-rush"),
                               ecrt_expected_tx=2.0)
    kw = dict(_KW, scenario=scen, buffer_k=2, staleness="polynomial")
    res = run_fl_buffered(cfg, _tc(), cx, cy, ti, tl, ledger=path,
                          trace=trace, phase_timers=timers, **kw)
    bare = run_fl_buffered(cfg, _tc(), cx, cy, ti, tl, **kw)
    return res, bare, path, trace, timers


# -------------------------------------------------------------------------
# 1. Observer neutrality
# -------------------------------------------------------------------------


def test_sync_sinks_are_neutral(sync_pair):
    res, bare, _, _ = sync_pair
    assert res.rounds == bare.rounds
    assert res.accuracy == bare.accuracy  # exact float equality intended
    assert res.airtime_s == bare.airtime_s
    assert res.final_accuracy == bare.final_accuracy
    assert res.link == bare.link


def test_static_sinks_are_neutral(static_pair):
    res, bare, _, timers = static_pair
    assert res.accuracy == bare.accuracy
    assert res.airtime_s == bare.airtime_s
    for a, b in zip(jax.tree_util.tree_leaves(res.params),
                    jax.tree_util.tree_leaves(bare.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # One airtime and one ledger summary a round, one read an eval.
    assert timers.summary()["sync"]["calls"] == (2 * _KW["n_rounds"]
                                                 + len(res.accuracy))


def test_async_sinks_are_neutral(async_pair):
    res, bare, _, _, _ = async_pair
    assert res.accuracy == bare.accuracy
    assert res.airtime_s == bare.airtime_s
    assert res.event_s == bare.event_s
    assert res.link == bare.link


# -------------------------------------------------------------------------
# 2. Records are the telemetry (golden link-view equivalence)
# -------------------------------------------------------------------------


def test_link_is_record_view(sync_pair, async_pair):
    """``FLResult.link`` must be exactly the ``to_link_dict`` view of the
    typed records, in order, across the full sync arm and the buffered
    arm (compression + downlink keys included)."""
    for res in (sync_pair[0], async_pair[0]):
        assert len(res.records) == len(res.link)
        assert [r.to_link_dict() for r in res.records] == res.link
    # The full sync arm carries all three optional field families.
    top = sync_pair[0].link[0]
    for key in ("comp_ratio", "downlink_airtime_s", "mode_counts"):
        assert key in top


def test_scenario_link_matches_pre_engine_golden(cfg, world, tmp_path):
    """Instrumented engine vs the frozen pre-engine loop: the record
    refactor (and an attached ledger) must not move the telemetry."""
    cx, cy, ti, tl = world
    kw = dict(_KW, scenario=_scenario())
    res = run_fl(cfg, _tc(), cx, cy, ti, tl,
                 ledger=str(tmp_path / "g.jsonl"), **kw)
    ref = golden.golden_run_fl(cfg, _tc(), cx, cy, ti, tl, **kw)
    assert res.accuracy == ref.accuracy
    assert res.airtime_s == ref.airtime_s
    assert res.link == ref.link


def test_driverless_run_has_records_but_no_link(cfg, world, tmp_path):
    """Driver-less runs never emitted link dicts; the record list still
    exists (one per round) but carries no link fields."""
    cx, cy, ti, tl = world
    res = run_fl(cfg, _tc(), cx, cy, ti, tl,
                 ledger=str(tmp_path / "d.jsonl"), **_KW)
    assert res.link == []
    assert len(res.records) == _KW["n_rounds"]
    assert not any(r.has_link_fields() for r in res.records)


def test_record_dict_roundtrip(sync_pair, async_pair):
    for res in (sync_pair[0], async_pair[0]):
        for rec in res.records:
            assert R.RoundRecord.from_dict(rec.to_dict()) == rec
    ev = R.EventRecord(t=1.5, kind="compute", wave=2, client=7, dur=0.25)
    assert R.EventRecord.from_dict(ev.to_dict()) == ev
    with pytest.raises(ValueError):
        R.EventRecord(t=0.0, kind="not-a-kind")


# -------------------------------------------------------------------------
# 3. Ledger round-trip + schema
# -------------------------------------------------------------------------


def test_ledger_roundtrips_link(sync_pair, async_pair):
    for res, path in ((sync_pair[0], sync_pair[2]),
                      (async_pair[0], async_pair[2])):
        assert L.validate_ledger(path) == []
        data = L.read_ledger(path)
        assert data.link == res.link  # bit-exact through JSON
        assert len(data.rounds) == len(res.records)
        assert [ev["accuracy"] for ev in data.evals] == res.accuracy


def test_manifest_contents(sync_pair, async_pair):
    sync = L.read_ledger(sync_pair[2]).manifest
    asy = L.read_ledger(async_pair[2]).manifest
    for man in (sync, asy):
        for key in L.MANIFEST_KEYS:
            if key != "kind":  # read_ledger strips the line discriminator
                assert key in man
        for key in L.PROVENANCE_KEYS:
            assert key in man["provenance"]
        assert man["seed"] == _KW["seed"]
    assert sync["engine"] == "sync"
    assert asy["engine"] == "async"
    assert asy["buffer_k"] == 2
    # Different engine configs must not collide on the join key.
    assert sync["fingerprint"] != asy["fingerprint"]


def test_async_ledger_has_events(async_pair):
    data = L.read_ledger(async_pair[2])
    kinds = {ev.kind for ev in data.events}
    for kind in ("wave", "compute", "uplink", "arrival", "aggregate",
                 "buffer"):
        assert kind in kinds
    # Summary carries the run outcome + the phase table.
    assert data.summary["final_accuracy"] == async_pair[0].final_accuracy
    assert "phases" in data.summary


def test_config_fingerprint_is_stable():
    a = L.config_fingerprint(_tc(), _scenario(), 4, "seed", 3)
    b = L.config_fingerprint(_tc(), _scenario(), 4, "seed", 3)
    c = L.config_fingerprint(_tc(), _scenario(), 4, "seed", 4)
    assert a == b
    assert a != c
    assert len(a) == 12


def test_validate_ledger_failure_modes(tmp_path):
    # Missing manifest keys.
    p = tmp_path / "bad.jsonl"
    p.write_text(json.dumps({"kind": "manifest", "schema": 1}) + "\n")
    assert any("manifest" in msg for msg in L.validate_ledger(str(p)))
    # First line is not a manifest at all.
    p.write_text(json.dumps({"kind": "round", "round": 0}) + "\n")
    assert L.validate_ledger(str(p)) != []
    # Torn final line (crashed run) must not break reading: every complete
    # record before the tear is preserved.
    good = tmp_path / "torn.jsonl"
    lines = [json.dumps({"kind": "manifest", "schema": 1,
                         "fingerprint": "x", "engine": "sync",
                         "algorithm": "a", "n_rounds": 1,
                         "num_clients": 1, "seed": 0,
                         "provenance": {k: None
                                        for k in L.PROVENANCE_KEYS}}),
             json.dumps({"kind": "round", "round": 0}),
             '{"kind": "round", "rou']
    good.write_text("\n".join(lines))
    data = L.read_ledger(str(good))
    assert len(data.rounds) == 1


# -------------------------------------------------------------------------
# Trace + timers
# -------------------------------------------------------------------------


def test_trace_is_loadable_chrome_json(async_pair):
    trace = async_pair[3]
    tracks = trace.track_types()
    assert len(tracks) >= 4, f"only {sorted(tracks)}"
    with open(trace.path) as f:
        chrome = json.load(f)
    evs = chrome["traceEvents"]
    assert evs
    # Metadata names the process tracks; spans are complete ('X') events
    # with microsecond timestamps.
    phases = {e["ph"] for e in evs}
    assert "M" in phases and "X" in phases
    assert all(e["ts"] >= 0 for e in evs if e["ph"] != "M")


def test_phase_timers_split_first_call(async_pair):
    timers = async_pair[4]
    summary = timers.summary()
    for phase in ("sample", "wave", "telemetry", "eval"):
        assert phase in summary
        assert summary[phase]["calls"] >= 1
    wave = summary["wave"]
    # First call includes jit compilation; it must be excluded from the
    # steady-state median (calls counts every scope entry).
    assert wave["first_s"] >= wave["steady_median_s"]
    assert wave["total_s"] >= wave["first_s"]


def test_phase_timers_unit():
    tm = PhaseTimers()
    with tm.scope("p"):
        pass
    assert tm.summary()["p"]["calls"] == 1
    assert "p" in tm.report()
    # Deterministic durations straight through the accumulator.
    stat = timers_lib.PhaseStat("q")
    for dt in (5.0, 1.0, 2.0, 3.0):
        stat.record(dt)
    assert stat.calls == 4
    assert stat.first_s == 5.0
    assert stat.steady_median_s() == 2.0
    assert stat.total_s == 11.0
    # The null sink records nothing and resolve_timers passes real ones
    # through untouched.
    with timers_lib.NULL_TIMERS.scope("x"):
        pass
    assert timers_lib.NULL_TIMERS.summary() == {}
    assert timers_lib.resolve_timers(tm) is tm
    assert timers_lib.resolve_timers(None) is timers_lib.NULL_TIMERS


# -------------------------------------------------------------------------
# Tooling satellites: bench schema validator + report CLI + timeit split
# -------------------------------------------------------------------------


def test_bench_schema_validator(tmp_path):
    from tools import bench_schema

    meta = {k: "x" for k in bench_schema.META_KEYS}
    good = {"snr_db": 10, "clients": 4, "rounds": 3, "arms": {},
            "downlink_worse_than_uplink": True, "meta": meta}
    p = tmp_path / "BENCH_fl_round.json"
    p.write_text(json.dumps(good))
    assert bench_schema.validate_file(p) == []
    # Missing + unexpected keys are both named.
    bad = dict(good)
    del bad["arms"]
    bad["extra"] = 1
    p.write_text(json.dumps(bad))
    msgs = "\n".join(bench_schema.validate_file(p))
    assert "'arms'" in msgs and "'extra'" in msgs
    # Incomplete meta provenance.
    weak = dict(good, meta={"jax": "x"})
    p.write_text(json.dumps(weak))
    assert bench_schema.validate_file(p) != []
    # Unknown artifacts are an error (schema drift must be registered).
    q = tmp_path / "BENCH_mystery.json"
    q.write_text("{}")
    assert bench_schema.validate_file(q) != []


def test_report_cli_smoke(sync_pair, async_pair, capsys):
    from tools import report

    report.summarize(sync_pair[2])
    out = capsys.readouterr().out
    assert "fingerprint" in out and "mode histogram" in out
    assert "final accuracy" in out
    report.diff(sync_pair[2], async_pair[2])
    out = capsys.readouterr().out
    assert "DIFFER" in out and "final_accuracy" in out


def test_timeit_splits_first_call():
    from benchmarks import common

    calls = []
    t = common.timeit(lambda: calls.append(0), warmup=1, iters=3)
    assert isinstance(t, common.Timing)
    assert isinstance(t, float)  # drop-in for the old steady median
    assert t.first_us >= 0.0
    assert len(calls) == 1 + 3  # first+warmup share one call, then iters
