"""The round engine's host spans, host-sync counter and device scopes.

Host side: every round opens ``sample`` (with ``gather`` and ``h2d`` inside
it), ``round``, ``telemetry`` and ``sync``, and an eval round ``eval`` with
a ``sync`` inside. Every blocking device-to-host read sits in a ``sync``
scope, so ``PhaseTimers.summary()["sync"]["calls"]`` counts the round's
host syncs. Device side: the layers of every round-step variant lower
under ``fl_*`` named scopes, which the profiler's op metadata carries.

Runs are tiny (4 clients x 24 samples, 4 rounds, eval every 2 rounds).
"""

import contextlib
import dataclasses

import jax
import numpy as np
import pytest
from jax._src import array as array_lib

from repro.compress.sparsify import CompressionConfig
from repro.configs.mnist_cnn import config as cnn_config
from repro.core import channel as CH
from repro.core import transport as T
from repro.data import synth_mnist
from repro.fl import engine as E
from repro.fl import partition
from repro.fl.loop import run_fl
from repro.link import scenario as S
from repro.obs import PhaseTimers
from repro.obs import timers as timers_lib

N_ROUNDS, EVAL_EVERY = 4, 2
EVAL_ROUNDS = [0, 2, 3]  # every EVAL_EVERY rounds, and the last


@pytest.fixture(scope="module")
def world():
    (img, lab), (ti, tl) = synth_mnist.train_test(60, 16, seed=0)
    parts = partition.non_iid_partition(img, lab, n_clients=4)
    cx, cy = partition.stack_clients(parts, per_client=24)
    return cx, cy, ti, tl


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(cnn_config(), lr=0.1)


def _tc(**kw):
    return T.TransportConfig(mode="approx",
                             channel=CH.ChannelConfig(snr_db=10.0), **kw)


def _scenario(**over):
    return dataclasses.replace(S.get_scenario("vehicular"),
                               ecrt_expected_tx=2.0, **over)


def _engine(cfg, world, sink=None, tcfg=None, **kw):
    cx, cy, ti, tl = world
    return E.RoundEngine(E.FedSGD(cfg, batch_per_round=8), tcfg or _tc(),
                         cx, cy, ti, tl, n_rounds=N_ROUNDS,
                         eval_every=EVAL_EVERY, seed=3, phase_timers=sink,
                         **kw)


class RecordingSink(PhaseTimers):
    """``PhaseTimers`` that also keeps every scope as ``(name, parent)``,
    in the order the scopes open."""

    def __init__(self):
        super().__init__()
        self.events = []
        self._open = []

    @contextlib.contextmanager
    def scope(self, name):
        self.events.append((name, self._open[-1] if self._open else None))
        self._open.append(name)
        try:
            with super().scope(name) as stat:
                yield stat
        finally:
            self._open.pop()


def _rounds(events):
    """Split a sink's events into rounds, each starting at ``sample``."""
    out = []
    for ev in events:
        if ev == ("sample", None):
            out.append([])
        out[-1].append(ev)
    return out


# -------------------------------------------------------------------------
# Host spans and the sync counter
# -------------------------------------------------------------------------


@pytest.mark.parametrize("fused", [False, True], ids=["layered", "fused"])
def test_static_round_spans_nest(cfg, world, fused):
    sink = RecordingSink()
    _engine(cfg, world, sink, fused_aggregate=fused).run()
    rounds = _rounds(sink.events)
    assert len(rounds) == N_ROUNDS
    for r, events in enumerate(rounds):
        want = [("sample", None), ("gather", "sample"), ("h2d", "sample"),
                ("round", None), ("telemetry", None), ("sync", None)]
        if r in EVAL_ROUNDS:
            want += [("eval", None), ("sync", "eval")]
        assert events == want, f"round {r}"


@pytest.mark.parametrize("fused", [False, True], ids=["layered", "fused"])
def test_static_sync_count_is_rounds_plus_evals(cfg, world, fused):
    timers = PhaseTimers()
    res = _engine(cfg, world, timers, fused_aggregate=fused).run()
    summary = timers.summary()
    assert len(res.accuracy) == len(EVAL_ROUNDS)
    assert summary["sync"]["calls"] == N_ROUNDS + len(EVAL_ROUNDS)
    for name in ("sample", "gather", "h2d", "round", "telemetry"):
        assert summary[name]["calls"] == N_ROUNDS
    assert summary["eval"]["calls"] == len(EVAL_ROUNDS)


def test_bucketed_link_round_sync_count(cfg, world):
    """The bucketed scenario round reads three times a round: the mode
    vector inside ``round``, the link record inside ``telemetry``, and the
    airtime."""
    sink = RecordingSink()
    _engine(cfg, world, sink, scenario=_scenario()).run()
    assert sink.summary()["sync"]["calls"] == 3 * N_ROUNDS + len(EVAL_ROUNDS)
    first = _rounds(sink.events)[0]
    syncs = [parent for name, parent in first if name == "sync"]
    assert syncs == ["round", "telemetry", None, "eval"]


def test_ledger_round_summary_is_a_sync(cfg, world, tmp_path):
    timers = PhaseTimers()
    _engine(cfg, world, timers, ledger=str(tmp_path / "l.jsonl")).run()
    assert timers.summary()["sync"]["calls"] == (2 * N_ROUNDS
                                                 + len(EVAL_ROUNDS))


def test_sample_split_keeps_the_batches(cfg, world):
    """``gather`` and ``h2d`` split ``sample`` without changing what it
    returns: the same rows as one numpy gather from the same draw."""
    cx, cy, _, _ = world
    algos = [E.FedSGD(cfg, batch_per_round=8),
             E.FedAvg(cfg, local_steps=2, batch_per_step=4)]
    for algo in algos:
        sink = RecordingSink()
        xb, yb = algo.sample(np.random.default_rng(7), cx, cy, sink)
        x0, y0 = algo.sample(np.random.default_rng(7), cx, cy)
        np.testing.assert_array_equal(np.asarray(xb), np.asarray(x0))
        np.testing.assert_array_equal(np.asarray(yb), np.asarray(y0))
        assert sink.events == [("gather", None), ("h2d", None)]
    take = np.random.default_rng(7).integers(0, cx.shape[1], (cx.shape[0], 8))
    xb, _ = algos[0].sample(np.random.default_rng(7), cx, cy)
    np.testing.assert_array_equal(
        np.asarray(xb), np.take_along_axis(cx, take[:, :, None, None], axis=1))


class _ReadGuard:
    """Flags every host read of a ``jax.Array`` made outside a ``sync``
    scope. The CPU backend does not enforce
    ``jax.transfer_guard_device_to_host``, so reads are caught where every
    one of them goes through: ``ArrayImpl._value``."""

    def __init__(self, monkeypatch):
        self.depth = 0
        self.armed = False
        self.stray = []
        read = array_lib.ArrayImpl._value.fget

        def guarded(arr):
            if self.armed and self.depth == 0:
                self.stray.append(arr.shape)
            return read(arr)

        monkeypatch.setattr(array_lib.ArrayImpl, "_value", property(guarded))

    def sink(self):
        guard = self

        class SyncAllows(PhaseTimers):
            @contextlib.contextmanager
            def scope(self, name):
                guard.depth += name == "sync"
                try:
                    with super().scope(name) as stat:
                        yield stat
                finally:
                    guard.depth -= name == "sync"

        return SyncAllows()


@pytest.mark.parametrize("arm", ["static", "static-fused", "full", "select",
                                 "bucketed-fused"])
def test_every_host_read_is_in_a_sync_scope(cfg, world, monkeypatch, arm):
    """After a first run (which compiles, and reads closed-over device
    constants while lowering), no round reads a device value outside a
    ``sync`` scope."""
    dl = S.DownlinkConfig(mode="approx", snr_offset_db=-3.0, adaptive=True)
    kw = {
        "static": {},
        "static-fused": dict(fused_aggregate=True),
        "full": dict(scenario=_scenario(downlink=dl),
                     compression=CompressionConfig(method="topk", ratio=0.1)),
        "select": dict(scenario=_scenario(downlink=dl),
                       adaptive_dispatch="select"),
        "bucketed-fused": dict(scenario=_scenario(), fused_aggregate=True),
    }[arm]
    guard = _ReadGuard(monkeypatch)
    engine = _engine(cfg, world, guard.sink(), **kw)
    engine.run()
    guard.armed = True
    engine.run()
    guard.armed = False
    assert guard.stray == []


# -------------------------------------------------------------------------
# Sinks stay neutral, the profiler's clock included
# -------------------------------------------------------------------------


def test_phase_timers_scope_enters_trace_annotation(monkeypatch):
    entered = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(("enter", self.name))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    tm = PhaseTimers()
    with tm.scope("sample"):
        with tm.scope("gather"):
            pass
    assert entered == [("enter", "sample"), ("enter", "gather"),
                       ("exit", "gather"), ("exit", "sample")]
    assert tm.summary()["gather"]["calls"] == 1


def test_null_timers_is_one_shared_nullcontext():
    a = timers_lib.NULL_TIMERS.scope("sample")
    assert isinstance(a, contextlib.nullcontext)
    assert a is timers_lib.NULL_TIMERS.scope("sync")
    with a as stat:
        assert stat is None


def test_spans_under_a_live_profiler_are_neutral(cfg, world, tmp_path):
    """PhaseTimers inside a running ``jax.profiler`` trace leaves the
    parameters, accuracy and airtime bit-identical to a bare run."""
    cx, cy, ti, tl = world
    kw = dict(n_rounds=3, batch_per_round=8, eval_every=2, seed=3,
              fused_aggregate=True)
    bare = run_fl(cfg, _tc(), cx, cy, ti, tl, **kw)
    timers = PhaseTimers()
    with jax.profiler.trace(str(tmp_path / "trace")):
        traced = run_fl(cfg, _tc(), cx, cy, ti, tl, phase_timers=timers,
                        **kw)
    assert traced.accuracy == bare.accuracy
    assert traced.airtime_s == bare.airtime_s
    for a, b in zip(jax.tree_util.tree_leaves(traced.params),
                    jax.tree_util.tree_leaves(bare.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert timers.summary()["sync"]["calls"] == 3 + 2


# -------------------------------------------------------------------------
# Device scopes
# -------------------------------------------------------------------------


def _lowered_text(engine, variant, cx, cy):
    xb, yb = engine.algo.sample(np.random.default_rng(0), cx, cy)
    p, a, k = engine.params, engine.aux, engine._key
    if variant == "eval":
        fn, args = engine._eval_acc, (p,)
    elif variant == "compressed":
        fn, args = engine._round_step_comp, (p, a, xb, yb, k,
                                             engine._ef_residual)
    elif variant == "select":
        fn, args = engine._round_step_link, (p, a, xb, yb, k, engine.lstate,
                                             engine.prev_mode,
                                             engine.prev_est)
    else:
        fn, args = engine._round_step, (p, a, xb, yb, k)
    return fn.lower(*args).as_text(debug_info=True)


SCOPE_CASES = {
    "fused": (dict(fused_aggregate=True, tcfg=_tc(use_kernel=True)),
              ["fl_grad", "fl_uplink", "fl_apply"]),
    "layered": ({}, ["fl_grad", "fl_uplink", "fl_aggregate", "fl_apply"]),
    "downlink": (dict(downlink=S.DownlinkConfig(mode="approx")),
                 ["fl_downlink", "fl_grad", "fl_uplink", "fl_aggregate",
                  "fl_apply"]),
    "compressed": (dict(compression=CompressionConfig(method="topk",
                                                      ratio=0.1)),
                   ["fl_grad", "fl_uplink", "fl_aggregate", "fl_apply"]),
    "select": (dict(scenario=_scenario(), adaptive_dispatch="select"),
               ["fl_link", "fl_grad", "fl_uplink", "fl_aggregate",
                "fl_apply"]),
    "eval": ({}, ["fl_eval"]),
}


@pytest.mark.parametrize("variant", list(SCOPE_CASES))
def test_round_step_lowers_under_layer_scopes(cfg, world, variant):
    kw, scopes = SCOPE_CASES[variant]
    cx, cy, _, _ = world
    text = _lowered_text(_engine(cfg, world, **kw), variant, cx, cy)
    for scope in scopes:
        assert scope in text, f"{scope} missing from the {variant} lowering"
