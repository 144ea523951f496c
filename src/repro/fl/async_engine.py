"""Event-driven buffered FL engine: FedBuff-style asynchronous rounds.

The synchronous :class:`~repro.fl.engine.RoundEngine` closes a barrier every
round: the cohort's uplink is materialized at once and the slowest client
stalls everyone — exactly the regime the paper's approximate-communication
scheme is meant to escape. This module replaces the barrier with an **event
clock** (Nguyen et al.'s FedBuff, arXiv:2106.06639, composed with this
repo's noisy two-leg transport): clients are dispatched in *waves*, each
client's update lands at

    t_arrival = t_dispatch + downlink_wait + compute_time + uplink_airtime

(``core.latency.arrival_times``; compute times from
``link.dynamics.ComputeTimeConfig``, airtime from the same per-client
pricing the synchronous engine uses), and the server aggregates whenever
``buffer_k`` updates have landed — weighting each buffered update by a
pluggable **staleness function** of how many aggregations it missed while
in flight (constant / polynomial / inverse).

Determinism and the key-lane convention
---------------------------------------
The wave key schedule *is* the synchronous round schedule: one
``key, rk = split(key)`` per dispatched wave, with every extra draw riding
reserved ``fold_in`` lanes of ``rk`` (``dynamics.COMPUTE_KEY_LANE`` for
compute times, ``dynamics.EVENT_KEY_LANE`` for churn/idle draws) — lanes
consume no splits and each client folds its own index, so arrival draws are
bit-stable across dispatches and independent of cohort batching. Every wave
computes the **full-cohort** uplink with non-members masked out: per-client
fold_in keys make the member rows bit-identical to a subset computation,
shapes stay static (one compiled program per wave variant), and discarded
non-member draws perturb nothing.

The load-bearing invariant (``tests/test_async_golden.py``): with
simultaneous arrivals (degenerate compute model), ``buffer_k =`` cohort
size, and constant staleness weights, every wave is one full synchronous
round — the buffered engine is **bit-identical** to ``RoundEngine`` on
every scenario x algorithm x dispatch combination, including compressed
and noisy-downlink arms. Two arithmetic details make that exact:

* a buffer holding one complete uniform-weight driver-less wave aggregates
  with ``jnp.mean`` (the weighted mean reduces to the plain mean in real
  arithmetic, but not bit-wise — ``tensordot(ones, g)/M != mean(g, 0)`` on
  XLA CPU, so the degenerate path must use the synchronous engine's op);
* scenario buffers use ``tensordot(wvec, hat) / where(total > 0, total, 1)``
  — bit-equal to ``engine.dropout_weighted_mean``'s ``maximum(total, 1)``
  form whenever the weights are 0/1.

State across participation gaps
-------------------------------
EF/compression residuals update through a ``where(member, new, old)`` mask:
a client that skips R waves (dropped, in flight, or churned out) re-enters
with its full accumulated residual bit-exact. Link-policy hysteresis and
CSI memory survive the same way: ``ScenarioDriver.round(observed=member)``
holds absent clients' modes, and the previous-estimate carry only refreshes
member rows.
"""

from __future__ import annotations

import heapq
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress import framing as framing_lib
from repro.compress import sparsify as sparsify_lib
from repro.core import aggregation as aggregation_lib
from repro.core import keylanes
from repro.core import latency as latency_lib
from repro.core import transport as transport_lib
from repro.fl import engine as engine_lib
from repro.link import dynamics as dynamics_lib
from repro.obs import records as obs_records_lib
from repro.obs import trace as obs_trace_lib

__all__ = [
    "STALENESS_KINDS",
    "staleness_weight",
    "weighted_buffer_mean",
    "AsyncRoundEngine",
    "run_fl_buffered",
    "run_fedavg_buffered",
]

STALENESS_KINDS = ("constant", "polynomial", "inverse")


def staleness_weight(staleness, kind: str = "constant",
                     alpha: float = 0.5) -> jax.Array:
    """Aggregation weight of an update that missed ``staleness`` rounds.

    ``constant`` is exactly 1.0 regardless of staleness (FedBuff's
    unweighted buffer, and the synchronous-equivalence setting);
    ``polynomial`` is ``(1 + s)^-alpha`` (Xie et al.'s FedAsync damping);
    ``inverse`` is ``1 / (1 + s)``. All are non-negative, equal to 1 at
    ``s = 0``, and non-increasing in ``s``; normalization happens in the
    aggregation (:func:`weighted_buffer_mean` divides by the total weight).
    """
    s = jnp.asarray(staleness, jnp.float32)
    if kind == "constant":
        return jnp.ones_like(s)
    if kind == "polynomial":
        return (1.0 + s) ** (-alpha)
    if kind == "inverse":
        return 1.0 / (1.0 + s)
    raise ValueError(
        f"unknown staleness kind {kind!r}; pick one of {STALENESS_KINDS}")


def weighted_buffer_mean(entries):
    """Staleness-weighted mean of buffered wave payloads.

    ``entries`` is an iterable of ``(wave_id, hat, wvec)``: ``hat`` a
    payload pytree with ``(M, ...)`` leaves, ``wvec`` the ``(M,)``
    per-client weight (0 for clients of the wave not in the buffer).
    Entries are canonicalized by wave id before any float op, so the
    result is **invariant to arrival order** — the property the buffered
    engine's aggregation schedule relies on (and
    ``tests/test_async_properties.py`` pins). An all-zero total weight
    yields zeros (the model does not move), mirroring
    ``engine.dropout_weighted_mean``.
    """
    entries = sorted(entries, key=lambda e: e[0])
    if not entries:
        raise ValueError("weighted_buffer_mean needs at least one entry")
    part = None
    total = jnp.float32(0.0)
    for _, hat, wvec in entries:
        w = jnp.asarray(wvec, jnp.float32)
        p = jax.tree_util.tree_map(
            lambda g: jnp.tensordot(w, g, axes=(0, 0)), hat)
        part = p if part is None else jax.tree_util.tree_map(
            jnp.add, part, p)
        total = total + jnp.sum(w)
    denom = jnp.where(total > 0, total, 1.0)
    return jax.tree_util.tree_map(lambda g: g / denom, part)


class AsyncRoundEngine(engine_lib.RoundEngine):
    """Buffered asynchronous round driver over the synchronous engine.

    Inherits all of :class:`~repro.fl.engine.RoundEngine`'s construction —
    scenario/downlink/compression resolution, analytic-ECRT pricing, the
    key schedule — and replaces the barrier loop with the event loop
    described in the module docstring. ``n_rounds`` counts *aggregations*
    (model versions), so results line up with the synchronous engine's
    round axis; ``FLResult.event_s`` carries the event-clock timestamp of
    each eval point.

    Scheduling model: new waves are dispatched at aggregation boundaries
    (and on buffer drains), sending every client that is joined, idle, and
    past its post-upload gap — a batched approximation of per-client
    restarts that keeps one compiled program per wave variant. Dropped
    clients (scenario ``dropout_prob``) produce no arrival and become
    ready again after their compute time; churned-out clients
    (``ArrivalConfig.p_leave``) keep any in-flight upload but are not
    re-dispatched until they rejoin.
    """

    def __init__(self, algorithm, transport_cfg, client_x, client_y,
                 test_x, test_y, *, n_rounds: int, buffer_k: int | None = None,
                 staleness: str = "constant", staleness_alpha: float = 0.5,
                 compute: dynamics_lib.ComputeTimeConfig | None = None,
                 arrival: dynamics_lib.ArrivalConfig | None = None,
                 seed: int = 0, eval_every: int = 2,
                 timings: latency_lib.PhyTimings | None = None,
                 scenario=None, adaptive_dispatch: str = "bucketed",
                 downlink=None, compression=None,
                 fused_aggregate: bool = False, ledger=None, trace=None,
                 phase_timers=None, sketches=None):
        super().__init__(
            algorithm, transport_cfg, client_x, client_y, test_x, test_y,
            n_rounds=n_rounds, seed=seed, eval_every=eval_every,
            timings=timings, scenario=scenario,
            adaptive_dispatch=adaptive_dispatch, downlink=downlink,
            compression=compression, fused_aggregate=fused_aggregate,
            ledger=ledger, phase_timers=phase_timers, sketches=sketches)
        # Perfetto trace sink (repro.obs.trace): a path or a TraceRecorder.
        # Like the ledger, a pure observer of host values the event loop
        # already computed.
        self.trace = obs_trace_lib.as_trace(trace)
        M = self.num_clients
        self.buffer_k = M if buffer_k is None else int(buffer_k)
        if not 1 <= self.buffer_k <= M:
            raise ValueError(
                f"buffer_k must be in [1, {M}], got {self.buffer_k}")
        if self.fused_aggregate and self.buffer_k != M:
            # With one full wave per aggregation, every buffered update has
            # staleness 0 and the aggregation weights are known at dispatch
            # — the precondition for folding the weighted sum into the wave's
            # transport pass. A partial buffer mixes waves of different
            # staleness, whose weights only exist at aggregation time.
            raise ValueError(
                "fused_aggregate=True needs buffer_k == num_clients "
                f"({M}): partial buffers weight updates by staleness at "
                "aggregation time, after the fused transport pass")
        if staleness not in STALENESS_KINDS:
            raise ValueError(
                f"staleness must be one of {STALENESS_KINDS}, got "
                f"{staleness!r}")
        self.staleness = staleness
        self.staleness_alpha = float(staleness_alpha)
        scen = None if self.driver is None else self.driver.scenario
        self.compute_cfg = (compute
                            or (scen.compute if scen is not None else None)
                            or dynamics_lib.ComputeTimeConfig())
        self.arrival_cfg = (arrival if arrival is not None
                            else (scen.arrival if scen is not None else None))
        # Frozen per-client speed factors ride a reserved lane of the
        # post-init base key — fold_in consumes no splits, so the wave key
        # schedule below still matches the synchronous round schedule.
        self._speed = dynamics_lib.client_speed_factors(
            jax.random.fold_in(self._key, keylanes.COMPUTE_KEY_LANE),
            M, self.compute_cfg)
        self._build_wave_fns()

    # ------------------------------------------------------- observability

    def _manifest(self) -> dict:
        """The synchronous manifest plus the buffering axis; the config
        fingerprint re-derives over the buffer/staleness/event-layer
        configs so async runs never collide with their sync twins."""
        from repro.obs import ledger as obs_ledger_lib

        man = super()._manifest()
        man["engine"] = "async"
        man["buffer_k"] = self.buffer_k
        man["staleness"] = self.staleness
        man["staleness_alpha"] = self.staleness_alpha
        man["fingerprint"] = obs_ledger_lib.config_fingerprint(
            man["fingerprint"], self.buffer_k, self.staleness,
            self.staleness_alpha, self.compute_cfg, self.arrival_cfg)
        return man

    def _emit_event(self, ev: obs_records_lib.EventRecord) -> None:
        """Fan one event-clock record out to the attached sinks (callers
        gate on ``_obs_events`` so uninstrumented runs build no records)."""
        if self.ledger is not None:
            self.ledger.write_event(ev)
        if self.trace is not None:
            self.trace.add(ev)

    @property
    def _obs_events(self) -> bool:
        """Whether any sink wants the event stream."""
        return (self.trace is not None
                or (self.ledger is not None and self.ledger.events))

    # ----------------------------------------------------------- wave fns

    def _build_wave_fns(self):
        """Jitted wave-step variants: the synchronous round steps with the
        aggregate/apply tail split off (buffered aggregation happens at its
        own event times) and a ``member`` mask threaded through the EF and
        link-memory updates. Masked-out rows are computed (static shapes)
        and discarded — per-client fold_in keys keep member rows
        bit-identical to the synchronous full-cohort rounds."""
        algo, tcfg, driver = self.algo, self.transport_cfg, self.driver
        dl, M = self.downlink, self.num_clients
        comp, D, kbase = self.compression, self._comp_dim, self._comp_k

        def _sel_keys(key):
            if comp.method != "randk":
                return None
            return sparsify_lib.selection_keys(key, M)

        # Aggregation/apply tails. The degenerate driver-less buffer (one
        # complete uniform-weight wave) must use jnp.mean — see the module
        # docstring; the weighted tail's where-form denominator is
        # bit-equal to dropout_weighted_mean's maximum-form for 0/1
        # weights.
        @jax.jit
        def agg_apply_mean(params, aux, hat):
            return algo.apply(params, aux, engine_lib.cohort_mean(hat))

        @jax.jit
        def agg_apply_one(params, aux, hat, wvec):
            with jax.named_scope("fl_aggregate"):
                total = jnp.sum(wvec)
                denom = jnp.where(total > 0, total, 1.0)
                agg = jax.tree_util.tree_map(
                    lambda g: jnp.tensordot(wvec, g, axes=(0, 0)) / denom, hat)
            return algo.apply(params, aux, agg)

        @jax.jit
        def apply_only(params, aux, agg):
            return algo.apply(params, aux, agg)

        self._agg_apply_mean = agg_apply_mean
        self._agg_apply_one = agg_apply_one
        self._apply_only = apply_only

        if driver is None:

            @jax.jit
            def wave_plain(params, xb, yb, key):
                dstats = None
                if dl is None:
                    payload = algo.payload(params, xb, yb)
                else:
                    recv, dstats = transport_lib.transmit_pytree_broadcast(
                        params, key, self.dl_cfg, M)
                    payload = algo.payload_from(recv, xb, yb)
                hat, stats = algo.wrap_uplink(
                    payload,
                    lambda t: transport_lib.transmit_pytree_batch(
                        t, key, tcfg))
                return hat, stats, dstats

            self._wave_plain = wave_plain

            if self.fused_aggregate:

                @jax.jit
                def wave_plain_fused(params, xb, yb, key, member):
                    # Fused wave: uplink + weighted aggregation in one
                    # transport pass. buffer_k == M guarantees this wave is
                    # the whole next aggregation (staleness 0), so the
                    # weights — the normalized member mask — are known now.
                    dstats = None
                    if dl is None:
                        payload = algo.payload(params, xb, yb)
                    else:
                        recv, dstats = transport_lib.transmit_pytree_broadcast(
                            params, key, self.dl_cfg, M)
                        payload = algo.payload_from(recv, xb, yb)
                    w = aggregation_lib.normalize_weights(member)
                    agg, stats = transport_lib.transmit_pytree_batch_aggregate(
                        payload, key, tcfg, w, donate=True)
                    return agg, stats, dstats

                self._wave_plain_fused = wave_plain_fused

            if comp is not None:

                @jax.jit
                def wave_plain_comp(params, xb, yb, key, residual, member):
                    dstats = None
                    if dl is None:
                        payload = algo.payload(params, xb, yb)
                    else:
                        recv, dstats = \
                            transport_lib.transmit_pytree_broadcast(
                                params, key, self.dl_cfg, M)
                        payload = algo.payload_from(recv, xb, yb)
                    flat, spec = transport_lib._flatten_client_tree(payload)
                    vals, idx, new_res = sparsify_lib.ef_select_batch(
                        residual, flat, kbase, comp, _sel_keys(key),
                        active=member)
                    hat_flat, stats = algo.wrap_uplink(
                        vals,
                        lambda v: framing_lib.transmit_sparse_batch(
                            v, idx, D, key, tcfg, comp))
                    hat = transport_lib._unflatten_client_tree(hat_flat, spec)
                    # Non-members never transmitted: keep their residual
                    # bit-exact (their payload rows were mask fodder).
                    new_res = jnp.where(member[:, None] > 0, new_res,
                                        residual)
                    return hat, stats, dstats, new_res

                self._wave_plain_comp = wave_plain_comp
            return

        @jax.jit
        def wave_link(params, xb, yb, key, lstate, prev_mode, prev_est,
                      member):
            # Select dispatch: the synchronous fused round minus its
            # aggregate/apply tail; hysteresis and CSI memory only refresh
            # member rows.
            k_link, k_tx = jax.random.split(key)
            lstate, rnd = driver.round(lstate, prev_mode, prev_est, k_link,
                                       observed=member)
            dstats = None
            if dl is None:
                payload = algo.payload(params, xb, yb)
            else:
                recv, dstats = self._broadcast_scenario(params, k_tx, rnd)
                payload = algo.payload_from(recv, xb, yb)
            hat, stats = algo.wrap_uplink(
                payload,
                lambda t: transport_lib.transmit_pytree_batch_adaptive(
                    t, k_tx, engine_lib.select_mode_cfgs(driver), rnd.mode,
                    snr_db=rnd.snr_db, dispatch="select"))
            new_est = jnp.where(member > 0, rnd.est_db, prev_est)
            return hat, stats, lstate, rnd, dstats, new_est

        self._wave_link = wave_link

        @jax.jit
        def link_round_obs(lstate, prev_mode, prev_est, key, member):
            lstate, rnd = driver.round(lstate, prev_mode, prev_est, key,
                                       observed=member)
            new_est = jnp.where(member > 0, rnd.est_db, prev_est)
            return lstate, rnd, new_est

        payload_shared = jax.jit(lambda params, xb, yb: algo.payload(
            params, xb, yb))
        payload_per_client = jax.jit(lambda recv, xb, yb: algo.payload_from(
            recv, xb, yb))

        def wave_link_bucketed(params, xb, yb, key, lstate, prev_mode,
                               prev_est, member):
            # Bucketed dispatch: the mode vector syncs to the host so each
            # transport leg runs per-mode buckets, as in the synchronous
            # engine.
            k_link, k_tx = jax.random.split(key)
            lstate, rnd, new_est = link_round_obs(lstate, prev_mode,
                                                  prev_est, k_link, member)
            mode_np = np.asarray(rnd.mode)
            dstats = None
            if dl is None:
                payload = payload_shared(params, xb, yb)
            else:
                dl_mode = None
                if dl.adaptive:
                    dl_mode = np.asarray(self._downlink_modes(
                        np.asarray(rnd.est_db)))
                recv, dstats = self._broadcast_scenario(
                    params, k_tx, rnd, dl_mode=dl_mode, dispatch="bucketed")
                payload = payload_per_client(recv, xb, yb)
            hat, stats = algo.wrap_uplink(
                payload,
                lambda t: transport_lib.transmit_pytree_batch_adaptive(
                    t, k_tx, driver.mode_cfgs, mode_np, snr_db=rnd.snr_db,
                    dispatch="bucketed"))
            return hat, stats, lstate, rnd, dstats, new_est

        self._wave_link_bucketed = wave_link_bucketed

        if self.fused_aggregate:
            fused_weights = jax.jit(
                lambda member, active: aggregation_lib.normalize_weights(
                    member * active))

            def wave_link_bucketed_fused(params, xb, yb, key, lstate,
                                         prev_mode, prev_est, member):
                # Fused bucketed wave: dropped and non-member clients still
                # transmit (mask fodder, exactly as the layered wave) but
                # fold into the accumulator with weight 0; only members that
                # will actually arrive carry weight, and with buffer_k == M
                # those are the whole next aggregation (staleness 0).
                k_link, k_tx = jax.random.split(key)
                lstate, rnd, new_est = link_round_obs(lstate, prev_mode,
                                                      prev_est, k_link,
                                                      member)
                mode_np = np.asarray(rnd.mode)
                dstats = None
                if dl is None:
                    payload = payload_shared(params, xb, yb)
                else:
                    dl_mode = None
                    if dl.adaptive:
                        dl_mode = np.asarray(self._downlink_modes(
                            np.asarray(rnd.est_db)))
                    recv, dstats = self._broadcast_scenario(
                        params, k_tx, rnd, dl_mode=dl_mode,
                        dispatch="bucketed")
                    payload = payload_per_client(recv, xb, yb)
                agg, stats = \
                    transport_lib.transmit_pytree_batch_adaptive_aggregate(
                        payload, k_tx, driver.mode_cfgs, mode_np,
                        fused_weights(member, rnd.active),
                        snr_db=rnd.snr_db, donate=True)
                return agg, stats, lstate, rnd, dstats, new_est

            self._wave_link_bucketed_fused = wave_link_bucketed_fused

        if comp is None:
            return

        @jax.jit
        def wave_link_comp(params, xb, yb, key, lstate, prev_mode, prev_est,
                           residual, member):
            k_link, k_tx = jax.random.split(key)
            lstate, rnd = driver.round(lstate, prev_mode, prev_est, k_link,
                                       observed=member)
            dstats = None
            if dl is None:
                payload = algo.payload(params, xb, yb)
            else:
                recv, dstats = self._broadcast_scenario(params, k_tx, rnd)
                payload = algo.payload_from(recv, xb, yb)
            flat, spec = transport_lib._flatten_client_tree(payload)
            eff = member * rnd.active
            vals, idx, new_res = sparsify_lib.ef_select_batch(
                residual, flat, kbase, comp, _sel_keys(k_tx), active=eff)
            hat_flat, stats = algo.wrap_uplink(
                vals,
                lambda v: framing_lib.transmit_sparse_batch_adaptive(
                    v, idx, D, k_tx, engine_lib.select_mode_cfgs(driver),
                    rnd.mode, comp, snr_db=rnd.snr_db, dispatch="select"))
            hat = transport_lib._unflatten_client_tree(hat_flat, spec)
            new_res = jnp.where(member[:, None] > 0, new_res, residual)
            new_est = jnp.where(member > 0, rnd.est_db, prev_est)
            return hat, stats, lstate, rnd, dstats, new_res, new_est

        self._wave_link_comp = wave_link_comp

        if comp.error_feedback:
            accumulate = jax.jit(lambda r, f: r + f)
            residual_update = jax.jit(
                lambda acc, sent, act: acc - sent * act[:, None])
        else:
            accumulate = jax.jit(lambda r, f: f)
            residual_update = jax.jit(
                lambda acc, sent, act: jnp.zeros_like(acc))
        keep_absent = jax.jit(
            lambda member, new, old: jnp.where(member[:, None] > 0, new, old))

        def wave_link_bucketed_comp(params, xb, yb, key, lstate, prev_mode,
                                    prev_est, residual, member):
            k_link, k_tx = jax.random.split(key)
            lstate, rnd, new_est = link_round_obs(lstate, prev_mode,
                                                  prev_est, k_link, member)
            mode_np = np.asarray(rnd.mode)
            dstats = None
            if dl is None:
                payload = payload_shared(params, xb, yb)
            else:
                dl_mode = None
                if dl.adaptive:
                    dl_mode = np.asarray(self._downlink_modes(
                        np.asarray(rnd.est_db)))
                recv, dstats = self._broadcast_scenario(
                    params, k_tx, rnd, dl_mode=dl_mode, dispatch="bucketed")
                payload = payload_per_client(recv, xb, yb)
            flat, spec = transport_lib._flatten_client_tree(payload)
            acc = accumulate(residual, flat)
            dense_hat, stats, sent = self._sparse_bucketed_uplink(
                acc, k_tx, mode_np, rnd.snr_db)
            eff = member * rnd.active
            new_res = residual_update(acc, sent, eff)
            new_res = keep_absent(member, new_res, residual)
            hat = transport_lib._unflatten_client_tree(dense_hat, spec)
            return hat, stats, lstate, rnd, dstats, new_res, new_est

        self._wave_link_bucketed_comp = wave_link_bucketed_comp

    # --------------------------------------------------------------- run

    def run(self) -> engine_lib.FLResult:
        """Drive ``n_rounds`` buffered aggregations; returns ``FLResult``
        with ``event_s`` timestamps alongside the usual curves."""
        algo, driver, timings = self.algo, self.driver, self.timings
        comp, tm = self.compression, self.phase_timers
        obs_events = self._obs_events
        M, K = self.num_clients, self.buffer_k
        params, aux, key = self.params, self.aux, self._key
        rng = np.random.default_rng(self.seed)
        res = engine_lib.FLResult([], [], [], 0.0, 0.0)
        t0 = time.time()  # lint: ignore[determinism] wall-clock telemetry
        if self.ledger is not None:
            self.ledger.write_manifest(self._manifest())

        cum_air = 0.0
        t_now = 0.0
        version = 0
        next_wave = 0
        buffered = 0
        ready_t = np.zeros(M, np.float64)
        in_flight = np.zeros(M, bool)
        joined = np.ones(M, np.float32)
        heap = []  # (t_arrival, wave_id, client) — deterministic tie order
        waves = {}  # wave_id -> {hat, version, arrived, pending, gaps}

        def dispatch():
            """Send one wave of every joined, idle, ready client. Returns
            True iff a wave went out. Consumes exactly one key split per
            attempt that reaches the churn/wave draw — never on a plain
            nobody-is-ready miss (the degenerate schedule stays one split
            per synchronous round)."""
            nonlocal key, next_wave, cum_air, params, aux
            idle = (joined > 0) & ~in_flight & (ready_t <= t_now)
            if self.arrival_cfg is None and not idle.any():
                return False
            key, rk = jax.random.split(key)
            if self.arrival_cfg is not None:
                prev_joined = joined.copy()
                joined[:] = np.asarray(dynamics_lib.churn_step(
                    rk, jnp.asarray(joined), self.arrival_cfg))
                if obs_events:
                    for i in np.nonzero(prev_joined != joined)[0]:
                        self._emit_event(obs_records_lib.EventRecord(
                            t=t_now,
                            kind="join" if joined[i] > 0 else "leave",
                            client=int(i)))
                idle = (joined > 0) & ~in_flight & (ready_t <= t_now)
                if not idle.any():
                    return False
            member_np = idle.astype(np.float32)
            member = jnp.asarray(member_np)
            with tm.scope("sample"):
                xb, yb = algo.sample(rng, self.client_rows, self.client_y,
                                    tm)
            rnd = None
            agg = hat = None
            if driver is None:
                with tm.scope("wave"):
                    if self.fused_aggregate:
                        agg, stats, dstats = self._wave_plain_fused(
                            params, xb, yb, rk, member)
                    elif comp is None:
                        hat, stats, dstats = self._wave_plain(
                            params, xb, yb, rk)
                    else:
                        hat, stats, dstats, self._ef_residual = \
                            self._wave_plain_comp(params, xb, yb, rk,
                                                  self._ef_residual, member)
                rec = obs_records_lib.RoundRecord(round=next_wave)
                with tm.scope("telemetry"):
                    per_air = latency_lib.round_airtime(
                        stats, timings, self.transport_cfg.mode)
                    if self.ecrt_air_scale is not None:
                        per_air = per_air * self.ecrt_air_scale
                    per_air = per_air * member
                active = member
            else:
                with tm.scope("wave"):
                    if self.fused_aggregate:
                        (agg, stats, self.lstate, rnd, dstats,
                         self.prev_est) = self._wave_link_bucketed_fused(
                            params, xb, yb, rk, self.lstate, self.prev_mode,
                            self.prev_est, member)
                    elif comp is None:
                        step = (self._wave_link_bucketed
                                if self.dispatch == "bucketed"
                                else self._wave_link)
                        (hat, stats, self.lstate, rnd, dstats,
                         self.prev_est) = step(
                            params, xb, yb, rk, self.lstate, self.prev_mode,
                            self.prev_est, member)
                    else:
                        step = (self._wave_link_bucketed_comp
                                if self.dispatch == "bucketed"
                                else self._wave_link_comp)
                        (hat, stats, self.lstate, rnd, dstats,
                         self._ef_residual, self.prev_est) = step(
                            params, xb, yb, rk, self.lstate, self.prev_mode,
                            self.prev_est, self._ef_residual, member)
                self.prev_mode = rnd.mode
                with tm.scope("telemetry"):
                    per_air = driver.airtime(stats, rnd, timings) * member
                    rec = obs_records_lib.scenario_round_record(
                        next_wave, rnd, per_air, len(driver.mode_cfgs))
                active = member * rnd.active
            cum_air += float(jnp.sum(per_air))
            if comp is not None:
                self._compression_record(rec, stats, rnd)
            dl_wait = 0.0
            if dstats is not None:
                dl_wait = self._downlink_air_record(rec, dstats)
                cum_air += dl_wait
            comp_s = np.asarray(dynamics_lib.compute_times(
                rk, self.compute_cfg, M, self._speed), np.float64)
            air_np = np.asarray(per_air, np.float64)
            arr = latency_lib.arrival_times(t_now, comp_s, air_np, dl_wait)
            gaps = np.zeros(M, np.float64)
            if self.arrival_cfg is not None:
                gaps = np.asarray(dynamics_lib.idle_gaps(
                    rk, M, self.arrival_cfg), np.float64)
            active_b = np.asarray(active) > 0
            pending = 0
            for i in np.nonzero(member_np > 0)[0]:
                i = int(i)
                if active_b[i]:
                    heapq.heappush(heap, (float(arr[i]), next_wave, i))
                    in_flight[i] = True
                    pending += 1
                else:
                    # Dropped: no uplink happened (air = 0), the client is
                    # back after its broadcast wait + compute time.
                    ready_t[i] = float(arr[i])
            if obs_events:
                members = np.nonzero(member_np > 0)[0]
                arrived = [float(arr[i]) for i in members if active_b[i]]
                self._emit_event(obs_records_lib.EventRecord(
                    t=t_now, kind="wave", wave=next_wave,
                    dur=(max(arrived) - t_now) if arrived else 0.0,
                    value=float(len(members))))
                for i in members:
                    i = int(i)
                    self._emit_event(obs_records_lib.EventRecord(
                        t=t_now + dl_wait, kind="compute", wave=next_wave,
                        client=i, dur=float(comp_s[i])))
                    if active_b[i]:
                        self._emit_event(obs_records_lib.EventRecord(
                            t=t_now + dl_wait + float(comp_s[i]),
                            kind="uplink", wave=next_wave, client=i,
                            dur=float(air_np[i])))
            if self.sketcher is not None:
                with tm.scope("telemetry"):
                    rec.sketches = self.sketcher.round_group(
                        rk, snr_db=rnd.snr_db, est_db=rnd.est_db,
                        ber=stats.client_metrics()["ber"],
                        airtime_s=per_air, mode=rnd.mode,
                        active=rnd.active, member=member,
                        downlink_ber=(None if dstats is None
                                      else dstats.ber))
            rec.t_event = t_now
            self._finish_record(res, rec, stats)
            waves[next_wave] = {
                "hat": hat, "agg": agg, "version": version,
                "arrived": np.zeros(M, np.float32),
                "pending": pending, "gaps": gaps,
            }
            next_wave += 1
            return True

        def aggregate():
            """Fold the buffer into the model: one aggregation = one model
            version. Entries iterate in wave-id order (arrival-order
            invariant); the degenerate driver-less buffer takes the
            synchronous engine's ``jnp.mean`` path. Fused runs hold exactly
            one wave (buffer_k == M) whose transport pass already produced
            the aggregate — only the apply tail runs here."""
            nonlocal params, aux, version, buffered
            if self.fused_aggregate:
                w = max(waves)
                info = waves[w]
                if obs_events:
                    self._emit_event(obs_records_lib.EventRecord(
                        t=t_now, kind="aggregate", version=version,
                        value=float(info["arrived"].sum())))
                    self._emit_event(obs_records_lib.EventRecord(
                        t=t_now, kind="buffer", value=0.0))
                if self.sketcher is not None:
                    # Fused buffers hold exactly one zero-staleness wave.
                    self.sketcher.observe_staleness(
                        np.zeros(int(info["arrived"].sum()), np.float32))
                params, aux = self._apply_only(params, aux, info["agg"])
                del waves[w]
                buffered = 0
            else:
                entries = []
                for w in sorted(waves):
                    info = waves[w]
                    mask = info["arrived"]
                    if not mask.any():
                        continue
                    om = float(staleness_weight(
                        version - info["version"], self.staleness,
                        self.staleness_alpha))
                    entries.append((w, info["hat"],
                                    jnp.asarray(mask * np.float32(om)),
                                    mask, om))
                if self.sketcher is not None and entries:
                    # One staleness observation per folded client update.
                    self.sketcher.observe_staleness(np.concatenate([
                        np.full(int(mask.sum()),
                                version - waves[w]["version"], np.float32)
                        for w, _, _, mask, _ in entries]))
                if obs_events:
                    folded = sum(
                        int(mask.sum()) for _, _, _, mask, _ in entries)
                    self._emit_event(obs_records_lib.EventRecord(
                        t=t_now, kind="aggregate", version=version,
                        value=float(folded)))
                    self._emit_event(obs_records_lib.EventRecord(
                        t=t_now, kind="buffer", value=0.0))
                uniform_full = (
                    len(entries) == 1 and entries[0][4] > 0
                    and bool(entries[0][3].all()))
                if not entries:
                    # Every member of the flushed wave dropped out before
                    # the uplink: the synchronous engine still applies the
                    # (zero) aggregate and counts the round, so mirror its
                    # arithmetic — zero weights through the weighted tail.
                    w = max(waves)
                    params, aux = self._agg_apply_one(
                        params, aux, waves[w]["hat"],
                        jnp.zeros(M, jnp.float32))
                elif driver is None and uniform_full:
                    params, aux = self._agg_apply_mean(params, aux,
                                                       entries[0][1])
                elif len(entries) == 1:
                    params, aux = self._agg_apply_one(params, aux,
                                                      entries[0][1],
                                                      entries[0][2])
                else:
                    agg = weighted_buffer_mean(
                        [(w, hat, wvec) for w, hat, wvec, _, _ in entries])
                    params, aux = self._apply_only(params, aux, agg)
                for w, *_ in entries:
                    waves[w]["arrived"][:] = 0.0
                for w in [w for w, info in waves.items()
                          if info["pending"] == 0
                          and not info["arrived"].any()]:
                    del waves[w]
                buffered = 0
            r = version
            version += 1
            if r % self.eval_every == 0 or r == self.n_rounds - 1:
                with tm.scope("eval"):
                    acc = float(self._eval_acc(params))
                res.rounds.append(r)
                res.accuracy.append(acc)
                res.airtime_s.append(cum_air)
                res.event_s.append(t_now)
                if self.ledger is not None:
                    self.ledger.write_eval(r, acc, cum_air, event_s=t_now)

        dispatch()
        stalls = 0
        while version < self.n_rounds:
            if buffered >= K or (not heap and waves):
                # Trigger: K updates landed — or the pipeline drained with
                # outstanding waves (a partial buffer, e.g. the wave minus
                # dropouts — or a fully-dropped wave, which still costs a
                # zero-update round), which must aggregate *before* any
                # re-dispatch so the degenerate schedule matches the
                # synchronous rounds.
                aggregate()
                if version < self.n_rounds:
                    dispatch()
                continue
            if heap:
                t_arr, w, i = heapq.heappop(heap)
                t_now = t_arr
                info = waves[w]
                info["arrived"][i] = 1.0
                info["pending"] -= 1
                in_flight[i] = False
                ready_t[i] = t_arr + info["gaps"][i]
                buffered += 1
                if obs_events:
                    self._emit_event(obs_records_lib.EventRecord(
                        t=t_arr, kind="arrival", wave=w, client=int(i)))
                    self._emit_event(obs_records_lib.EventRecord(
                        t=t_arr, kind="buffer", value=float(buffered)))
                continue
            # Empty buffer, nothing in flight: dispatch, or advance the
            # clock to the next ready client, or churn until someone
            # rejoins.
            if dispatch():
                stalls = 0
                continue
            cand = ready_t[(joined > 0) & ~in_flight]
            if cand.size and cand.min() > t_now:
                t_now = float(cand.min())
                continue
            stalls += 1
            if (self.arrival_cfg is None
                    or self.arrival_cfg.p_rejoin <= 0 or stalls > 100_000):
                raise RuntimeError(
                    "buffered run stalled: no client can ever arrive "
                    f"(version {version}/{self.n_rounds})")

        self.params, self.aux, self._key = params, aux, key
        res.params = params
        res.wall_s = time.time() - t0  # lint: ignore[determinism]
        res.final_accuracy = res.accuracy[-1]
        self._finish_run(res)
        if self.trace is not None and self.trace.path is not None:
            self.trace.export()
        return res


def run_fl_buffered(cfg, transport_cfg, client_x, client_y, test_x, test_y,
                    n_rounds: int = 40, batch_per_round: int = 32,
                    seed: int = 0, eval_every: int = 2, timings=None,
                    scenario=None, adaptive_dispatch: str = "bucketed",
                    downlink=None, compression=None,
                    fused_aggregate: bool = False,
                    buffer_k: int | None = None,
                    staleness: str = "constant",
                    staleness_alpha: float = 0.5,
                    compute=None, arrival=None, ledger=None, trace=None,
                    phase_timers=None, sketches=None) -> engine_lib.FLResult:
    """Buffered (FedBuff-style) FedSGD over the simulated wireless uplink.

    The asynchronous counterpart of :func:`repro.fl.loop.run_fl` — same
    arguments plus the buffer size ``buffer_k`` (``None`` = cohort size),
    the ``staleness`` weighting (``constant``/``polynomial``/``inverse``
    with exponent ``staleness_alpha``), and optional
    ``compute``/``arrival`` event-layer overrides (defaulting to the
    scenario's fields). With ``buffer_k = None``, a degenerate compute
    model, and constant weights the result is bit-identical to ``run_fl``.
    ``ledger``/``trace``/``phase_timers`` attach observability sinks
    (:mod:`repro.obs`) without changing any numeric result.
    """
    algo = engine_lib.FedSGD(cfg, batch_per_round=batch_per_round)
    return AsyncRoundEngine(
        algo, transport_cfg, client_x, client_y, test_x, test_y,
        n_rounds=n_rounds, buffer_k=buffer_k, staleness=staleness,
        staleness_alpha=staleness_alpha, compute=compute, arrival=arrival,
        seed=seed, eval_every=eval_every, timings=timings, scenario=scenario,
        adaptive_dispatch=adaptive_dispatch, downlink=downlink,
        compression=compression, fused_aggregate=fused_aggregate,
        ledger=ledger, trace=trace, phase_timers=phase_timers,
        sketches=sketches,
    ).run()


def run_fedavg_buffered(cfg, transport_cfg, client_x, client_y, test_x,
                        test_y, n_rounds: int = 40, local_steps: int = 4,
                        batch_per_step: int = 32, scale_mode: str = "none",
                        seed: int = 0, eval_every: int = 2, timings=None,
                        scenario=None, adaptive_dispatch: str = "bucketed",
                        downlink=None, compression=None,
                        fused_aggregate: bool = False,
                        buffer_k: int | None = None,
                        staleness: str = "constant",
                        staleness_alpha: float = 0.5,
                        compute=None, arrival=None, ledger=None, trace=None,
                        phase_timers=None,
                        sketches=None) -> engine_lib.FLResult:
    """Buffered (FedBuff-style) FedAvg — the asynchronous counterpart of
    :func:`repro.fl.fedavg.run_fedavg`; see :func:`run_fl_buffered` for the
    buffering and observability arguments."""
    algo = engine_lib.FedAvg(cfg, local_steps=local_steps,
                             batch_per_step=batch_per_step,
                             scale_mode=scale_mode)
    return AsyncRoundEngine(
        algo, transport_cfg, client_x, client_y, test_x, test_y,
        n_rounds=n_rounds, buffer_k=buffer_k, staleness=staleness,
        staleness_alpha=staleness_alpha, compute=compute, arrival=arrival,
        seed=seed, eval_every=eval_every, timings=timings, scenario=scenario,
        adaptive_dispatch=adaptive_dispatch, downlink=downlink,
        compression=compression, fused_aggregate=fused_aggregate,
        ledger=ledger, trace=trace, phase_timers=phase_timers,
        sketches=sketches,
    ).run()
