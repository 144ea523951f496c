"""Unified FL round engine: one driver for every algorithm x dispatch x leg.

Before this module, ``fl/loop.py`` (FedSGD) and ``fl/fedavg.py`` (FedAvg)
each hand-wrote four round-step variants (driver-less, scenario+select,
scenario+bucketed, plus the jitted helper pieces) and duplicated the
driver/ECRT/airtime/eval plumbing — eight round functions to maintain, and
every new transport leg or algorithm would have doubled that again. The
engine splits the round into two orthogonal pieces:

* an :class:`Algorithm` strategy — *what* the clients compute and how the PS
  applies the aggregate. :class:`FedSGD` uploads one-step gradients and
  applies them through the SGD optimizer (paper eq. (4)-(6));
  :class:`FedAvg` uploads local-step weight deltas with optional per-client
  ``max_abs`` scaling and adds the mean delta to the global model.
* one :class:`RoundEngine` — *how* a round runs: scenario-driver resolution,
  adaptive-dispatch selection (bucketed/select), analytic-ECRT pricing,
  the optional noisy **downlink broadcast leg**, airtime accumulation, link
  telemetry, and the eval cadence. Every algorithm gets every axis for free.

``run_fl`` / ``run_fedavg`` keep their exact historical signatures as thin
wrappers and are **bit-identical** to the pre-engine loops for any
pre-existing configuration (``tests/test_engine_golden.py`` pins this
against a frozen snapshot): the fold_in key schedule, the jit boundaries,
and the op order of every round variant are preserved.

Downlink leg (beyond-paper; Qu et al., arXiv:2310.16652)
--------------------------------------------------------
``downlink=DownlinkConfig(...)`` (or a scenario whose ``downlink`` is set)
inserts a broadcast step at the top of each round: the global model rides
``transport.transmit_broadcast`` through every client's *downlink* channel
(error-free, or uncoded at an SNR offset from the uplink; per-client mode
via the scenario's policy table when ``adaptive=True``), and each client
computes its payload from its own corrupted copy. The broadcast reuses the
round's uplink base key on the downlink key lane
(``transport.DOWNLINK_KEY_LANE``), so uplink draws are unchanged — with
``downlink=None`` every result is bit-identical to the downlink-free loops.

Compressed uplinks (beyond-paper; Ma et al. 2404.11035, Amiri & Gündüz
1907.09769)
-----------------------------------------------------------------------
``compression=CompressionConfig(...)`` (or a scenario whose ``compression``
is set) replaces each round's dense uplink with the sparse wire
(:mod:`repro.compress`): every client accumulates an error-feedback
residual, selects ``k`` coordinates of ``residual + payload`` (top-k /
rand-k / threshold), and transmits the values through the configured
transport plus a protected index header. The EF residual is carried across
rounds per client inside the engine — dropped clients keep their whole
accumulation (they never transmitted) — and the selection/transport keys
derive from the same per-client fold_in keys as the dense engine, so every
dispatch (driver-less, select, bucketed) sees the same selection. Under a
scenario, ``PolicyConfig.compress_ratios`` makes the slot budget
CSI-adaptive per mode (bucketed dispatch only — ragged per-mode budgets
cannot live in one fused trace). ``compression=None`` leaves every code
path and every random draw bit-identical to the dense engine.
"""

from __future__ import annotations

import dataclasses
import functools
import math
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
from repro.fl import payload as payload_lib
from repro.obs import ledger as obs_ledger_lib
from repro.obs import metrics as obs_metrics_lib
from repro.obs import records as obs_records_lib
from repro.obs import timers as obs_timers_lib
from repro.optim.sgd import sgd as make_sgd

__all__ = [
    "FLResult",
    "FedSGD",
    "FedAvg",
    "RoundEngine",
    "resolve_scenario",
    "resolve_downlink",
    "resolve_compression",
    "cohort_mean",
    "device_shards",
    "sample_minibatches",
    "dropout_weighted_mean",
    "wave_clients",
    "record_link_round",
    "link_telemetry",
    "select_mode_cfgs",
    "resolve_ecrt_analytic",
]


@dataclasses.dataclass
class FLResult:
    """Outcome of one FL run (shared by every algorithm/loop)."""

    rounds: list
    accuracy: list
    airtime_s: list  # cumulative airtime: TDMA uplink sum (+ downlink leg)
    wall_s: float
    final_accuracy: float
    # Per-round link telemetry, as dicts — the historical view, preserved
    # bit-identically (same keys, insertion order, values) now that the
    # engines build typed records first. Scenario-driven runs append {round,
    # mean_snr_db, mean_est_db, mode_counts, n_active, n_stragglers,
    # airtime_s} (mode_counts indexes the driver's mode table); runs with a
    # downlink leg add {downlink_airtime_s, downlink_ber[, and for adaptive
    # downlinks downlink_mode_counts]}; compressed runs add
    # {comp_ratio (mean kept fraction), comp_bits_on_air (active clients'
    # on-air bits this round), comp_residual_norm (mean per-client L2 of
    # the EF residual)} — driver-less downlink/compressed runs append
    # records with just their own fields. [] otherwise.
    link: list = dataclasses.field(default_factory=list)
    # Typed per-round telemetry: one ``repro.obs.records.RoundRecord`` per
    # round (or per dispatched wave of the buffered engine), *including*
    # rounds with no link fields. ``link`` above is the dict view of the
    # records that have any (``rec.to_link_dict()``); the records carry
    # observability-only extras (uplink BER aggregates, event-clock times)
    # when a ledger is attached.
    records: list = dataclasses.field(default_factory=list)
    # Event-clock timestamps (seconds) of each eval point, parallel to
    # ``rounds``/``accuracy``. Only the buffered asynchronous engine
    # (``fl.async_engine``) fills this — the synchronous engine has no
    # event clock and leaves it empty, keeping its results bit-comparable
    # to pre-async runs.
    event_s: list = dataclasses.field(default_factory=list)
    # The global model after the last round (a params pytree).
    params: object = None


def resolve_scenario(scenario, transport_cfg):
    """``scenario=`` argument -> a bound ``ScenarioDriver`` (or ``None``).

    Accepts a registered scenario name, a ``Scenario``, or an already-built
    ``ScenarioDriver``; the single resolution rule under ``run_fl`` and
    ``run_fedavg``.
    """
    if scenario is None:
        return None
    from repro.link import scenario as scenario_lib

    if isinstance(scenario, scenario_lib.ScenarioDriver):
        return scenario
    if isinstance(scenario, str):
        scenario = scenario_lib.get_scenario(scenario)
    return scenario_lib.ScenarioDriver(scenario, transport_cfg)


def resolve_downlink(downlink, driver):
    """``downlink=`` argument -> the round's ``DownlinkConfig`` (or ``None``).

    An explicit argument wins; otherwise a scenario-driven run inherits the
    scenario's ``downlink`` field. ``None`` means the historical error-free
    downlink (no broadcast leg at all).
    """
    if downlink is not None:
        return downlink
    if driver is not None:
        return driver.scenario.downlink
    return None


def resolve_compression(compression, driver):
    """``compression=`` argument -> the run's ``CompressionConfig`` (or ``None``).

    An explicit argument wins; otherwise a scenario-driven run inherits the
    scenario's ``compression`` field. ``None`` means dense uplinks —
    bit-identical to the pre-compression engine.
    """
    if compression is not None:
        return compression
    if driver is not None:
        return driver.scenario.compression
    return None


def cohort_mean(tree):
    """Plain mean of ``(M, ...)`` leaves over the client axis: the layered
    aggregate of a driver-less round."""
    with jax.named_scope("fl_aggregate"):
        return jax.tree_util.tree_map(lambda g: jnp.mean(g, axis=0), tree)


def dropout_weighted_mean(tree, active):
    """Mean of ``(M, ...)`` leaves over active clients only.

    ``active`` is the 0/1 ``(M,)`` availability vector; an all-dropped round
    yields zeros (the global model simply does not move). Jit-safe — the
    shared aggregation rule of every scenario-driven round.
    """
    with jax.named_scope("fl_aggregate"):
        denom = jnp.maximum(jnp.sum(active), 1.0)
        return jax.tree_util.tree_map(
            lambda g: jnp.tensordot(active, g, axes=(0, 0)) / denom, tree)


def record_link_round(res: "FLResult", r: int, driver, stats, rnd,
                      timings) -> jax.Array:
    """Per-round scenario bookkeeping shared by the FL loops: price the
    round's per-client airtime and append the telemetry record. Returns the
    ``(M,)`` airtime vector."""
    air = driver.airtime(stats, rnd, timings)
    res.link.append(link_telemetry(r, rnd, air, len(driver.mode_cfgs)))
    return air


def link_telemetry(r: int, rnd, per_client_air, n_modes: int) -> dict:
    """One ``FLResult.link`` record from a round's ``LinkRound`` + airtime."""
    mode = np.asarray(rnd.mode)
    return {
        "round": r,
        "mean_snr_db": float(np.mean(np.asarray(rnd.snr_db))),
        "mean_est_db": float(np.mean(np.asarray(rnd.est_db))),
        "mode_counts": np.bincount(mode, minlength=n_modes).tolist(),
        "n_active": int(np.asarray(rnd.active).sum()),
        "n_stragglers": int(np.asarray(rnd.straggler).sum()),
        "airtime_s": float(np.asarray(per_client_air).sum()),
    }


def select_mode_cfgs(driver):
    """The driver's mode table, legal for the select dispatch.

    Delegates to ``transport.clear_kernel_rows`` (the one clearing rule):
    the fused select round cannot lower the Pallas grid. A select round is
    therefore *not* bit-comparable to a bucketed round of a kernel-enabled
    table — the jnp rows draw their own, equally valid, channel
    realization; within the select dispatch everything stays deterministic
    as usual.
    """
    return transport_lib.clear_kernel_rows(driver.mode_cfgs)


def resolve_ecrt_analytic(transport_cfg, num_clients: int):
    """Swap real-FEC ECRT for the calibrated analytic model in an FL loop.

    The real decoder inside a vmapped per-round loop would only re-measure a
    constant; calibrate instead — with the shared pricing sample budget
    (``latency.DEFAULT_CALIB_CODEWORDS``), so every entry point resolves
    the same channel to the same E[tx]. Heterogeneous cohorts get E[tx]
    interpolated per client over an SNR grid (``ecrt_expected_tx_profile``),
    with the cohort mean driving the transport constant and the per-client
    ratio returned as a ``(num_clients,)`` airtime scale (the analytic model
    is linear in E[tx]). Returns ``(transport_cfg, air_scale_or_None)``.
    """
    if not (transport_cfg.mode == "ecrt" and transport_cfg.simulate_fec):
        return transport_cfg, None
    snr_vec = np.asarray(transport_cfg.channel.snr_db, np.float32).reshape(-1)
    e_tx = latency_lib.ecrt_expected_tx_profile(
        snr_vec, transport_cfg.modulation,
        n_codewords=latency_lib.DEFAULT_CALIB_CODEWORDS,
        max_tx=latency_lib.DEFAULT_CALIB_MAX_TX)
    e_mean = float(e_tx.mean())
    transport_cfg = dataclasses.replace(
        transport_cfg, simulate_fec=False, ecrt_expected_tx=e_mean)
    air_scale = None
    if e_tx.size == num_clients and e_tx.size > 1:
        air_scale = jnp.asarray(e_tx / e_mean)
    return transport_cfg, air_scale


# ------------------------------------------------------------ client shards


def device_shards(client_x, client_y):
    """Put the client shards on the device in the layout the round's gather
    reads, in one ``jax.device_put``: ``(M, n, *S)`` images as ``(M·n,
    prod S)`` rows and ``(M, n)`` labels. Numpy and device inputs alike.

    On a TPU v5e a round's gather from the rows takes a twentieth of the
    time it takes from 4-D shards. The arrays stay uncommitted, as batches
    copied from the host are: committed ones would commit the round's
    outputs and compile the round program a second time."""
    M, n = client_y.shape
    return jax.device_put((client_x.reshape(M * n, -1), client_y))


@functools.partial(jax.jit, static_argnums=3)
def _gather_rows(rows, labels, take, image_shape):
    """Rows ``take`` ``(M, ...)`` of each client's ``n`` -> images
    ``take.shape + image_shape`` and labels ``take.shape``: exact copies of
    the shards' rows.

    The rows are gathered pixel-major, ``(D, K, M)``: a TPU lays the
    ``(M, K, *image_shape)`` result out with the clients minor, so the one
    transpose after the gather writes it in place."""
    M, n = labels.shape
    flat = take.reshape(M, -1) + n * jnp.arange(M, dtype=take.dtype)[:, None]
    xb = jnp.take(rows.T, flat.T, axis=1, mode="clip").T
    yb = jnp.take(labels.reshape(-1), flat, mode="clip")
    return xb.reshape(take.shape + image_shape), yb.reshape(take.shape)


def sample_minibatches(rng, client_x, client_y, shape, image_shape,
                       tm=obs_timers_lib.NULL_TIMERS):
    """One round's minibatches: rows drawn on the host, gathered on the
    device.

    ``rng.integers(0, n, shape)`` draws each client's rows (``shape`` is
    ``(M, ...)``), the same call and stream as a numpy gather, so a run
    takes the same rows in the same order. ``client_x`` is the ``(M·n, D)``
    device rows of :func:`device_shards`, or ``(M, n, *image_shape)``
    shards (numpy or device), which are put in that form first;
    ``client_y`` is ``(M, n)``. Returns device images ``shape +
    image_shape`` and labels ``shape``.

    ``tm`` (the engine's phase sink) times the host draw (``gather``) apart
    from ``h2d``: the copy of the int32 indices to the device (with the
    shards, where they were not there yet) and the enqueue of the gather,
    which runs on the device after the host returns."""
    with tm.scope("gather"):
        take = rng.integers(0, client_y.shape[1], shape)
    with tm.scope("h2d"):
        if client_x.ndim != 2:
            client_x, client_y = device_shards(client_x, client_y)
        return _gather_rows(client_x, client_y, take.astype(np.int32),
                            image_shape)


# -------------------------------------------------------------- cohort waves

# A fused round streams its cohort through the uplink in waves when the
# cohort's f32 payload would take more than this share of device memory.
# The share is assumed, not measured: a wave also holds the gradient's
# activations and the flat payload's copy, and one 568M-float client a wave
# peaks at 4.65 GB of a 16 GB v5e; no larger wave has been measured.
WAVE_MEMORY_SHARE = 1 / 8


def wave_clients(num_clients: int, payload_floats: int,
                 bytes_limit: int | None = None) -> int:
    """Clients per uplink wave of a fused round: the whole cohort when its
    f32 payload fits in ``WAVE_MEMORY_SHARE`` of the device's memory
    (``bytes_limit``, read from the device when not given; no limit known
    means one wave), else the largest divisor of the cohort that fits, and
    at least 1."""
    if bytes_limit is None:
        bytes_limit = (jax.devices()[0].memory_stats() or {}).get(
            "bytes_limit")
    if not bytes_limit:
        return num_clients
    budget = bytes_limit * WAVE_MEMORY_SHARE
    fits = [w for w in range(1, num_clients + 1)
            if num_clients % w == 0 and w * payload_floats * 4 <= budget]
    return max(fits, default=1)


# --------------------------------------------------------------- algorithms


class FedSGD:
    """The paper's algorithm: one gradient per client per round (eq. (4)-(6)).

    Payload = the stacked per-client single-step gradients; the PS applies
    the (dropout-weighted) mean through the SGD optimizer. ``cfg`` is the
    payload model (:mod:`repro.fl.payload`), or a CNN config for the
    paper's CNN.
    """

    name = "fedsgd"

    def __init__(self, cfg, batch_per_round: int = 32):
        self.cfg = cfg
        self.model = payload_lib.payload_model(cfg)
        self.batch_per_round = batch_per_round
        self.opt = make_sgd(self.model.lr)
        self.grad_fn = jax.grad(self.model.loss, has_aux=True)

    def init_params(self, key):
        """Global model at round 0."""
        return self.model.init(key)

    def init_opt(self, params):
        """Optimizer state threaded through the rounds."""
        return self.opt.init(params)

    def draw_shape(self, M: int) -> tuple:
        """Shape of one round's row draw: ``B`` rows per client."""
        return (M, self.batch_per_round)

    def sample(self, rng, client_x, client_y,
               tm=obs_timers_lib.NULL_TIMERS):
        """One round's per-client minibatches: ``(M, B, *sample_shape)``
        samples and ``(M, B)`` labels, on the device (see
        :func:`sample_minibatches`)."""
        return sample_minibatches(rng, client_x, client_y,
                                  self.draw_shape(client_y.shape[0]),
                                  self.model.sample_shape, tm)

    def payload_counted(self, params, xb, yb):
        """Per-client gradients of the shared global model (error-free
        downlink), leaves ``(M, ...)``, and the payload model's counters
        summed over the clients."""
        def client_grad(x, y):
            return self.grad_fn(params, x, y)

        with jax.named_scope("fl_grad"):
            grads, counters = jax.vmap(client_grad)(xb, yb)
            return grads, jax.tree_util.tree_map(
                lambda c: jnp.sum(c, axis=0), counters)

    def payload(self, params, xb, yb):
        """:meth:`payload_counted` without the counters."""
        return self.payload_counted(params, xb, yb)[0]

    def payload_from(self, recv_params, xb, yb):
        """Per-client gradients at each client's *received* model copy (the
        noisy-downlink variant of :meth:`payload`)."""
        with jax.named_scope("fl_grad"):
            return jax.vmap(self.grad_fn)(recv_params, xb, yb)[0]

    def wrap_uplink(self, payload, transmit):
        """FedSGD uploads raw gradients — no transport-side scaling."""
        return transmit(payload)

    def apply(self, params, opt_state, agg):
        """PS update (eq. (6)): one optimizer step on the aggregate."""
        with jax.named_scope("fl_apply"):
            return self.opt.update(agg, opt_state, params)


class FedAvg:
    """FedAvg over the approximate uplink (beyond-paper extension).

    Payload = the weight delta after ``local_steps`` local SGD steps;
    deltas stay bounded (|Δw| <= eta * sum|g|), so the same exponent-clamp
    receiver prior applies. ``scale_mode``:

      ``none``     transmit raw deltas (paper-style prior |Δ| < 2)
      ``max_abs``  scale by 1/max|Δ| before transmission and undo at the PS;
                   the scalar travels on the (error-free) control channel.
                   This concentrates values near the top of the representable
                   range where relative QAM error is smallest.
    """

    name = "fedavg"

    def __init__(self, cfg, local_steps: int = 4, batch_per_step: int = 32,
                 scale_mode: str = "none"):
        self.cfg = cfg
        self.model = payload_lib.payload_model(cfg)
        self.local_steps = local_steps
        self.batch_per_step = batch_per_step
        self.scale_mode = scale_mode
        self.grad_fn = jax.grad(self.model.loss, has_aux=True)
        # jitted so the host-driven bucketed round doesn't run the scale math
        # op-by-op; inside a fused round's trace they simply inline.
        self._compute_scale = jax.jit(self._scale_of)
        self._div_scale = jax.jit(self._div)
        self._mul_scale = jax.jit(self._mul)

    def init_params(self, key):
        """Global model at round 0."""
        return self.model.init(key)

    def init_opt(self, params):
        """FedAvg applies deltas directly — no optimizer state."""
        return None

    def draw_shape(self, M: int) -> tuple:
        """Shape of one round's row draw: ``L`` steps of ``B`` rows."""
        return (M, self.local_steps, self.batch_per_step)

    def sample(self, rng, client_x, client_y,
               tm=obs_timers_lib.NULL_TIMERS):
        """One round's batches: ``(M, local_steps, B, *sample_shape)``
        samples and ``(M, local_steps, B)`` labels, as in
        :meth:`FedSGD.sample`."""
        return sample_minibatches(rng, client_x, client_y,
                                  self.draw_shape(client_y.shape[0]),
                                  self.model.sample_shape, tm)

    def _local_delta(self, start, x, y):
        """One client's weight delta after ``local_steps`` SGD steps from
        ``start`` (its received copy of the global model)."""
        def body(p, inp):
            xi, yi = inp
            g, _ = self.grad_fn(p, xi, yi)
            p = jax.tree_util.tree_map(lambda a, b: a - self.model.lr * b, p, g)
            return p, None

        local, _ = jax.lax.scan(body, start, (x, y))
        return jax.tree_util.tree_map(lambda a, b: a - b, local, start)

    def payload(self, params, xb, yb):
        """Per-client local-step deltas from the shared global model."""
        with jax.named_scope("fl_grad"):
            return jax.vmap(
                lambda x, y: self._local_delta(params, x, y))(xb, yb)

    def payload_from(self, recv_params, xb, yb):
        """Per-client deltas, each relative to that client's *received*
        model copy — the PS still adds the mean delta to the true model."""
        with jax.named_scope("fl_grad"):
            return jax.vmap(self._local_delta)(recv_params, xb, yb)

    @staticmethod
    def _expand(s, like):
        return s.reshape((s.shape[0],) + (1,) * (like.ndim - 1))

    def _scale_of(self, deltas):
        leaves = jax.tree_util.tree_leaves(deltas)
        M = leaves[0].shape[0]
        flat = jnp.concatenate([l.reshape(M, -1) for l in leaves], axis=1)
        return jnp.maximum(jnp.max(jnp.abs(flat), axis=1), 1e-8) / 0.9

    def _div(self, deltas, scale):
        return jax.tree_util.tree_map(
            lambda l: l / self._expand(scale, l), deltas)

    def _mul(self, deltas, scale):
        return jax.tree_util.tree_map(
            lambda l: l * self._expand(scale, l), deltas)

    def wrap_uplink(self, deltas, transmit):
        """Per-client adaptive scale (``scale_mode == "max_abs"``): one
        scalar per client travels on the (error-free) control channel; the
        cohort then rides the batched uplink unchanged."""
        if self.scale_mode != "max_abs":
            return transmit(deltas)
        with jax.named_scope("fl_uplink"):
            scale = self._compute_scale(deltas)
            out, stats = transmit(self._div_scale(deltas, scale))
            return self._mul_scale(out, scale), stats

    def apply(self, params, aux, agg):
        """PS update: add the aggregated delta to the global model."""
        with jax.named_scope("fl_apply"):
            return jax.tree_util.tree_map(
                lambda p, d: p + d, params, agg), aux


# -------------------------------------------------------------- round engine


class RoundEngine:
    """One composable FL round driver for any :class:`Algorithm`.

    Owns everything the old per-algorithm loops duplicated: scenario-driver
    resolution, dispatch selection, analytic-ECRT pricing, the downlink
    broadcast leg, per-round airtime accumulation, link telemetry, and the
    eval cadence. Three round variants cover every configuration:

    * **driver-less** — one fused jitted round: [broadcast ->] payload ->
      single-mode batched uplink -> mean -> apply.
    * **scenario + select** — one fused jitted round: link pipeline ->
      [broadcast ->] payload -> vmapped-switch uplink -> dropout-weighted
      aggregate -> apply.
    * **scenario + bucketed** — jitted link/payload/apply steps around
      host-driven mode-bucketed transports (each mode runs once on its own
      client bucket; Pallas kernel rows allowed) — the mode vector syncs to
      the host once per round.

    The key schedule is the pre-engine one, exactly: ``key -> params`` split,
    an optional driver-init split, one split per round, and inside a
    scenario round ``k_link, k_tx = split(round_key)``. The downlink leg
    rides the *same* round/uplink key on the downlink fold_in lane, so
    enabling it consumes no extra splits and ``downlink=None`` runs are
    bit-identical to the pre-engine loops.
    """

    def __init__(self, algorithm, transport_cfg, client_x, client_y,
                 test_x, test_y, *, n_rounds: int, seed: int = 0,
                 eval_every: int = 2,
                 timings: latency_lib.PhyTimings | None = None,
                 scenario=None, adaptive_dispatch: str = "bucketed",
                 downlink=None, compression=None, fused_aggregate: bool = False,
                 ledger=None, phase_timers=None, sketches=None):
        self.algo = algorithm
        # The shards stay on the device for the whole run; each round's
        # ``sample`` sends only its drawn int32 row indices,
        # ``sample_h2d_bytes``.
        self.client_rows, self.client_y = device_shards(client_x, client_y)
        self._image_shape = tuple(client_x.shape[2:])
        self.test_x, self.test_y = test_x, test_y
        self.n_rounds = n_rounds
        self.seed = seed
        self.eval_every = eval_every
        self.timings = timings or latency_lib.PhyTimings()
        self.num_clients = client_x.shape[0]
        self.sample_h2d_bytes = 4 * math.prod(
            algorithm.draw_shape(self.num_clients))
        # Observability sinks (repro.obs). Pure observers: they only read
        # values the round already produced, so attaching them changes no
        # numeric result. ``ledger`` accepts a path or a RunLedger;
        # ``phase_timers`` accepts a PhaseTimers (None = shared no-op).
        self.ledger = obs_ledger_lib.as_ledger(ledger)
        self.phase_timers = obs_timers_lib.resolve_timers(phase_timers)

        key = jax.random.PRNGKey(seed)
        key, pk = jax.random.split(key)
        self.params = algorithm.init_params(pk)
        self.aux = algorithm.init_opt(self.params)
        self.payload_floats = sum(
            l.size for l in jax.tree_util.tree_leaves(self.params))
        self.driver = resolve_scenario(scenario, transport_cfg)
        if adaptive_dispatch not in ("bucketed", "select"):
            raise ValueError(
                f"adaptive_dispatch must be bucketed|select, got "
                f"{adaptive_dispatch!r}")
        self.dispatch = adaptive_dispatch
        # Per-client distribution sketches (repro.obs.metrics): like the
        # ledger, a pure observer — the sketcher only reads arrays the
        # round step already produced plus a reserved fold_in lane of the
        # round key, so sketches-on runs stay bit-identical to
        # sketches-off runs on weights and accuracy.
        self.sketcher = obs_metrics_lib.resolve_sketches(
            sketches, self.num_clients)
        if self.sketcher is not None and self.driver is None:
            raise ValueError(
                "sketches= needs a scenario — the per-client SNR/mode "
                "distributions being sketched come from the link driver")

        # Kept pre-resolution: the downlink leg re-derives its own transport
        # from this (its ECRT pricing anchors at the *shifted* SNR, not the
        # uplink's — see _downlink_transport_cfg).
        self._raw_transport_cfg = transport_cfg
        self.ecrt_air_scale = None
        if self.driver is None:
            transport_cfg, self.ecrt_air_scale = resolve_ecrt_analytic(
                transport_cfg, self.num_clients)
        self.transport_cfg = transport_cfg
        self.downlink = resolve_downlink(downlink, self.driver)
        if (self.downlink is not None and self.downlink.adaptive
                and self.driver is None):
            raise ValueError(
                "DownlinkConfig(adaptive=True) needs a scenario — the "
                "per-client downlink mode comes from the scenario's policy "
                "table; driver-less runs use a single broadcast mode")
        self.dl_air_scale = None
        self.dl_cfg = (None if self.downlink is None
                       else self._downlink_transport_cfg())

        self.compression = resolve_compression(compression, self.driver)
        self._ef_residual = None
        self._comp_ks = None
        self._comp_dim = self._comp_k = 0
        if self.compression is not None:
            comp = self.compression
            self._comp_dim = self.payload_floats
            self._comp_k = sparsify_lib.resolve_k(comp, self._comp_dim)
            if self.driver is not None:
                from repro.link import policy as policy_lib

                pol = self.driver.scenario.policy
                if comp.k is not None:
                    # An explicit absolute budget wins everywhere
                    # (resolve_k's rule): the policy's ratio column applies
                    # only to ratio-derived budgets, so bucketed and select
                    # dispatches agree on the slots per client.
                    self._comp_ks = (self._comp_k,) * len(pol.modes)
                else:
                    if (pol.compress_ratios is not None
                            and self.dispatch != "bucketed"):
                        raise ValueError(
                            "PolicyConfig.compress_ratios (per-mode slot "
                            "budgets) needs adaptive_dispatch='bucketed' — "
                            "a fused select round cannot trace ragged "
                            "per-mode selections")
                    self._comp_ks = policy_lib.compress_k_table(
                        pol, self._comp_dim, comp.ratio)
            # The EF residual is carried even with error_feedback=False (as
            # zeros) so the jitted round signatures stay uniform.
            self._ef_residual = jnp.zeros(
                (self.num_clients, self._comp_dim), jnp.float32)

        # Fused-aggregate fast path: the uplink's weighted sum folds into
        # the transport (in-kernel accumulator on use_kernel rows, scan
        # fallback elsewhere) — per-client demapped payloads never land in
        # HBM. The fused round is pinned bit-identical to the layered
        # fedsgd_aggregate-over-transmit_batch composition, so anything
        # that must touch per-client rows *between* demap and aggregate is
        # incompatible and rejected here rather than silently layered.
        self.fused_aggregate = bool(fused_aggregate)
        if self.fused_aggregate:
            if self.compression is not None:
                raise ValueError(
                    "fused_aggregate=True is incompatible with a compressed "
                    "uplink: the sparse path must scatter per-client "
                    "coordinates before aggregating")
            if getattr(algorithm, "scale_mode", "none") == "max_abs":
                raise ValueError(
                    "fused_aggregate=True is incompatible with "
                    "scale_mode='max_abs': the per-client descale runs "
                    "between demap and aggregate")
            if self.driver is not None and self.dispatch != "bucketed":
                raise ValueError(
                    "fused_aggregate=True needs adaptive_dispatch="
                    "'bucketed' for scenario runs — the select lowering "
                    "has no kernel rows to fuse into")

        # A fused driver-less round without a downlink leg streams a cohort
        # too large for the device in waves (``wave_clients``): the uplink
        # launches per round, ``uplink_waves``.
        self.wave = self.num_clients
        if (self.fused_aggregate and self.driver is None
                and self.downlink is None):
            self.wave = wave_clients(self.num_clients, self.payload_floats)
        self.uplink_waves = self.num_clients // self.wave

        self._build_round_fns()
        if self.driver is not None:
            key, lk = jax.random.split(key)
            self.lstate, self.prev_mode, self.prev_est = self.driver.init(
                lk, self.num_clients)
        self._key = key

    @property
    def client_x(self):
        """The images as given, ``(M, n, *S)``: a device reshape of
        ``client_rows`` made on each read, for readers. The rounds gather
        from ``client_rows``."""
        return self.client_rows.reshape(self.client_y.shape
                                        + self._image_shape)

    # ----------------------------------------------------------- downlink

    def _downlink_transport_cfg(self):
        """The broadcast ``TransportConfig``: the *raw* uplink config with
        the downlink's mode/modulation and (driver-less) shifted channel SNR.

        Derived from the pre-resolution uplink config, then put through its
        own analytic-ECRT resolution, because an ECRT downlink must not (a)
        trace the real LDPC decoder inside the jitted round, nor (b) reuse
        an E[tx] calibrated at the uplink's unshifted SNR — the analytic
        model is SNR-blind, so the constant must be calibrated where the
        *downlink* operates. Driver-less: the shift is baked into the
        channel (shape preserved — per-client SNR vectors shift elementwise)
        and ``resolve_ecrt_analytic`` runs on the shifted config, yielding a
        per-client downlink airtime scale for heterogeneous cohorts.
        Scenario rounds override SNR per round (``rnd.snr_db + Δ``), so the
        config keeps the base channel and an ECRT downlink calibrates at the
        scenario's fleet operating point + Δ.
        """
        dl = self.downlink
        cfg = dataclasses.replace(
            self._raw_transport_cfg, mode=dl.mode,
            modulation=dl.modulation or self._raw_transport_cfg.modulation)
        if self.driver is not None:
            if cfg.mode == "ecrt" and cfg.simulate_fec:
                anchor = float(self.driver.scenario.dynamics.mean_snr_db
                               + dl.snr_offset_db)
                e_tx = latency_lib.calibrate_ecrt(
                    anchor, cfg.modulation,
                    n_codewords=latency_lib.DEFAULT_CALIB_CODEWORDS,
                    max_tx=latency_lib.DEFAULT_CALIB_MAX_TX)
                cfg = dataclasses.replace(
                    cfg, simulate_fec=False, ecrt_expected_tx=float(e_tx))
            return cfg
        ch = cfg.channel
        snr = np.asarray(ch.snr_db, np.float32) + np.float32(dl.snr_offset_db)
        snr_val = (float(snr) if snr.ndim == 0
                   else tuple(float(v) for v in snr.reshape(-1)))
        cfg = dataclasses.replace(
            cfg, channel=dataclasses.replace(ch, snr_db=snr_val))
        cfg, self.dl_air_scale = resolve_ecrt_analytic(cfg, self.num_clients)
        return cfg

    def _downlink_modes(self, est_db):
        """Adaptive downlink: per-client mode from the scenario's policy
        table at the shifted CSI (jit-safe; bucketed rounds pass host CSI)."""
        from repro.link import policy as policy_lib

        return policy_lib.downlink_mode(
            est_db, self.driver.scenario.policy, self.downlink.snr_offset_db)

    def _broadcast_scenario(self, params, k_tx, rnd, dl_mode=None,
                            dispatch="select"):
        """One scenario round's broadcast leg: global model -> per-client
        received copies at the shifted per-round SNR."""
        dl_snr = rnd.snr_db + self.downlink.snr_offset_db
        if self.downlink.adaptive:
            cfgs = (self.driver.mode_cfgs if dispatch == "bucketed"
                    else select_mode_cfgs(self.driver))
            mode = dl_mode if dl_mode is not None else self._downlink_modes(
                rnd.est_db)
            return transport_lib.transmit_pytree_broadcast_adaptive(
                params, k_tx, cfgs, mode, snr_db=dl_snr, dispatch=dispatch)
        return transport_lib.transmit_pytree_broadcast(
            params, k_tx, self.dl_cfg, self.num_clients, snr_db=dl_snr)

    def _downlink_air_record(self, rec, dstats):
        """Price the round's broadcast and set its fields on ``rec`` (the
        round's :class:`~repro.obs.records.RoundRecord`).

        Returns the seconds the PS spent broadcasting (each distinct mode is
        transmitted once — see ``latency.broadcast_airtime``).
        """
        dl = self.downlink
        if self.driver is not None and dl.adaptive:
            air = latency_lib.round_airtime_adaptive(
                dstats, self.timings, self.driver.mode_cfgs)
            total = latency_lib.broadcast_airtime(air, dstats.mode_idx)
        else:
            air = latency_lib.round_airtime(dstats, self.timings, dl.mode)
            if self.dl_air_scale is not None:
                # Heterogeneous analytic-ECRT downlink: per-client E[tx]
                # rescale, as on the uplink.
                air = air * self.dl_air_scale
            total = latency_lib.broadcast_airtime(air)
        rec.downlink_airtime_s = total
        rec.downlink_ber = float(np.mean(np.asarray(dstats.ber)))
        if dstats.mode_idx is not None:
            rec.downlink_mode_counts = np.bincount(
                np.asarray(dstats.mode_idx),
                minlength=len(self.driver.mode_cfgs)).tolist()
        return total

    # -------------------------------------------------------- round builds

    def _build_round_fns(self):
        algo, tcfg, driver = self.algo, self.transport_cfg, self.driver
        dl, M = self.downlink, self.num_clients
        comp, D, kbase = self.compression, self._comp_dim, self._comp_k

        @jax.jit
        def round_step(params, aux, xb, yb, key):
            # Driver-less round, one fused program. The downlink broadcast
            # (when configured) and the uplink share `key` on disjoint
            # fold_in lanes.
            dstats = None
            if dl is None:
                payload = algo.payload(params, xb, yb)
            else:
                recv, dstats = transport_lib.transmit_pytree_broadcast(
                    params, key, self.dl_cfg, M)
                payload = algo.payload_from(recv, xb, yb)
            hat, stats = algo.wrap_uplink(
                payload,
                lambda t: transport_lib.transmit_pytree_batch(t, key, tcfg))
            agg = cohort_mean(hat)
            params, aux = algo.apply(params, aux, agg)
            return params, aux, stats, dstats, {}

        self._round_step = round_step

        if self.fused_aggregate:
            # Uniform cohort weights, normalized once at build time (every
            # round reuses the same device constant, so all rounds share one
            # weight realization with the layered fedsgd_aggregate_batch
            # twin). Donation of the payload buffer happens inside the jit
            # boundary here (a single fused program — XLA already reuses
            # the buffer; the flag matters at the bucketed host-level
            # launches).
            uniform_w = aggregation_lib.normalize_weights(
                jnp.ones((M,), jnp.float32))

            @jax.jit
            def round_step_fused(params, aux, xb, yb, key):
                # Driver-less fused round: modulate -> channel -> demap ->
                # accumulate in one transport pass; no per-client hat tree.
                dstats, counters = None, {}
                if dl is None:
                    agg, stats, counters = self._fused_uplink(
                        params, xb, yb, key, uniform_w)
                else:
                    recv, dstats = transport_lib.transmit_pytree_broadcast(
                        params, key, self.dl_cfg, M)
                    payload = algo.payload_from(recv, xb, yb)
                    agg, stats = \
                        transport_lib.transmit_pytree_batch_aggregate(
                            payload, key, tcfg, uniform_w, donate=True)
                if stats.exact_tiles is not None:
                    # The kernel's tiles redone on its exact path.
                    counters = dict(counters, uplink_exact_tiles=jnp.sum(
                        stats.exact_tiles))
                params, aux = algo.apply(params, aux, agg)
                return params, aux, stats, dstats, counters

            self._round_step = round_step_fused

        def _sel_keys(key):
            # rand-k selection keys ride the per-client transport key on the
            # reserved lane; deterministic methods need none.
            if comp.method != "randk":
                return None
            return sparsify_lib.selection_keys(key, M)

        if comp is not None:

            @jax.jit
            def round_step_comp(params, aux, xb, yb, key, residual):
                # Driver-less *compressed* round, one fused program: EF
                # accumulate -> select -> sparse uplink -> scatter -> mean.
                dstats = None
                if dl is None:
                    payload = algo.payload(params, xb, yb)
                else:
                    recv, dstats = transport_lib.transmit_pytree_broadcast(
                        params, key, self.dl_cfg, M)
                    payload = algo.payload_from(recv, xb, yb)
                with jax.named_scope("fl_uplink"):
                    flat, spec = transport_lib._flatten_client_tree(payload)
                    vals, idx, residual = sparsify_lib.ef_select_batch(
                        residual, flat, kbase, comp, _sel_keys(key))
                    hat_flat, stats = algo.wrap_uplink(
                        vals,
                        lambda v: framing_lib.transmit_sparse_batch(
                            v, idx, D, key, tcfg, comp))
                    hat = transport_lib._unflatten_client_tree(hat_flat, spec)
                agg = cohort_mean(hat)
                params, aux = algo.apply(params, aux, agg)
                return params, aux, stats, dstats, residual

            self._round_step_comp = round_step_comp

        @jax.jit
        def eval_acc(params):
            with jax.named_scope("fl_eval"):
                return algo.model.evaluate(params, jnp.asarray(self.test_x),
                                           jnp.asarray(self.test_y))

        self._eval_acc = eval_acc

        if driver is None:
            return

        @jax.jit
        def round_step_link(params, aux, xb, yb, key, lstate, prev_mode,
                            prev_est):
            # Select dispatch: one fused program — dynamics -> noisy CSI ->
            # mode policy -> [broadcast ->] payload -> vmapped-switch uplink
            # -> dropout-weighted aggregation -> apply.
            k_link, k_tx = jax.random.split(key)
            lstate, rnd = driver.round(lstate, prev_mode, prev_est, k_link)
            dstats = None
            if dl is None:
                payload = algo.payload(params, xb, yb)
            else:
                recv, dstats = self._broadcast_scenario(params, k_tx, rnd)
                payload = algo.payload_from(recv, xb, yb)
            hat, stats = algo.wrap_uplink(
                payload,
                lambda t: transport_lib.transmit_pytree_batch_adaptive(
                    t, k_tx, select_mode_cfgs(driver), rnd.mode,
                    snr_db=rnd.snr_db, dispatch="select"))
            agg = dropout_weighted_mean(hat, rnd.active)
            params, aux = algo.apply(params, aux, agg)
            return params, aux, stats, lstate, rnd, dstats

        self._round_step_link = round_step_link

        if comp is not None:

            @jax.jit
            def round_step_link_comp(params, aux, xb, yb, key, lstate,
                                     prev_mode, prev_est, residual):
                # Select dispatch, compressed: one fused program — link
                # pipeline -> [broadcast ->] payload -> EF select -> sparse
                # vmapped-switch uplink -> dropout-weighted aggregate.
                # Uniform slot budget (per-mode budgets are bucketed-only).
                k_link, k_tx = jax.random.split(key)
                lstate, rnd = driver.round(lstate, prev_mode, prev_est,
                                           k_link)
                dstats = None
                if dl is None:
                    payload = algo.payload(params, xb, yb)
                else:
                    recv, dstats = self._broadcast_scenario(params, k_tx, rnd)
                    payload = algo.payload_from(recv, xb, yb)
                with jax.named_scope("fl_uplink"):
                    flat, spec = transport_lib._flatten_client_tree(payload)
                    vals, idx, residual = sparsify_lib.ef_select_batch(
                        residual, flat, kbase, comp, _sel_keys(k_tx),
                        active=rnd.active)
                    hat_flat, stats = algo.wrap_uplink(
                        vals,
                        lambda v: framing_lib.transmit_sparse_batch_adaptive(
                            v, idx, D, k_tx, select_mode_cfgs(driver),
                            rnd.mode, comp, snr_db=rnd.snr_db,
                            dispatch="select"))
                    hat = transport_lib._unflatten_client_tree(hat_flat, spec)
                agg = dropout_weighted_mean(hat, rnd.active)
                params, aux = algo.apply(params, aux, agg)
                return params, aux, stats, lstate, rnd, dstats, residual

            self._round_step_link_comp = round_step_link_comp

        @jax.jit
        def link_round(lstate, prev_mode, prev_est, key):
            return driver.round(lstate, prev_mode, prev_est, key)

        @jax.jit
        def payload_shared(params, xb, yb):
            return algo.payload(params, xb, yb)

        @jax.jit
        def payload_per_client(recv, xb, yb):
            return algo.payload_from(recv, xb, yb)

        @jax.jit
        def apply_update(params, aux, hat, active):
            agg = dropout_weighted_mean(hat, active)
            return algo.apply(params, aux, agg)

        def round_step_link_bucketed(params, aux, xb, yb, key, lstate,
                                     prev_mode, prev_est):
            # Bucketed dispatch: the link step runs first and the mode
            # vector syncs to the host, so each transport leg can sort
            # clients into per-mode buckets and run each mode once (O(M)
            # work, kernel rows allowed) around the jitted compute steps.
            k_link, k_tx = jax.random.split(key)
            lstate, rnd = link_round(lstate, prev_mode, prev_est, k_link)
            with self.phase_timers.scope("sync"):
                mode_np = np.asarray(rnd.mode)
            dstats = None
            if dl is None:
                payload = payload_shared(params, xb, yb)
            else:
                dl_mode = None
                if dl.adaptive:
                    with self.phase_timers.scope("sync"):
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
            params, aux = apply_update(params, aux, hat, rnd.active)
            return params, aux, stats, lstate, rnd, dstats

        self._round_step_link_bucketed = round_step_link_bucketed

        if self.fused_aggregate:
            # Dropout-as-weights: dropped clients still transmit in their
            # bucket (exactly as the layered bucketed round) but fold into
            # the accumulator with weight 0; the normalization is global
            # (before the bucket split), matching fedsgd_aggregate_batch
            # over the cohort's active mask.
            fused_weights = jax.jit(
                lambda active: aggregation_lib.normalize_weights(active))
            apply_agg = jax.jit(
                lambda params, aux, agg: algo.apply(params, aux, agg))

            def round_step_link_bucketed_fused(params, aux, xb, yb, key,
                                               lstate, prev_mode, prev_est):
                # Bucketed fused round: link step syncs the mode vector to
                # the host, each mode bucket runs uplink+aggregate in one
                # pass (kernel accumulator on use_kernel rows), partials add
                # in mode order, and only the apply tail is jitted.
                k_link, k_tx = jax.random.split(key)
                lstate, rnd = link_round(lstate, prev_mode, prev_est, k_link)
                with self.phase_timers.scope("sync"):
                    mode_np = np.asarray(rnd.mode)
                dstats = None
                if dl is None:
                    payload = payload_shared(params, xb, yb)
                else:
                    dl_mode = None
                    if dl.adaptive:
                        with self.phase_timers.scope("sync"):
                            dl_mode = np.asarray(self._downlink_modes(
                                np.asarray(rnd.est_db)))
                    recv, dstats = self._broadcast_scenario(
                        params, k_tx, rnd, dl_mode=dl_mode,
                        dispatch="bucketed")
                    payload = payload_per_client(recv, xb, yb)
                agg, stats = \
                    transport_lib.transmit_pytree_batch_adaptive_aggregate(
                        payload, k_tx, driver.mode_cfgs, mode_np,
                        fused_weights(rnd.active), snr_db=rnd.snr_db,
                        donate=True)
                params, aux = apply_agg(params, aux, agg)
                return params, aux, stats, lstate, rnd, dstats

            self._round_step_link_bucketed = round_step_link_bucketed_fused

        if comp is None:
            return

        if comp.error_feedback:
            accumulate = jax.jit(lambda r, f: r + f)
            residual_update = jax.jit(
                lambda acc, sent, act: acc - sent * act[:, None])
        else:
            accumulate = jax.jit(lambda r, f: f)
            residual_update = jax.jit(
                lambda acc, sent, act: jnp.zeros_like(acc))

        def round_step_link_bucketed_comp(params, aux, xb, yb, key, lstate,
                                          prev_mode, prev_est, residual):
            # Bucketed dispatch, compressed: the mode vector syncs to the
            # host so each mode bucket selects with its *own* slot budget
            # (the CSI-adaptive compress_ratios column) and runs its sparse
            # batch once, around the jitted compute steps.
            k_link, k_tx = jax.random.split(key)
            lstate, rnd = link_round(lstate, prev_mode, prev_est, k_link)
            with self.phase_timers.scope("sync"):
                mode_np = np.asarray(rnd.mode)
            dstats = None
            if dl is None:
                payload = payload_shared(params, xb, yb)
            else:
                dl_mode = None
                if dl.adaptive:
                    with self.phase_timers.scope("sync"):
                        dl_mode = np.asarray(self._downlink_modes(
                            np.asarray(rnd.est_db)))
                recv, dstats = self._broadcast_scenario(
                    params, k_tx, rnd, dl_mode=dl_mode, dispatch="bucketed")
                payload = payload_per_client(recv, xb, yb)
            with jax.named_scope("fl_uplink"):
                flat, spec = transport_lib._flatten_client_tree(payload)
                acc = accumulate(residual, flat)
                dense_hat, stats, sent = self._sparse_bucketed_uplink(
                    acc, k_tx, mode_np, rnd.snr_db)
                residual = residual_update(acc, sent, rnd.active)
                hat = transport_lib._unflatten_client_tree(dense_hat, spec)
            params, aux = apply_update(params, aux, hat, rnd.active)
            return params, aux, stats, lstate, rnd, dstats, residual

        self._round_step_link_bucketed_comp = round_step_link_bucketed_comp

    def _fused_uplink(self, params, xb, yb, key, weights):
        """Payload, uplink and aggregate of a fused driver-less round:
        ``(agg tree, stats, counters)``.

        With one wave the cohort's payload goes through one kernel launch.
        Otherwise a ``lax.scan`` over waves of ``self.wave`` clients
        computes each wave's payload, flattens it, and folds it into the
        running aggregate; wave ``i``'s clients keep the keys of clients
        ``i * wave ..`` of one launch and the sum runs in client order, so
        the same payload gives the one-launch sum bit for bit (a gradient
        batched over fewer clients may itself round differently)."""
        algo, tcfg, W = self.algo, self.transport_cfg, self.wave
        M = xb.shape[0]
        if W >= M:
            payload, counters = algo.payload_counted(params, xb, yb)
            agg, stats = transport_lib.transmit_pytree_batch_aggregate(
                payload, key, tcfg, weights, donate=True)
            return agg, stats, counters

        def waves(a):
            return a.reshape((M // W, W) + a.shape[1:])

        shapes = jax.eval_shape(algo.payload, params, xb[:W], yb[:W])
        leaves, treedef = jax.tree_util.tree_flatten(shapes)
        spec = (leaves, treedef, [l.size // W for l in leaves])

        def wave(acc, inp):
            i, x, y, w = inp
            payload, counters = algo.payload_counted(params, x, y)
            flat, _ = transport_lib._flatten_client_tree(payload)
            acc, stats = transport_lib.transmit_batch_aggregate(
                flat, key, tcfg, w, client_offset=i * W, donate=True,
                acc=acc)
            return acc, (stats, counters)

        acc0 = jnp.zeros((transport_lib.aggregate_words(
            self.payload_floats, tcfg),), jnp.float32)
        with jax.named_scope("fl_uplink"):
            acc, (stats, counters) = jax.lax.scan(
                wave, acc0,
                (jnp.arange(M // W), waves(xb), waves(yb), waves(weights)))
            stats = jax.tree_util.tree_map(
                lambda a: a.reshape((M,) + a.shape[2:]), stats)
            counters = jax.tree_util.tree_map(
                lambda c: jnp.sum(c, axis=0), counters)
            return (transport_lib._unflatten_aggregate_tree(acc, spec),
                    stats, counters)

    def _sparse_bucketed_uplink(self, acc, key, mode_np, snr_db):
        """Per-mode-budget sparse uplink over host-side mode buckets.

        The compressed counterpart of the bucketed dispatch: clients are
        stable-argsorted by mode; each mode's bucket selects ``k_m``
        coordinates of its accumulated payload (``k_m`` from the policy's
        ``compress_ratios`` column), rides the algorithm's uplink wrapper
        (per-client ``max_abs`` scaling composes per bucket), and transmits
        through its own mode config; results scatter back to client order.
        Keys ride the *client index*, so each row is bit-identical to a
        per-client ``transmit_sparse`` call. Returns ``(dense_hat (M, D),
        stats, sent (M, D))`` — ``sent`` is the transmitter-side scatter
        of the selected values, the quantity error feedback subtracts.
        """
        comp, algo, driver = self.compression, self.algo, self.driver
        cfgs, ks = driver.mode_cfgs, self._comp_ks
        M, D = acc.shape
        if M == 0:
            empty = jnp.zeros((0,), jnp.float32)
            stats = transport_lib.TxStats(
                empty, empty, empty, empty,
                mode_idx=jnp.zeros((0,), jnp.int32), bits_on_air=empty)
            return acc, stats, acc
        snr_vec = transport_lib._resolve_batch_snr(cfgs[0], M, snr_db)
        keys = transport_lib.client_keys(key, M)
        order = np.argsort(mode_np, kind="stable")
        counts = np.bincount(mode_np, minlength=len(cfgs))
        starts = np.concatenate([[0], np.cumsum(counts)])
        parts_x, parts_sent, parts_st = [], [], []
        for m, cfg in enumerate(cfgs):
            count = int(counts[m])
            if count == 0:
                continue
            rows = jnp.asarray(order[starts[m]: starts[m] + count])
            xb = jnp.take(acc, rows, axis=0)
            kb = jnp.take(keys, rows, axis=0)
            sb = None if snr_vec is None else jnp.take(snr_vec, rows)
            sel = None
            if comp.method == "randk":
                sel = jax.vmap(lambda kk: jax.random.fold_in(
                    kk, keylanes.SELECT_KEY_LANE))(kb)
            vals, sidx = sparsify_lib.select_batch(xb, ks[m], comp, sel)
            parts_sent.append(sparsify_lib.scatter_dense_batch(vals, sidx, D))
            fn = framing_lib._sparse_fn(cfg, comp, D, sb is not None)
            hat_m, st_m = algo.wrap_uplink(
                vals,
                lambda v, sidx=sidx, kb=kb, sb=sb, fn=fn: (
                    fn(v, sidx, kb) if sb is None else fn(v, sidx, kb, sb)))
            parts_x.append(hat_m)
            parts_st.append(st_m)
        dense_hat, stats, inv = transport_lib._scatter_bucket_parts(
            parts_x, parts_st, order, M)
        sent = jnp.take(jnp.concatenate(parts_sent, axis=0), inv, axis=0)
        stats.mode_idx = jnp.asarray(mode_np, jnp.int32)
        return dense_hat, stats, sent

    def _compression_record(self, rec, stats, rnd):
        """Set one round's compression telemetry on ``rec`` (the round's
        :class:`~repro.obs.records.RoundRecord`).

        Records the mean kept fraction (per-mode budgets resolve through
        the round's mode vector), the active cohort's total bits on air,
        and the mean per-client L2 norm of the EF residual.
        """
        if rnd is not None and self._comp_ks is not None:
            k_vec = np.asarray(self._comp_ks)[np.asarray(rnd.mode)]
        else:
            k_vec = np.full(self.num_clients, self._comp_k)
        active = (np.asarray(rnd.active) if rnd is not None
                  else np.ones(self.num_clients, np.float32))
        boa = np.asarray(stats.bits_on_air, np.float32)
        rec.comp_ratio = float(k_vec.mean() / max(self._comp_dim, 1))
        rec.comp_bits_on_air = float((boa * active).sum())
        # Reduce on device: pulling only the scalar avoids a per-round
        # (num_clients, dim) device-to-host transfer for telemetry.
        rec.comp_residual_norm = float(jnp.sqrt(jnp.mean(jnp.sum(
            self._ef_residual ** 2, axis=1))))

    # ------------------------------------------------------- observability

    def _manifest(self) -> dict:
        """The run-manifest line of an attached ledger: the config
        fingerprint, the run's shape, config summaries, and the provenance
        block (see :mod:`repro.obs.ledger`)."""
        scen = None if self.driver is None else self.driver.scenario
        man = {
            "fingerprint": obs_ledger_lib.config_fingerprint(
                type(self.algo).__name__, self._raw_transport_cfg, scen,
                self.downlink, self.compression, self.dispatch,
                self.n_rounds, self.num_clients, self.seed),
            "engine": "sync",
            "algorithm": self.algo.name,
            "n_rounds": self.n_rounds,
            "num_clients": self.num_clients,
            "seed": self.seed,
            "eval_every": self.eval_every,
            "dispatch": self.dispatch,
            "transport_mode": self.transport_cfg.mode,
            "sample_h2d_bytes": self.sample_h2d_bytes,
            "uplink_waves": self.uplink_waves,
        }
        if scen is not None:
            from repro.link import policy as policy_lib

            man["scenario"] = scen.name
            man["mode_names"] = policy_lib.mode_names(scen.policy)
        if self.downlink is not None:
            man["downlink"] = dataclasses.asdict(self.downlink)
        if self.compression is not None:
            man["compression"] = dataclasses.asdict(self.compression)
        if self.fused_aggregate:
            # Re-derive (rather than add an unconditional fingerprint arg)
            # so every pre-existing layered run keeps its fingerprint.
            man["fused_aggregate"] = True
            man["fingerprint"] = obs_ledger_lib.config_fingerprint(
                man["fingerprint"], "fused_aggregate")
        man["provenance"] = obs_ledger_lib.provenance()
        return man

    def _finish_record(self, res, rec, stats):
        """Tail bookkeeping of one round's :class:`RoundRecord`: fill the
        observability-only ``uplink_*`` aggregates (ledger runs only — they
        force a device->host sync the dict view never paid), append the
        record, mirror its link-dict view, and write the ledger line."""
        if self.ledger is not None and stats is not None:
            with self.phase_timers.scope("sync"):
                summary = stats.round_summary()
            for name, value in summary.items():
                setattr(rec, name, value)
        res.records.append(rec)
        if rec.has_link_fields():
            res.link.append(rec.to_link_dict())
        if self.ledger is not None:
            self.ledger.write_round(rec)

    def _finish_run(self, res) -> None:
        """Close out the attached sinks at the end of :meth:`run`: the
        ledger's summary line (with the phase-timer summary when one was
        attached) and the ledger file itself."""
        if self.ledger is None:
            return
        summary = {
            "final_accuracy": res.final_accuracy,
            "wall_s": res.wall_s,
            "airtime_s": res.airtime_s[-1] if res.airtime_s else 0.0,
            "n_evals": len(res.accuracy),
        }
        if res.event_s:
            summary["event_s"] = res.event_s[-1]
        phases = self.phase_timers.summary()
        if phases:
            summary["phases"] = phases
        if self.sketcher is not None:
            summary["sketches"] = self.sketcher.summary()
        self.ledger.write_summary(summary)
        self.ledger.close()

    # --------------------------------------------------------------- run

    def run(self) -> FLResult:
        """Drive ``n_rounds`` rounds and return the :class:`FLResult`."""
        algo, driver, timings = self.algo, self.driver, self.timings
        comp, tm = self.compression, self.phase_timers
        params, aux, key = self.params, self.aux, self._key
        rng = np.random.default_rng(self.seed)
        res = FLResult([], [], [], 0.0, 0.0)
        t0 = time.time()  # lint: ignore[determinism] wall-clock telemetry
        if self.ledger is not None:
            self.ledger.write_manifest(self._manifest())
        cum_air = 0.0
        for r in range(self.n_rounds):
            key, rk = jax.random.split(key)
            with tm.scope("sample"):
                xb, yb = algo.sample(rng, self.client_rows, self.client_y, tm)
            rnd, counters = None, {}
            if driver is None:
                with tm.scope("round"):
                    if comp is None:
                        params, aux, stats, dstats, counters = \
                            self._round_step(params, aux, xb, yb, rk)
                    else:
                        (params, aux, stats, dstats,
                         self._ef_residual) = self._round_step_comp(
                            params, aux, xb, yb, rk, self._ef_residual)
                rec = obs_records_lib.RoundRecord(round=r)
                with tm.scope("telemetry"):
                    # TDMA uplink: total airtime is the sum over clients.
                    per_client_air = latency_lib.round_airtime(
                        stats, timings, self.transport_cfg.mode)
                    if self.ecrt_air_scale is not None:
                        # Heterogeneous analytic ECRT: rescale each client's
                        # airtime from the cohort-mean E[tx] to its own value.
                        per_client_air = per_client_air * self.ecrt_air_scale
            else:
                with tm.scope("round"):
                    if comp is None:
                        step = (self._round_step_link_bucketed
                                if self.dispatch == "bucketed"
                                else self._round_step_link)
                        params, aux, stats, self.lstate, rnd, dstats = step(
                            params, aux, xb, yb, rk, self.lstate,
                            self.prev_mode, self.prev_est)
                    else:
                        step = (self._round_step_link_bucketed_comp
                                if self.dispatch == "bucketed"
                                else self._round_step_link_comp)
                        (params, aux, stats, self.lstate, rnd, dstats,
                         self._ef_residual) = step(
                            params, aux, xb, yb, rk, self.lstate,
                            self.prev_mode, self.prev_est, self._ef_residual)
                self.prev_mode, self.prev_est = rnd.mode, rnd.est_db
                with tm.scope("telemetry"):
                    per_client_air = driver.airtime(stats, rnd, timings)
                    with tm.scope("sync"):
                        rec = obs_records_lib.scenario_round_record(
                            r, rnd, per_client_air, len(driver.mode_cfgs))
            # Every blocking device-to-host read of the round sits in a
            # ``sync`` scope: the host waits for the device there. The
            # payload's counters come back with the airtime, in one read.
            with tm.scope("sync"):
                air, counters = jax.device_get(
                    (jnp.sum(per_client_air), counters))
                cum_air += float(air)
            if counters:
                rec.counters = {k: int(v) for k, v in counters.items()}
            if comp is not None:
                with tm.scope("sync"):
                    self._compression_record(rec, stats, rnd)
            if dstats is not None:
                with tm.scope("sync"):
                    cum_air += self._downlink_air_record(rec, dstats)
            if self.sketcher is not None:
                with tm.scope("telemetry"), tm.scope("sync"):
                    rec.sketches = self.sketcher.round_group(
                        rk, snr_db=rnd.snr_db, est_db=rnd.est_db,
                        ber=stats.client_metrics()["ber"],
                        airtime_s=per_client_air, mode=rnd.mode,
                        active=rnd.active,
                        downlink_ber=(None if dstats is None
                                      else dstats.ber))
            self._finish_record(res, rec, stats)
            # The engine holds the newest model only: a payload of GBs
            # keeps no stale copy on the device.
            self.params, self.aux = params, aux
            if r % self.eval_every == 0 or r == self.n_rounds - 1:
                with tm.scope("eval"):
                    acc = self._eval_acc(params)
                    with tm.scope("sync"):
                        acc = float(acc)
                res.rounds.append(r)
                res.accuracy.append(acc)
                res.airtime_s.append(cum_air)
                if self.ledger is not None:
                    self.ledger.write_eval(r, acc, cum_air)
        self.params, self.aux, self._key = params, aux, key
        res.params = params
        res.wall_s = time.time() - t0  # lint: ignore[determinism]
        res.final_accuracy = res.accuracy[-1]
        self._finish_run(res)
        return res
