"""End-to-end link scenarios: dynamics + CSI + policy + client availability.

A :class:`Scenario` bundles everything the FL loops need to run the paper's
adaptive system under a named mobility/availability profile: how per-client
SNR evolves round to round (``link.dynamics``), how noisily the PS observes
it (``link.estimator``), how the mode policy reacts (``link.policy``), and
which clients drop out or straggle. ``SCENARIOS`` is the registry
(``get_scenario``/``register_scenario``/``list_scenarios``);
:class:`ScenarioDriver` compiles a scenario against a base transport config
into pure per-round functions that live *inside* the jitted FL round step —
one XLA program per round, link adaptation included.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress.sparsify import CompressionConfig
from repro.core import latency as latency_lib
from repro.core import transport as transport_lib
from repro.link import dynamics as dynamics_lib
from repro.link import estimator as estimator_lib
from repro.link import policy as policy_lib

__all__ = [
    "DownlinkConfig",
    "Scenario",
    "LinkRound",
    "ScenarioDriver",
    "SCENARIOS",
    "get_scenario",
    "register_scenario",
    "list_scenarios",
]


@dataclasses.dataclass(frozen=True)
class DownlinkConfig:
    """The broadcast leg of an FL round: how the global model reaches clients.

    The paper models bit errors on the uplink only; Qu et al.
    (arXiv:2310.16652) show the downlink broadcast of the global model is
    markedly *less* error-tolerant than uplink gradients, so this config
    makes the leg explicit. ``None`` on a scenario / FL loop (the default
    everywhere) keeps the historical error-free downlink and changes no
    existing result bit-wise.

    ``mode``
        Broadcast transport: ``"perfect"`` (error-free reference) or an
        uncoded mode (``"approx"``/``"naive"``) — the error-budget axis of
        the Qu et al. comparison. Any transport mode is accepted; an
        ``"ecrt"`` downlink is priced with the calibrated analytic model at
        the *shifted* operating point (the engine never runs the real LDPC
        decoder inside a round — see ``engine.RoundEngine``).
    ``modulation``
        ``None`` inherits the uplink's modulation.
    ``snr_offset_db``
        Downlink SNR = uplink SNR + Δ dB (base stations transmit with more
        power than handsets — a positive Δ; 0 is the matched-SNR study).
    ``adaptive``
        Scenario-driven runs only: pick each client's downlink mode from the
        scenario's *existing* policy table at the shifted CSI
        (``policy.downlink_mode``) instead of one fixed broadcast mode.
    """

    mode: str = "approx"
    modulation: str | None = None
    snr_offset_db: float = 0.0
    adaptive: bool = False


@dataclasses.dataclass(frozen=True)
class Scenario:
    """A named, fully specified link environment for an FL run.

    ``dropout_prob`` is the per-round probability a client is silently
    absent (no uplink, no airtime, excluded from aggregation);
    ``straggler_prob``/``straggler_slowdown`` model clients whose uplink
    takes ``slowdown``x the modeled airtime (contention, duty cycling).
    ``ecrt_expected_tx = None`` means "calibrate with the real LDPC chain"
    (cached): the transport constant anchors at the protected regime's SNR
    and airtime interpolates E[tx] per client per round over a calibrated
    SNR grid (see :meth:`ScenarioDriver.airtime`). A float skips
    calibration and prices with that constant — tests and quick sweeps set
    it explicitly.
    """

    name: str
    dynamics: dynamics_lib.LinkDynamicsConfig
    estimator: estimator_lib.EstimatorConfig = estimator_lib.EstimatorConfig()
    policy: policy_lib.PolicyConfig = policy_lib.PolicyConfig()
    dropout_prob: float = 0.0
    straggler_prob: float = 0.0
    straggler_slowdown: float = 3.0
    ecrt_expected_tx: float | None = None
    # Broadcast leg of each round; None = error-free downlink (the paper's
    # implicit assumption, and bit-identical to pre-downlink behavior).
    downlink: DownlinkConfig | None = None
    # Default uplink compression for runs under this scenario (the FL
    # loops' explicit ``compression=`` argument wins); None = dense uplinks,
    # bit-identical to pre-compression behavior. Per-mode slot budgets come
    # from ``policy.compress_ratios`` (the CSI-adaptive column).
    compression: CompressionConfig | None = None
    # Event-layer defaults for the buffered (asynchronous) engine: how long
    # local computation takes per wave and how clients churn/idle between
    # waves. Both are ignored by the synchronous engine; ``compute=None``
    # resolves to the degenerate constant-time model and ``arrival=None``
    # means always-available clients with no idle gaps.
    compute: dynamics_lib.ComputeTimeConfig | None = None
    arrival: dynamics_lib.ArrivalConfig | None = None
    description: str = ""


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LinkRound:
    """One round's link telemetry; every field is ``(num_clients,)``.

    ``snr_db`` is ground truth (drives the channel), ``est_db`` is what the
    policy saw, ``mode`` indexes the driver's mode table, ``active`` and
    ``straggler`` are 0/1 floats.
    """

    snr_db: jax.Array
    est_db: jax.Array
    mode: jax.Array
    active: jax.Array
    straggler: jax.Array


class ScenarioDriver:
    """A scenario bound to a transport config: the FL loops' link engine.

    Construction resolves the mode table (calibrating ECRT's E[tx] if the
    scenario asks for it); ``init``/``round`` are pure jax and safe to call
    inside jit — ``round`` advances dynamics, estimates CSI, runs the
    policy, and draws availability, returning the carry for the next round
    plus the :class:`LinkRound` record the uplink and telemetry consume.

    ECRT pricing: ``scenario.ecrt_expected_tx = None`` calibrates E[tx] at
    the policy's anchor SNR for the *transport* constant (the analytic model
    inside the uplink) and, for *airtime*, lazily builds a small calibrated
    curve over ECRT's operating band so each client's airtime reflects its
    own SNR that round (a client in a fade retransmits more than the
    anchor average). An explicit float keeps the old constant pricing.
    """

    def __init__(self, scenario: Scenario,
                 base_cfg: transport_lib.TransportConfig,
                 *, calib_codewords: int = policy_lib.DEFAULT_CALIB_CODEWORDS,
                 calib_max_tx: int = policy_lib.DEFAULT_CALIB_MAX_TX,
                 calib_grid_points: int = 3):
        self.scenario = scenario
        self._calib = (calib_codewords, calib_max_tx, calib_grid_points)
        self._ecrt_curve = None  # lazily built by _ecrt_tx_curve
        ecrt_mods = {mod for m, mod in scenario.policy.modes if m == "ecrt"}
        # Per-client/per-round interpolated airtime only applies when the
        # scenario asked for calibration (None); an explicit float means
        # "price with this constant" (tests, controlled sweeps). Tables with
        # several distinct ECRT modulations fall back to their (per-row
        # calibrated) constants — one interpolation curve cannot serve two
        # constellations.
        self._interp_ecrt_airtime = (len(ecrt_mods) == 1) and (
            scenario.ecrt_expected_tx is None)
        # Calibration (when ecrt_expected_tx is None) happens inside
        # build_mode_cfgs — the single pricing path; the scenario's fleet
        # operating point is the anchor fallback for threshold-less tables.
        self.mode_cfgs = policy_lib.build_mode_cfgs(
            base_cfg, scenario.policy,
            ecrt_expected_tx=scenario.ecrt_expected_tx,
            calib_codewords=calib_codewords, calib_max_tx=calib_max_tx,
            anchor_fallback_db=scenario.dynamics.mean_snr_db)
        self._ecrt_rows = tuple(
            i for i, c in enumerate(self.mode_cfgs) if c.mode == "ecrt")

    def _ecrt_modulation(self) -> str:
        return next(mod for m, mod in self.scenario.policy.modes
                    if m == "ecrt")

    def _ecrt_tx_curve(self):
        """Calibrated (grid_db, E[tx]) over ECRT's operating band, cached.

        The band runs from the dynamics' SNR floor up to the first policy
        threshold plus the hysteresis window (above that the policy moves
        clients off ECRT); a fixed-ECRT table spans the whole dynamics
        range. Points go through ``latency.calibrate_ecrt``'s cache.
        """
        if self._ecrt_curve is None:
            scen = self.scenario
            codewords, max_tx, points = self._calib
            thr = scen.policy.thresholds_db
            lo = scen.dynamics.snr_floor_db
            hi = (thr[0] + scen.policy.hysteresis_db) if thr \
                else scen.dynamics.snr_ceil_db
            # The anchor joins the grid so a client sitting exactly at the
            # transport constant's calibration point gets ratio 1 (its grid
            # value is the same LRU-cached calibrate_ecrt call). Wide bands
            # (threshold-less tables span the whole dynamics range) get
            # proportionally more points — E[tx] vs SNR is convex, so a
            # sparse linear chord would overprice mid-band clients.
            hi = max(hi, lo + 1.0)
            points = max(points, int(np.ceil((hi - lo) / 12.0)) + 1)
            anchor = policy_lib.ecrt_anchor_snr_db(
                scen.policy, scen.dynamics.mean_snr_db)
            grid = np.unique(np.concatenate(
                [np.linspace(lo, hi, points), [anchor]]))
            self._ecrt_curve = latency_lib.ecrt_expected_tx_curve(
                grid, self._ecrt_modulation(), n_codewords=codewords,
                max_tx=max_tx)
        return self._ecrt_curve

    def init(self, key: jax.Array, num_clients: int
             ) -> tuple[dynamics_lib.LinkState, jax.Array, jax.Array]:
        """Stationary link state, round-0 modes, and round-0 CSI.

        Modes are the hysteresis-free mapping of each client's static
        operating point (mean SNR + frozen offset); that operating point is
        also returned as the initial "previous estimate" the first
        :meth:`round` call's staleness logic falls back on — callers thread
        both through as ``prev_mode`` / ``prev_est_db``.
        """
        state = dynamics_lib.init_state(key, num_clients,
                                        self.scenario.dynamics)
        op_point = self.scenario.dynamics.mean_snr_db + state.offset_db
        mode0 = policy_lib.initial_mode(op_point, self.scenario.policy)
        return state, mode0, op_point

    def round(self, state: dynamics_lib.LinkState, prev_mode: jax.Array,
              prev_est_db: jax.Array, key: jax.Array,
              observed: jax.Array | None = None
              ) -> tuple[dynamics_lib.LinkState, LinkRound]:
        """One link round: dynamics -> estimator -> policy -> availability.

        ``observed`` (0/1 per client, or ``None`` = everyone) marks the
        clients actually dispatched this wave: unobserved clients keep
        their previous mode (``policy.choose_mode``'s participation mask),
        so hysteresis state survives the participation gaps of a buffered
        asynchronous run. ``None`` is bit-identical to the synchronous
        behavior.
        """
        scen = self.scenario
        with jax.named_scope("fl_link"):
            k_dyn, k_est, k_drop, k_strag = jax.random.split(key, 4)
            state, snr = dynamics_lib.step(state, k_dyn, scen.dynamics)
            est = estimator_lib.step_estimate(snr, prev_est_db, k_est,
                                              scen.estimator)
            mode = policy_lib.choose_mode(est, prev_mode, scen.policy,
                                          observed=observed)
            shape = snr.shape
            active = jax.random.bernoulli(
                k_drop, 1.0 - scen.dropout_prob, shape).astype(jnp.float32)
            straggler = jax.random.bernoulli(
                k_strag, scen.straggler_prob, shape).astype(jnp.float32)
            return state, LinkRound(snr, est, mode, active, straggler)

    def airtime(self, stats: transport_lib.TxStats, rnd: LinkRound,
                timings: latency_lib.PhyTimings) -> jax.Array:
        """Per-client airtime of the round: mode-priced, straggler-scaled,
        zero for dropped clients. ``(num_clients,)`` seconds.

        With calibrated ECRT (``scenario.ecrt_expected_tx = None``) each
        ECRT client's symbols/transmissions are rescaled from the anchor
        constant to E[tx] interpolated at *its* SNR *this round* — the
        analytic model is linear in E[tx], so the rescale prices the fade
        exactly as a per-client calibration would.

        Known approximation: for *sparse* frames (``repro.compress``) the
        combined stats include the uncoded index-header symbols, which the
        rescale scales along with the LDPC value leg even though the
        header is never retransmitted — an error bounded by the header's
        share of the frame (typically <= ~20%); pricing it exactly would
        need per-leg stats. Explicit ``ecrt_expected_tx`` (no rescale) is
        unaffected.
        """
        if (self._interp_ecrt_airtime and self._ecrt_rows
                and stats.mode_idx is not None):
            grid, vals = self._ecrt_tx_curve()
            e_tx = latency_lib.interp_expected_tx(rnd.snr_db, grid, vals)
            anchor = jnp.asarray(
                [c.ecrt_expected_tx for c in self.mode_cfgs], jnp.float32
            )[stats.mode_idx]
            is_ecrt = jnp.any(
                jnp.asarray(stats.mode_idx)[:, None]
                == jnp.asarray(self._ecrt_rows, jnp.int32), axis=-1)
            ratio = jnp.where(is_ecrt, e_tx / jnp.maximum(anchor, 1e-6), 1.0)
            stats = transport_lib.TxStats(
                stats.data_symbols * ratio, stats.transmissions * ratio,
                stats.bit_errors, stats.n_bits, stats.mode_idx,
                bits_on_air=None if stats.bits_on_air is None
                else stats.bits_on_air * ratio)
        air = latency_lib.round_airtime_adaptive(stats, timings,
                                                 self.mode_cfgs)
        slowdown = 1.0 + (self.scenario.straggler_slowdown - 1.0) * rnd.straggler
        return air * slowdown * rnd.active


SCENARIOS: dict[str, Scenario] = {}


def register_scenario(scenario: Scenario) -> Scenario:
    """Add (or replace) a scenario in the registry; returns it."""
    SCENARIOS[scenario.name] = scenario
    return scenario


def get_scenario(name: str) -> Scenario:
    """Look up a registered scenario; unknown names list what exists."""
    try:
        return SCENARIOS[name]
    except KeyError:
        raise KeyError(
            f"unknown scenario {name!r}; registered: "
            f"{', '.join(sorted(SCENARIOS))}"
        ) from None


def list_scenarios() -> list[str]:
    """Registered scenario names, sorted."""
    return sorted(SCENARIOS)


def _preset(name: str, **kw) -> Scenario:
    return register_scenario(Scenario(
        name=name, dynamics=dynamics_lib.DYNAMICS_PRESETS[kw.pop("dyn", name)],
        **kw))


_preset("static",
        description="the paper's setup: one SNR, all clients, whole run")
_preset("pedestrian",
        description="walking users: slow fading drift + moderate shadowing")
_preset("vehicular",
        description="driving users: fast fading, wide per-client spread")
_preset("shadowed-urban",
        description="urban canyon: slowly-decorrelating deep shadowing")
_preset("bursty",
        description="IoT links: good on average with Markov blockage spells")
_preset("iot-flaky", dyn="bursty",
        estimator=estimator_lib.EstimatorConfig(n_pilots=16, stale_prob=0.2),
        dropout_prob=0.1, straggler_prob=0.1, straggler_slowdown=3.0,
        description="bursty links + few pilots, stale CSI, dropout, stragglers")
_preset("vehicular-noisy-dl", dyn="vehicular",
        downlink=DownlinkConfig(mode="approx", snr_offset_db=3.0,
                                adaptive=True),
        description="vehicular links with a noisy adaptive broadcast "
                    "downlink 3 dB above the uplink (per-client mode via "
                    "the policy table)")
_preset("static-noisy-dl", dyn="static",
        downlink=DownlinkConfig(mode="approx", snr_offset_db=0.0),
        description="the paper's static setup plus a matched-SNR uncoded "
                    "broadcast downlink (the Qu et al. error-budget axis)")
_preset("iot-lowrate",
        estimator=estimator_lib.EstimatorConfig(n_pilots=16),
        policy=policy_lib.PolicyConfig(
            compress_ratios=(0.01, 0.02, 0.05, 0.10)),
        dropout_prob=0.05,
        compression=CompressionConfig(method="topk", ratio=0.02),
        description="narrowband low-SNR IoT links; top-k+EF sparse uplinks "
                    "on by default, compressed deepest in the protected "
                    "low-SNR modes (CSI-adaptive ratio column)")
_preset("metro-rush", dyn="vehicular",
        dropout_prob=0.05, straggler_prob=0.10, straggler_slowdown=3.0,
        compute=dynamics_lib.ComputeTimeConfig(
            mean_s=0.5, speed_spread=0.4, jitter=0.3,
            straggler_prob=0.15, straggler_factor=20.0),
        arrival=dynamics_lib.ArrivalConfig(mean_idle_s=0.25),
        description="rush-hour metro cell: vehicular links, heavy-tailed "
                    "compute stragglers (20x spells), Poisson re-arrival "
                    "gaps — the buffered engine's home turf")
_preset("global-churn", dyn="shadowed-urban",
        dropout_prob=0.05,
        compute=dynamics_lib.ComputeTimeConfig(
            mean_s=1.0, speed_spread=0.5, jitter=0.2,
            straggler_prob=0.05, straggler_factor=8.0),
        arrival=dynamics_lib.ArrivalConfig(
            mean_idle_s=1.0, p_leave=0.10, p_rejoin=0.30),
        description="planet-scale cohort: urban-canyon shadowing with "
                    "clients leaving and rejoining between waves (EF "
                    "residuals and hysteresis state must survive the gaps)")
