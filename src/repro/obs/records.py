"""Typed per-round / per-event telemetry records (schema v2).

Before this module, per-round FL telemetry was a pile of ad-hoc dicts in
``FLResult.link`` whose schema lived in a comment on the dataclass, and the
asynchronous engine's event clock was invisible outside ``event_s``
scalars. This module is the single source of truth for both shapes:

* :class:`RoundRecord` — one synchronous round (or one dispatched wave of
  the buffered engine): the scenario link fields, the compression fields,
  the downlink fields, plus observability-only extras (per-leg BER
  aggregates from ``TxStats``, the event-clock dispatch time). Engines
  build these natively; :meth:`RoundRecord.to_link_dict` reproduces the
  historical ``FLResult.link`` dict **bit-identically** (same keys, same
  insertion order, same values — pinned by ``tests/test_obs.py``).
* :class:`EventRecord` — one event-clock happening of the buffered engine
  (wave dispatch, per-client compute/uplink spans, arrivals, aggregations,
  churn, buffer-fill samples). The run ledger persists them as JSONL and
  the Perfetto exporter (:mod:`repro.obs.trace`) renders them as tracks.

Records serialize losslessly: ``to_dict`` drops unset (``None``) fields,
``from_dict`` restores them, and ``SCHEMA_VERSION`` stamps every ledger so
readers can refuse records they do not understand.
"""

from __future__ import annotations

import dataclasses

__all__ = [
    "SCHEMA_VERSION",
    "SUPPORTED_SCHEMAS",
    "V2_ROUND_FIELDS",
    "LINK_FIELDS",
    "EVENT_KINDS",
    "RoundRecord",
    "EventRecord",
    "scenario_round_record",
]

# Versioned record schema: bump when a field changes meaning or a field
# group is added that old readers must not misparse. v1 = the original
# typed-record layer; v2 adds the per-round ``sketches`` group (mergeable
# per-client distribution sketches, see ``repro.obs.sketch``). Readers
# accept every version in SUPPORTED_SCHEMAS; writers stamp SCHEMA_VERSION.
SCHEMA_VERSION = 2
SUPPORTED_SCHEMAS = (1, 2)

# Fields that only exist from schema v2 on: a v1-stamped ledger line
# carrying one of these is a mixed-version line and is rejected with a
# per-line error by ``repro.obs.ledger.read_ledger``.
V2_ROUND_FIELDS = ("sketches",)

# The historical ``FLResult.link`` dict keys, in the exact insertion order
# the engines produced before the typed-record layer existed: scenario
# fields first, then compression, then downlink. ``to_link_dict`` walks
# this tuple, so the dict view stays bit-identical to the pre-record dicts.
LINK_FIELDS = (
    "round",
    "mean_snr_db",
    "mean_est_db",
    "mode_counts",
    "n_active",
    "n_stragglers",
    "airtime_s",
    "comp_ratio",
    "comp_bits_on_air",
    "comp_residual_norm",
    "downlink_airtime_s",
    "downlink_ber",
    "downlink_mode_counts",
)

# Event-record kinds the buffered engine emits. Span kinds carry ``dur``;
# instant kinds carry only ``t``; ``buffer`` is a counter sample (``value``
# = updates buffered after the event).
EVENT_KINDS = (
    "wave",       # span: one dispatch wave, t .. t + dur (last arrival)
    "compute",    # span: one client's local computation
    "uplink",     # span: one client's uplink airtime
    "arrival",    # instant: an update landed in the server buffer
    "aggregate",  # instant: the buffer folded into a new model version
    "join",       # instant: a churned-out client rejoined
    "leave",      # instant: a client churned out
    "buffer",     # counter: buffer fill level after an event
)


@dataclasses.dataclass
class RoundRecord:
    """Typed telemetry of one FL round (or one buffered-engine wave).

    Only ``round`` is mandatory; every other field is ``None`` until the
    engine fills it, and ``None`` fields are dropped from both serialized
    forms. The first three field groups mirror the historical link-dict
    keys exactly (see :data:`LINK_FIELDS`); the observability-only group is
    new with this layer and never appears in :meth:`to_link_dict`.
    """

    round: int
    # -- scenario link fields (driver-backed rounds only)
    mean_snr_db: float | None = None
    mean_est_db: float | None = None
    mode_counts: list | None = None
    n_active: int | None = None
    n_stragglers: int | None = None
    airtime_s: float | None = None
    # -- compression fields (compressed uplinks only)
    comp_ratio: float | None = None
    comp_bits_on_air: float | None = None
    comp_residual_norm: float | None = None
    # -- downlink fields (noisy broadcast leg only)
    downlink_airtime_s: float | None = None
    downlink_ber: float | None = None
    downlink_mode_counts: list | None = None
    # -- observability-only fields (never in the link-dict view)
    t_event: float | None = None  # event-clock dispatch time (async engine)
    uplink_symbols: float | None = None  # cohort data symbols on air
    uplink_bits: float | None = None  # cohort payload bits offered
    uplink_bit_errors: float | None = None  # cohort residual bit errors
    uplink_ber: float | None = None  # cohort end-to-end payload BER
    # the payload model's counters this round, and the fused uplink
    # kernel's ``uplink_exact_tiles``
    counters: dict | None = None
    uplink_mean_tx: float | None = None  # mean PHY transmissions/client
    uplink_bits_on_air: float | None = None  # cohort bits actually on air
    # -- schema v2: constant-size per-client distribution sketches
    # (``repro.obs.metrics.RoundSketcher.round_group`` output: per-metric
    # bucket counts + reservoir/worst-client exemplars)
    sketches: dict | None = None

    def to_link_dict(self) -> dict:
        """The historical ``FLResult.link`` dict: link-view fields only, in
        the pre-record insertion order, ``None`` fields omitted."""
        return {k: getattr(self, k) for k in LINK_FIELDS
                if getattr(self, k) is not None}

    def has_link_fields(self) -> bool:
        """Whether any link-view field beyond ``round`` is set — the
        condition under which the pre-record engines appended a dict."""
        return any(getattr(self, k) is not None for k in LINK_FIELDS[1:])

    def to_dict(self) -> dict:
        """All set fields (link view + observability extras) as one flat
        JSON-ready dict."""
        out = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is not None:
                out[f.name] = v
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "RoundRecord":
        """Inverse of :meth:`to_dict`; unknown keys are rejected so ledger
        corruption fails loudly instead of round-tripping silently."""
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - names
        if unknown:
            raise ValueError(
                f"RoundRecord.from_dict: unknown field(s) {sorted(unknown)}")
        if "round" not in d:
            raise ValueError("RoundRecord.from_dict: missing 'round'")
        return cls(**d)


@dataclasses.dataclass
class EventRecord:
    """One event-clock happening of the buffered asynchronous engine.

    ``t`` is the simulated event-clock time in seconds; ``kind`` is one of
    :data:`EVENT_KINDS`. Span kinds (``wave``/``compute``/``uplink``) set
    ``dur``; ``buffer`` samples set ``value`` (the fill level); client- and
    wave-scoped kinds set ``client``/``wave``; ``aggregate`` sets
    ``version`` (the model version the aggregation produced) and ``value``
    (how many updates it folded).
    """

    t: float
    kind: str
    wave: int | None = None
    client: int | None = None
    version: int | None = None
    dur: float | None = None
    value: float | None = None

    def __post_init__(self):
        if self.kind not in EVENT_KINDS:
            raise ValueError(
                f"unknown event kind {self.kind!r}; one of {EVENT_KINDS}")

    def to_dict(self) -> dict:
        """Set fields as a flat JSON-ready dict (``None`` omitted)."""
        out = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is not None:
                out[f.name] = v
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "EventRecord":
        """Inverse of :meth:`to_dict`; unknown keys fail loudly."""
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - names
        if unknown:
            raise ValueError(
                f"EventRecord.from_dict: unknown field(s) {sorted(unknown)}")
        return cls(**d)


def scenario_round_record(r, rnd, per_client_air, n_modes) -> RoundRecord:
    """One round's scenario fields as a :class:`RoundRecord`.

    The typed twin of the pre-record ``engine.link_telemetry`` — same
    arithmetic on the same arrays, so ``to_link_dict()`` of the result is
    bit-identical to the dict that function produced.
    """
    import numpy as np

    mode = np.asarray(rnd.mode)
    return RoundRecord(
        round=r,
        mean_snr_db=float(np.mean(np.asarray(rnd.snr_db))),
        mean_est_db=float(np.mean(np.asarray(rnd.est_db))),
        mode_counts=np.bincount(mode, minlength=n_modes).tolist(),
        n_active=int(np.asarray(rnd.active).sum()),
        n_stragglers=int(np.asarray(rnd.straggler).sum()),
        airtime_s=float(np.asarray(per_client_air).sum()),
    )
