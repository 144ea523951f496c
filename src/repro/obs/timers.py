"""Phase timers: wall-clock scopes with compile time split from steady state.

JAX wall-clock numbers are bimodal — the first call of a jitted function
pays tracing + XLA compilation, every later call pays only execution — so a
single mean/median over a run conflates two different quantities. The FL
engines need the split *per phase*, so a 100-round run can say "the
bucketed uplink cost 80 µs steady after a 2.1 s compile".

:class:`PhaseTimers` keeps one :class:`PhaseStat` per named scope:

    timers = PhaseTimers()
    with timers.scope("uplink"):
        ...host work / dispatch...
    timers.summary()["uplink"]  # first_s vs steady_median_s

Scopes measure *host* wall time between ``__enter__`` and ``__exit__``, and
each one is also a ``jax.profiler.TraceAnnotation``: under a live
``jax.profiler`` trace the scopes land on the profiler's host plane, on the
same clock as the device ops. JAX dispatch is asynchronous, so a scope that
only enqueues device work returns before the work is done; the host waits
for the device only where it reads a value back. The round engine puts
every such blocking device-to-host read inside a ``sync`` scope, so the
wait is charged to ``sync`` (nested in whichever scope holds the read), and
``summary()["sync"]["calls"]`` counts the host syncs. The first call of a
scope still captures trace + compile time, which is synchronous.

``NULL_TIMERS`` is a shared no-op sink — its ``scope`` returns one shared
``contextlib.nullcontext`` — so engine code can always write
``with self.phase_timers.scope(...)`` without branching.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import jax

__all__ = ["PhaseStat", "PhaseTimers", "NULL_TIMERS", "resolve_timers"]


@dataclasses.dataclass
class PhaseStat:
    """Accumulated wall-clock samples of one named phase."""

    name: str
    first_s: float | None = None  # the first call: includes trace + compile
    steady_s: list = dataclasses.field(default_factory=list)  # later calls

    @property
    def calls(self) -> int:
        """Total number of completed scopes."""
        return (0 if self.first_s is None else 1) + len(self.steady_s)

    @property
    def total_s(self) -> float:
        """Wall-clock seconds across every call, first included."""
        return (self.first_s or 0.0) + sum(self.steady_s)

    def steady_median_s(self) -> float:
        """Median of the post-first calls (0.0 with fewer than two calls)."""
        if not self.steady_s:
            return 0.0
        ss = sorted(self.steady_s)
        n = len(ss)
        mid = n // 2
        return ss[mid] if n % 2 else 0.5 * (ss[mid - 1] + ss[mid])

    def record(self, seconds: float) -> None:
        """Add one completed scope's duration."""
        if self.first_s is None:
            self.first_s = seconds
        else:
            self.steady_s.append(seconds)


class PhaseTimers:
    """A bag of named :class:`PhaseStat` scopes (see module docstring)."""

    def __init__(self):
        self.phases: dict[str, PhaseStat] = {}

    @contextlib.contextmanager
    def scope(self, name: str):
        """Context manager timing one occurrence of phase ``name`` and
        marking it in the profiler's trace."""
        stat = self.phases.get(name)
        if stat is None:
            stat = self.phases[name] = PhaseStat(name)
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield stat
            finally:
                stat.record(time.perf_counter() - t0)

    def summary(self) -> dict:
        """JSON-ready per-phase summary: calls, first (compile) seconds,
        steady-state median/total seconds."""
        return {
            name: {
                "calls": st.calls,
                "first_s": st.first_s or 0.0,
                "steady_median_s": st.steady_median_s(),
                "steady_total_s": sum(st.steady_s),
                "total_s": st.total_s,
            }
            for name, st in self.phases.items()
        }

    def report(self) -> str:
        """Human-readable fixed-width table of :meth:`summary`."""
        lines = [f"{'phase':<14} {'calls':>5} {'first':>10} "
                 f"{'steady med':>10} {'total':>10}"]
        for name, s in self.summary().items():
            lines.append(
                f"{name:<14} {s['calls']:>5} {s['first_s'] * 1e3:>8.1f}ms "
                f"{s['steady_median_s'] * 1e3:>8.2f}ms "
                f"{s['total_s']:>9.2f}s")
        return "\n".join(lines)


class _NullTimers(PhaseTimers):
    """Shared do-nothing sink: ``scope`` hands back one shared
    ``nullcontext`` and records nothing, so uninstrumented runs stay
    unperturbed."""

    _NULL_SCOPE = contextlib.nullcontext()

    def scope(self, name: str):
        """No-op scope."""
        return self._NULL_SCOPE


NULL_TIMERS = _NullTimers()


def resolve_timers(phase_timers) -> PhaseTimers:
    """``phase_timers=`` engine argument -> a usable sink (``None`` maps to
    the shared no-op)."""
    return NULL_TIMERS if phase_timers is None else phase_timers
