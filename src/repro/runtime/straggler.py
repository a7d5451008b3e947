"""Straggler watchdog: per-step wall-time EMA with a deadline multiple.

On a real pod this drives mitigation (preempt + re-slot the slow host, or
drop to the checkpoint and exclude it — runtime/elastic.py); in this
container the detection logic is what we can exercise (tests inject delays).

``FleetWatchdog`` is the multi-replica feed (runtime/fleet.py): one
``StragglerWatchdog`` per serving replica, plus a cross-replica comparison —
a replica whose step-time EMA exceeds ``factor`` x the median EMA of its
live peers is a *fleet* straggler even if its own per-step deadline never
fires (a uniformly-slow replica looks healthy to itself). The fleet router
steals queued requests from flagged replicas.

``StepTimer`` is the one step clock: the training driver and the fleet time
whole steps with it, and the serving engine also splits each step into
named phases (``StepTimer.phase``), which a profiler trace shows as spans.
"""

from __future__ import annotations

import dataclasses
import time

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class StragglerWatchdog:
    factor: float = 3.0          # deadline = factor × EMA
    ema_decay: float = 0.9
    min_samples: int = 5
    ema: float = 0.0
    n: int = 0
    events: list = dataclasses.field(default_factory=list)

    def record(self, step: int, dt: float) -> bool:
        """Record a step time; returns True if this step is a straggler."""
        is_straggler = (self.n >= self.min_samples
                        and dt > self.factor * self.ema)
        if is_straggler:
            self.events.append({"step": step, "dt": dt, "ema": self.ema})
        else:  # stragglers don't poison the EMA
            self.ema = (dt if self.n == 0
                        else self.ema_decay * self.ema
                        + (1 - self.ema_decay) * dt)
            self.n += 1
        return is_straggler

    @property
    def deadline(self) -> float:
        return self.factor * self.ema if self.n >= self.min_samples else float("inf")


class FleetWatchdog:
    """Per-replica straggler feed for the serving fleet.

    Each replica's fleet turn records ONE sample (the wall time of the
    engine steps it ran, plus any injected fault delay) into that replica's
    ``StragglerWatchdog``. ``stragglers()`` then flags a replica when

    * its own watchdog flagged the most recent sample (deadline blown), or
    * its EMA exceeds ``factor`` x the median EMA across the live replicas
      (relative slowness its own deadline cannot see).

    ``min_samples=1`` on the per-replica feeds: a replica's very first
    sample seeds its EMA, so scripted delays are visible immediately.
    """

    def __init__(self, n_replicas: int, factor: float = 3.0,
                 ema_decay: float = 0.9):
        self.factor = factor
        self.ema_decay = ema_decay
        self.feeds = {r: StragglerWatchdog(factor=factor,
                                           ema_decay=ema_decay,
                                           min_samples=1)
                      for r in range(n_replicas)}
        self._last_flag = {r: False for r in range(n_replicas)}

    def record(self, replica: int, step: int, dt: float) -> bool:
        flagged = self.feeds[replica].record(step, dt)
        self._last_flag[replica] = flagged
        return flagged

    def reset(self, replica: int) -> None:
        """Fresh feed for a rejoining replica (its old EMA is meaningless
        after a restore)."""
        self.feeds[replica] = StragglerWatchdog(factor=self.factor,
                                                ema_decay=self.ema_decay,
                                                min_samples=1)
        self._last_flag[replica] = False

    def ema(self, replica: int) -> float:
        return self.feeds[replica].ema

    def stragglers(self, live=None) -> list[int]:
        rs = sorted(self.feeds if live is None else live)
        emas = sorted(self.feeds[r].ema for r in rs if self.feeds[r].n > 0)
        med = emas[len(emas) // 2] if emas else 0.0
        out = []
        for r in rs:
            feed = self.feeds[r]
            if self._last_flag[r] or (len(emas) >= 2 and med > 0.0
                                      and feed.n > 0
                                      and feed.ema > self.factor * med):
                out.append(r)
        return out


class StepTimer:
    """Wall time of one step (``dt``) and of the named phases inside it.

    ``phase(name)`` adds the seconds spent inside it to ``phases[name]`` and
    opens a profiler ``TraceAnnotation`` of the same name, so a device trace
    carries the same split on its host line. With no profiler running the
    annotation does nothing but the call; nothing turns it on or off.
    """

    def __init__(self):
        self.t0 = None
        self.phases: dict[str, float] = {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.dt = time.perf_counter() - self.t0

    def elapsed(self) -> float:
        """Seconds since the step started, while it runs."""
        return time.perf_counter() - self.t0

    def phase(self, name: str) -> "_Phase":
        return _Phase(self.phases, name)


class _Phase:
    __slots__ = ("phases", "name", "span", "t0")

    def __init__(self, phases: dict, name: str):
        self.phases, self.name = phases, name
        self.span = TraceAnnotation(name)

    def __enter__(self):
        self.span.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *a):
        dt = time.perf_counter() - self.t0
        self.span.__exit__(*a)
        self.phases[self.name] = self.phases.get(self.name, 0.0) + dt
