"""Fault tolerance for the multi-host refresh (``mv.multihost``):
preemption-safe shutdown and straggler detection.

Every mechanism is per-host-local with O(1) state and no global
coordination.

* ``PreemptionHandler`` — converts SIGTERM/SIGINT into a cooperative flag a
  worker polls, so it can drain and exit cleanly.
* ``StragglerDetector`` — per-host step-duration EWMA vs the fleet median;
  hosts slower than ``threshold ×`` median for ``patience`` consecutive steps
  are flagged (driver action: re-dispatch/evict — here surfaced as events).
"""
from __future__ import annotations

import dataclasses
import signal
import statistics
import threading
from typing import Any


class PreemptionHandler:
    def __init__(self, signals=(signal.SIGTERM, signal.SIGINT)):
        self._flag = threading.Event()
        self._prev: dict[int, Any] = {}
        self._signals = signals

    def install(self) -> "PreemptionHandler":
        for s in self._signals:
            self._prev[s] = signal.signal(s, self._on_signal)
        return self

    def _on_signal(self, signum, frame):
        self._flag.set()

    @property
    def preempted(self) -> bool:
        return self._flag.is_set()

    def uninstall(self) -> None:
        for s, prev in self._prev.items():
            signal.signal(s, prev)
        self._prev.clear()


@dataclasses.dataclass
class StragglerEvent:
    step: int
    host: int
    duration: float
    median: float


class StragglerDetector:
    """Flags hosts whose step time exceeds ``threshold`` × fleet median for
    ``patience`` consecutive steps."""

    def __init__(self, n_hosts: int, threshold: float = 2.0, patience: int = 3,
                 ewma: float = 0.5):
        self.n_hosts = n_hosts
        self.threshold = threshold
        self.patience = patience
        self.ewma = ewma
        self._avg = [0.0] * n_hosts
        self._strikes = [0] * n_hosts
        self.events: list[StragglerEvent] = []

    def observe(self, step: int, durations: list[float]) -> list[int]:
        """Feed per-host step durations; returns hosts flagged this step."""
        assert len(durations) == self.n_hosts
        for h, d in enumerate(durations):
            self._avg[h] = (
                d if self._avg[h] == 0.0
                else self.ewma * d + (1 - self.ewma) * self._avg[h]
            )
        med = statistics.median(self._avg)
        flagged = []
        for h in range(self.n_hosts):
            if med > 0 and self._avg[h] > self.threshold * med:
                self._strikes[h] += 1
            else:
                self._strikes[h] = 0
            if self._strikes[h] >= self.patience:
                flagged.append(h)
                self.events.append(
                    StragglerEvent(step, h, self._avg[h], med)
                )
                self._strikes[h] = 0  # re-arm after reporting
        return flagged

