"""Straggler + anomaly watches: pure-host detectors over walls the run
already measured. Zero fences by construction — every input is a float
some existing fence or ``perf_counter`` delta produced.

Two detectors:

``ImbalanceWatch`` — sustained cross-actor imbalance with hysteresis.
Fed the max/median ratio of per-sub-fleet round walls (the batched
sweep).
``update(ratio)`` returns ``"raised"`` exactly once after K
consecutive samples at/above the threshold, ``"cleared"`` exactly once
after K consecutive samples at/below the clear ratio, and ``None``
otherwise — edge-triggered, so the ledger/event stream carries state
TRANSITIONS, not one line per sampled round.

``AnomalyWatch`` — rolling-median round-wall deviation. Fed every
traced round's ``wall_ms``; fires when a wall exceeds ``factor`` x the
trailing-window median. Anomalous walls are NOT folded into the window
(a burst must not drag the median up to meet itself), and consecutive
anomalies fire once (edge-triggered) — a run drifting into trouble
says so near the FIRST bad round.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Dict, List, Optional, Sequence

__all__ = ["AnomalyWatch", "ImbalanceWatch", "imbalance_ratio"]


def _median(vals: Sequence[float]) -> float:
    s = sorted(vals)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


def imbalance_ratio(walls: Sequence[float]) -> Optional[float]:
    """max/median over per-actor round times; None when fewer than two
    actors reported or the median is degenerate (all-idle sample)."""
    vals = [float(w) for w in walls
            if isinstance(w, (int, float)) and w >= 0]
    if len(vals) < 2:
        return None
    med = _median(vals)
    if med <= 0:
        return None
    return max(vals) / med


class ImbalanceWatch:
    """Edge-triggered sustained-imbalance detector with hysteresis."""

    def __init__(self, threshold: float = 1.5, rounds: int = 3,
                 clear_ratio: Optional[float] = None) -> None:
        self.threshold = max(float(threshold), 1.0)
        self.rounds = max(int(rounds), 1)
        # default clear level: halfway back from the threshold to 1.0,
        # so a ratio oscillating AT the threshold cannot flap
        self.clear = (float(clear_ratio) if clear_ratio is not None
                      else 1.0 + (self.threshold - 1.0) * 0.5)
        self.raised = False
        self.last: Optional[float] = None
        self._high = 0
        self._low = 0

    def update(self, ratio: Optional[float]) -> Optional[str]:
        """Fold one sampled ratio; "raised"/"cleared" on a state
        transition, else None. A None ratio (degenerate sample) leaves
        the counters untouched."""
        if ratio is None:
            return None
        self.last = float(ratio)
        if not self.raised:
            self._high = self._high + 1 if ratio >= self.threshold else 0
            if self._high >= self.rounds:
                self.raised = True
                self._high = 0
                self._low = 0
                return "raised"
        else:
            self._low = self._low + 1 if ratio <= self.clear else 0
            if self._low >= self.rounds:
                self.raised = False
                self._high = 0
                self._low = 0
                return "cleared"
        return None


class AnomalyWatch:
    """Rolling-median round-wall anomaly detector (edge-triggered)."""

    def __init__(self, factor: float = 3.0, window: int = 32,
                 min_rounds: Optional[int] = None) -> None:
        self.factor = max(float(factor), 0.0)
        self.window = max(int(window), 2)
        # arm only once the window holds enough normal rounds for the
        # median to mean something
        self.min_rounds = (int(min_rounds) if min_rounds is not None
                           else max(self.window // 4, 3))
        self._walls: deque = deque(maxlen=self.window)
        self._in_anomaly = False
        self.fired: List[Dict[str, Any]] = []

    def update(self, wall_ms: float) -> Optional[Dict[str, float]]:
        """Fold one round wall. Returns ``{"ratio", "median_ms"}`` when
        this wall opens an anomaly (previous round was normal and this
        one deviates > factor x trailing median); None otherwise.
        Anomalous walls never enter the trailing window."""
        wall = float(wall_ms)
        if wall < 0:
            return None
        if len(self._walls) >= self.min_rounds and self.factor > 0:
            med = _median(self._walls)
            if med > 0 and wall > self.factor * med:
                was = self._in_anomaly
                self._in_anomaly = True
                if was:
                    return None          # still inside the same burst
                hit = {"ratio": round(wall / med, 3),
                       "median_ms": round(med, 3)}
                self.fired.append(hit)
                return hit
        self._in_anomaly = False
        self._walls.append(wall)
        return None
