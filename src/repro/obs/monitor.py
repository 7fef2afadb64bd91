"""EWMA step monitoring (DESIGN.md §Observability).

``StepMonitor`` is the straggler/heartbeat detector (absorbed from the
former ``repro.runtime.monitor``, whose deprecation shim is now
retired): EWMA step-time tracking, straggler flagging when a step
exceeds ``straggler_factor`` x the EWMA, and a JSON heartbeat file a
supervisor can watch. The clock is injectable so straggler logic is
testable without sleeps.
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, List, Optional

from repro.obs import metrics as obs_metrics


@dataclass
class StepMonitor:
    ewma_alpha: float = 0.1
    straggler_factor: float = 3.0  # step > factor * ewma => flag
    heartbeat_path: Optional[Path] = None
    clock: Callable[[], float] = time.perf_counter

    ewma: float = 0.0
    last_step_time: float = 0.0
    stragglers: List[int] = field(default_factory=list)
    _t0: float = field(default=0.0, repr=False)
    step: int = 0

    def begin(self):
        self._t0 = self.clock()

    def end(self) -> bool:
        """Record a step; returns True if this step was a straggler."""
        dt = self.clock() - self._t0
        self.last_step_time = dt
        self.step += 1
        is_straggler = False
        if self.ewma > 0 and dt > self.straggler_factor * self.ewma:
            self.stragglers.append(self.step)
            is_straggler = True
        self.ewma = dt if self.ewma == 0 else (
            self.ewma_alpha * dt + (1 - self.ewma_alpha) * self.ewma
        )
        reg = obs_metrics.get_registry()
        if reg is not None:
            # metrics-plane bridge: monitored-step durations + straggler
            # totals land in the registry alongside the tracer artifacts
            reg.histogram(
                "fw_monitor_step_seconds",
                "durations of StepMonitor-wrapped units (path points)",
            ).observe(dt)
            reg.gauge(
                "fw_monitor_step_ewma_seconds",
                "EWMA of monitored step durations (straggler baseline)",
            ).set(self.ewma)
            if is_straggler:
                reg.counter(
                    "fw_monitor_stragglers",
                    "monitored steps exceeding straggler_factor x EWMA",
                ).inc(1)
        if self.heartbeat_path is not None:
            self.heartbeat_path.write_text(
                json.dumps(
                    {
                        "step": self.step,
                        "t": time.time(),
                        "step_time": dt,
                        "ewma": self.ewma,
                        "straggler": is_straggler,
                        "stragglers": self.stragglers,
                    }
                )
            )
        return is_straggler
