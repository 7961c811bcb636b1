"""Wall-clock timers + throughput accounting.

Analog of reference utils/timer.py (SynchronizedWallClockTimer :44,
ThroughputTimer :199).  On TPU there is no CUDA-event timing; everything under
``jit`` is one fused program, so the meaningful breakdown is host-side phase
timing around the dispatch (data placement, device step, host bookkeeping).
Dispatch is asynchronous: a timer that must cover device completion is stopped
after the caller has synchronized (``jax.block_until_ready``, or a value fetch
where the value is needed anyway).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from deepspeed_tpu.utils.logging import log_dist

FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_GLOBAL_TIMER = "bwd"
STEP_GLOBAL_TIMER = "step"
TRAIN_BATCH_TIMER = "train_batch"
DATA_TIMER = "batch_input"


class SynchronizedWallClockTimer:
    """Named host timers (reference utils/timer.py:44)."""

    class Timer:
        def __init__(self, name: str):
            self.name_ = name
            self.started_ = False
            self.start_time = 0.0
            self.records: List[float] = []

        def start(self):
            assert not self.started_, f"{self.name_} already started"
            self.start_time = time.perf_counter()
            self.started_ = True

        def stop(self, record: bool = True):
            assert self.started_, f"{self.name_} not started"
            elapsed = (time.perf_counter() - self.start_time) * 1000.0
            if record:
                self.records.append(elapsed)
            self.started_ = False
            return elapsed

        def reset(self):
            self.started_ = False
            self.records = []

        def elapsed(self, reset: bool = True) -> float:
            """Total recorded msec (optionally resetting)."""
            total = sum(self.records)
            if reset:
                self.records = []
            return total

        def mean(self) -> float:
            return sum(self.records) / max(len(self.records), 1)

    def __init__(self):
        self.timers: Dict[str, SynchronizedWallClockTimer.Timer] = {}

    def __call__(self, name: str) -> "SynchronizedWallClockTimer.Timer":
        if name not in self.timers:
            self.timers[name] = self.Timer(name)
        return self.timers[name]

    def log(self, names: List[str], normalizer: float = 1.0,
            reset: bool = True, ranks: Optional[List[int]] = None):
        """Print 'name: msec' for each timer (reference timer.py log :168)."""
        assert normalizer > 0.0
        parts = []
        for name in names:
            if name in self.timers:
                elapsed = self.timers[name].elapsed(reset=reset) / normalizer
                parts.append(f"{name}: {elapsed:.2f}")
        if parts:
            log_dist("time (ms) | " + " | ".join(parts),
                     ranks=ranks or [0])


class ThroughputTimer:
    """Samples/sec + tokens/sec tracking (reference utils/timer.py:199).

    ``update_epoch_count``-style bookkeeping is dropped; the engine feeds
    (batch_size, seq_len) per step and reads smoothed rates.
    ``steps_per_output`` gates a rate log line every N counted steps
    (reference :222 prints its throughput summary at the same cadence);
    0 disables the output, matching the reference's None default.
    """

    def __init__(self, steps_per_output: int = 0, warmup_steps: int = 1):
        self.warmup_steps = warmup_steps
        self.steps_per_output = steps_per_output
        self.global_steps = 0
        self.total_time = 0.0
        self.total_samples = 0
        self.total_tokens = 0
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self, batch_size: int, tokens: int = 0):
        if self._t0 is None:
            return
        dt = time.perf_counter() - self._t0
        self._t0 = None
        self.global_steps += 1
        if self.global_steps > self.warmup_steps:
            self.total_time += dt
            self.total_samples += batch_size
            self.total_tokens += tokens
            if (self.steps_per_output
                    and self.global_steps % self.steps_per_output == 0):
                self._log_rates(batch_size, tokens, dt)

    def _log_rates(self, batch_size: int, tokens: int, dt: float):
        parts = [f"step={self.global_steps}",
                 f"samples/sec={batch_size / dt:.2f} "
                 f"(avg {self.avg_samples_per_sec:.2f})"]
        if tokens:
            parts.append(f"tokens/sec={tokens / dt:.1f} "
                         f"(avg {self.avg_tokens_per_sec:.1f})")
        parts.append(f"step_time_ms={dt * 1e3:.1f}")
        log_dist("throughput: " + " ".join(parts), ranks=[0])

    @property
    def avg_samples_per_sec(self) -> float:
        return self.total_samples / self.total_time if self.total_time else 0.0

    @property
    def avg_tokens_per_sec(self) -> float:
        return self.total_tokens / self.total_time if self.total_time else 0.0
