"""What a driver hands back to ``run.py``, and what metric readers read."""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

from harness.trace import Reduced


@dataclass
class Check:
    """One number compared with its limit; ``ok`` when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    setup_s: float
    end_to_end: dict                      # metric name -> value
    counters: dict                        # what the drivers counted
    checks: list                          # [Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    reduced: Optional[Reduced] = None     # the traced window (--trace 1)
    notes: list = field(default_factory=list)   # lines for stderr


@dataclass
class Readings:
    """What a per-layer metric reader (``metrics/<name>.py``) gets."""
    counters: dict
    trace: Optional[Reduced]
    config: dict
    peaks: dict


class CompileCounter:
    """Counts JAX compilations (backend compiles and jaxpr traces) while
    ``counting()`` is open."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self._on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, name, _secs, **_kw):
        if self._on and name in self.EVENTS:
            self.count += 1

    @contextlib.contextmanager
    def counting(self):
        self._on = True
        try:
            yield self
        finally:
            self._on = False


class GcWatch:
    """Times Python's garbage collections while ``counting()`` is open."""

    def __init__(self):
        import gc
        self._on = False
        self._t = 0.0
        self.pauses = []          # (generation, seconds)
        gc.callbacks.append(self._hear)

    def _hear(self, phase, info):
        import time
        if not self._on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    @contextlib.contextmanager
    def counting(self):
        self._on = True
        try:
            yield self
        finally:
            self._on = False

    def note(self) -> str:
        full = sum(1 for g, _ in self.pauses if g == 2)
        longest = max((s for _, s in self.pauses), default=0.0)
        return (f"garbage collections in the window: {len(self.pauses)} "
                f"({full} full), longest {longest * 1e3:.1f} ms")


class StallWatch:
    """A thread that sleeps in short steps while ``counting()`` is open
    and keeps its longest oversleep. A stall of the driving thread that
    this thread sees too held up the whole process (the host, or a call
    that kept the interpreter's lock), not just one call's wait."""

    STEP = 0.005

    def __init__(self):
        self.t0 = 0.0
        self.longest = (0.0, 0.0)      # (seconds late, seconds after t0)

    @contextlib.contextmanager
    def counting(self):
        stop = threading.Event()
        self.t0 = last = time.perf_counter()

        def watch():
            nonlocal last
            while not stop.wait(self.STEP):
                now = time.perf_counter()
                late = now - last - self.STEP
                if late > self.longest[0]:
                    self.longest = (late, now - self.t0)
                last = now
        th = threading.Thread(target=watch, daemon=True)
        th.start()
        try:
            yield self
        finally:
            stop.set()
            th.join()

    def note(self, what: str, spans: list) -> str:
        """``spans`` [(start, end, thread CPU seconds)] of the driving
        thread's calls, on the perf_counter clock: its longest beside
        this thread's longest oversleep."""
        a, b, cpu = max(spans, key=lambda x: x[1] - x[0],
                        default=(0.0, 0.0, 0.0))
        late, at = self.longest
        return (f"longest {what}: {(b - a) * 1e3:.1f} ms at "
                f"{a - self.t0:.2f} s into the window, the driving thread "
                f"on the CPU for {cpu * 1e3:.1f} ms of it; a sleeping "
                f"thread's longest oversleep: {late * 1e3:.1f} ms at "
                f"{at:.2f} s")


def settle() -> None:
    """End set-up: collect what it left and move the survivors out of
    the collector's reach, so that a collection in the window scans only
    what the window makes."""
    import gc
    gc.collect()
    gc.freeze()
