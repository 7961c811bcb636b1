"""The set-up account: where a process's start-up seconds went, by program.

jax emits, on every first call of a jitted function, how long it traced
(``jaxpr_trace_duration``), lowered (``jaxpr_to_mlir_module_duration``) and
compiled or loaded (``backend_compile_duration``, and on a persistent-cache
hit ``cache_retrieval_time_sec`` / ``compile_time_saved_sec``), plus one
``cache_hits`` / ``cache_misses`` event a program its cache was asked for.
One pair of ``jax.monitoring`` listeners, registered when this module is
first imported (once a process; an engine registers none), books each into
the open account with a ``time.perf_counter()`` stamp.  Nothing is traced,
lowered or compiled for the account's sake: it only hears what the ONE call
an engine makes anyway has paid.

**Booked to the program that paid.**  A dispatch site reads ``ACCOUNT.booked``
before its jitted call and compares after it has returned (two attribute
reads on the steady path, where nothing is booked); when the count moved it
calls ``ACCOUNT.close(program, mark, tracer, **key)``: what was booked since
the mark becomes ONE ``program_setup`` record of that program, what lay open
before it (weight init, the cache's allocation, one-op programs) one record
of program ``other``.  Records live here, in a bounded list, because the
train engine's tracer buffer is off wherever ``telemetry.enabled`` is false;
they are mirrored to the engine's ``SpanTracer`` buffer where that is on and
summed into ``default_registry``.

**Parts do not overlap.**  A jit traced inside another emits its own trace
event before the outer one ends, so durations nest: an event whose interval
``[stamp - seconds, stamp]`` lies inside a later one's is dropped from the
sums (``traces`` counts the outermost only: one a program and shape).

``setup_account()`` is the operator's read; docs/observability.md has the
workflow.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import jax

from deepspeed_tpu.telemetry.registry import default_registry

_BOOKED = {         # jax's event -> the part it is booked as
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
    "/jax/compilation_cache/compile_time_saved_sec": "time_saved",
    "/jax/compilation_cache/cache_hits": "hits",        # events: no seconds
    "/jax/compilation_cache/cache_misses": "misses",
}
PARTS = ("trace", "lower", "compile", "cache_load")
MAX_RECORDS = 1024
MAX_INIT_SPANS = 256


def _sum_parts(events) -> dict:
    """Seconds by part of one record's events, nested intervals dropped.
    ``events`` are ``(part, seconds, stamp)`` in the order jax emitted them,
    which is the order their intervals END."""
    out = {"trace": 0.0, "lower": 0.0, "compile": 0.0, "cache_load": 0.0,
           "time_saved": 0.0, "traces": 0, "hits": 0, "misses": 0}
    stack = []                  # (start, part, seconds) of outermost intervals
    for part, seconds, stamp in events:
        if part in ("hits", "misses"):
            out[part] += 1
            continue
        if part == "time_saved":
            out[part] += seconds
            continue
        start = stamp - seconds
        loads = 0.0
        while stack and stack[-1][0] >= start:
            _, inner, inner_s = stack.pop()
            if inner == "cache_load":
                loads += inner_s
        if part == "backend_compile":
            # on jax 0.9.0 backend_compile_duration wraps
            # compile_or_get_cached, so on a hit it CONTAINS the cache
            # retrieval: compile is the difference
            if loads:
                stack.append((start, "cache_load", loads))
            stack.append((start, "compile", max(seconds - loads, 0.0)))
        else:
            stack.append((start, part, seconds))
    for _, part, seconds in stack:
        out[part] += seconds
        if part == "trace":
            out["traces"] += 1
    return out


class SetupAccount:
    """See the module docstring.  ``booked`` is the one attribute a dispatch
    site reads: the count of events ever booked."""

    def __init__(self, registry=default_registry):
        self.registry = registry
        self.booked = 0
        self._closed = 0            # events already in records
        self._open: List[tuple] = []
        self._lock = threading.Lock()
        # bounded as the tracer's buffer is: the oldest go, and are counted
        self.records: deque = deque(maxlen=MAX_RECORDS)
        self.dropped_records = 0
        self.init_spans: deque = deque(maxlen=MAX_INIT_SPANS)
        self.import_seconds: Optional[float] = None

    # ------------------------------------------------------ the listeners
    def on_duration(self, event: str, seconds: float, **_) -> None:
        part = _BOOKED.get(event)
        if part is not None:
            with self._lock:
                self._open.append((part, float(seconds),
                                   time.perf_counter()))
                self.booked += 1

    def on_event(self, event: str, **_) -> None:
        self.on_duration(event, 0.0)

    # ---------------------------------------------------------- the sites
    def close(self, program: str = "other", mark: Optional[int] = None,
              tracer=None, **key) -> None:
        """Close what is open: events booked since ``mark`` (a reading of
        ``booked`` taken before the call that paid them) as one record of
        ``program``, earlier ones as one of ``other``."""
        with self._lock:
            split = (len(self._open) if mark is None
                     else min(max(mark - self._closed, 0), len(self._open)))
            before, mine = self._open[:split], self._open[split:]
            self._open = []
            self._closed = self.booked
        if before:
            self._record("other", before, tracer, {})
        if mine:
            self._record(program, mine, tracer, key)

    def _record(self, program, events, tracer, key) -> None:
        sums = _sum_parts(events)
        # (time_saved's seconds are a saving, not an interval)
        start = min(stamp - (0.0 if part == "time_saved" else seconds)
                    for part, seconds, stamp in events)
        end = events[-1][2]
        rec = {"program": program, **key,
               "trace_s": sums["trace"], "lower_s": sums["lower"],
               "compile_s": sums["compile"],
               "cache_load_s": sums["cache_load"],
               "time_saved_s": sums["time_saved"],
               "traces": sums["traces"], "cache_hit": sums["hits"] > 0,
               "hits": sums["hits"], "misses": sums["misses"],
               "host_ns": int(start * 1e9), "wall_s": end - start}
        with self._lock:
            self.dropped_records += len(self.records) == MAX_RECORDS
            self.records.append(rec)
        seconds = self.registry.counter(
            "setup_seconds_total", "seconds jax spent tracing, lowering, "
            "compiling and loading programs from its compile cache, by "
            "part and by the program whose first call paid them")
        for part in PARTS:
            if sums[part]:
                seconds.inc(sums[part], part=part, program=program)
        self.registry.counter(
            "setup_programs_total", "program_setup records closed: first "
            "calls of a program and shape, by program (other: what was "
            "compiled outside a dispatch site)").inc(1, program=program)
        if tracer is not None and tracer.enabled:
            tracer.record("program_setup", tracer.us_of(start),
                          (end - start) * 1e6, **rec)

    # ----------------------------------------------- the engines' set-up
    def book_init(self, engine: str, part: str, seconds: float,
                  host_ns: int) -> None:
        self.init_spans.append({"engine": engine, "part": part,
                                "seconds": seconds, "host_ns": host_ns})
        self.registry.gauge(
            "init_seconds", "seconds of the newest engine's construction, "
            "by engine and part (engine_init holds the others)").set(
                seconds, engine=engine, part=part)

    def set_import_seconds(self, seconds: float) -> None:
        self.import_seconds = float(seconds)
        self.registry.gauge(
            "import_seconds", "seconds `import deepspeed_tpu` took, jax "
            "and the package's own modules").set(seconds)

    # ----------------------------------------------------- the operator
    def as_dict(self) -> dict:
        self.close()
        with self._lock:
            records = [dict(r) for r in self.records]
            init = [dict(s) for s in self.init_spans]
        seconds = {p: 0.0 for p in PARTS}
        by_program: Dict[str, dict] = {}
        hits = misses = 0
        for r in records:
            mine = by_program.setdefault(
                r["program"], {"programs": 0, **{p: 0.0 for p in PARTS}})
            mine["programs"] += 1
            for p in PARTS:
                seconds[p] += r[f"{p}_s"]
                mine[p] += r[f"{p}_s"]
            hits += r["hits"]
            misses += r["misses"]
        return {"seconds": seconds, "by_program": by_program,
                "hits": hits, "misses": misses, "records": records,
                "dropped_records": self.dropped_records,
                "import_seconds": self.import_seconds, "init_spans": init}


ACCOUNT = SetupAccount()
jax.monitoring.register_event_duration_secs_listener(ACCOUNT.on_duration)
jax.monitoring.register_event_listener(ACCOUNT.on_event)


def setup_account() -> dict:
    """The account as a dict: ``seconds`` by part, ``by_program`` (seconds
    and ``programs`` first called), ``hits`` / ``misses`` of the compile
    cache, the ``records`` themselves (newest 1,024), ``import_seconds`` and
    the engines' ``init_spans``.  A read closes what is open as ``other``."""
    return ACCOUNT.as_dict()


@contextlib.contextmanager
def init_span(tracer, part: str, engine: str):
    """One part of an engine's construction: a ``ds.<part>`` span (profiler
    annotation, buffered event where the buffer is on) whose seconds are
    booked into the account on exit, so they are there with either off."""
    t0 = time.perf_counter_ns()
    with tracer.span(part, host_ns=t0, engine=engine):
        yield
    ACCOUNT.book_init(engine, part, (time.perf_counter_ns() - t0) / 1e9, t0)
