"""Phase tracing: host spans on the profiler's clock, plus an optional
bounded span ring with Chrome ``trace_event`` export.

Spans wrap the engine's phase dispatches (``engine/*``), the scheduler's
admission, epoch formation, dispatches and joins (``service/*``),
``engine/gc_sweep`` and ``engine/reassign_k``. A span records the HOST
interval of the work it wraps and nothing else: JAX dispatch is
asynchronous, so a dispatch span is the host's cost of enqueueing the
program, and a join span (``service/wait``, ``service/backpressure``)
is the time the host spent blocked on the device. No span fences:
tracing never waits on a device value, so it never serialises the
dispatch-ahead pipeline it measures. The device's time is read from the
device trace instead, where every phase is a program named after its
function (``jit_commit_phase``, ...) and the stages inside it carry
``jax.named_scope`` names (``commit/head``, ``resolve/layout``, ...) in
their ops' metadata.

Two independent switches:

  * ``annotate=True`` enters each span as a ``jax.profiler.
    TraceAnnotation`` carrying the span's arguments (an epoch's spans
    share ``epoch=<dispatch_log index>``). With no profiler session
    open that costs about a microsecond a span; inside
    ``jax.profiler.start_trace`` / ``stop_trace`` the spans land in the
    host planes of the trace, on the same clock as the device's
    programs and ops. The engine's default tracer is
    ``PhaseTracer(enabled=False, annotate=True)``.
  * ``enabled=True`` also records every span and ``instant`` into a
    ``deque(maxlen=capacity)`` ring — a long-running service keeps the
    most recent window and counts what it dropped — exported as Chrome
    ``trace_event`` JSON (the ``{"traceEvents": [...]}`` object format):
    well-formed B/E pairs per (pid, tid) plus thread-scoped instants,
    loadable in Perfetto / ``chrome://tracing``.
    ``validate_chrome_trace`` checks the invariants CI enforces on
    exported artifacts (B/E LIFO matching, monotonic timestamps).

With both off, ``span`` returns the shared no-op ``NULL_SPAN`` and
``instant`` is a single attribute test.
"""
from __future__ import annotations

import json
import os
import time
from collections import deque
from typing import Dict, List, Optional

import jax

_US = 1e6


class _NullSpan:
    """Shared no-op span — the hot path with tracing and annotation off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **kw):
        pass


NULL_SPAN = _NullSpan()


class _AnnotatedSpan:
    """A span that only annotates the profiler's trace (``enabled``
    off): nothing is recorded in the ring."""
    __slots__ = ("_ann",)

    def __init__(self, ann):
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        return self._ann.__exit__(*exc)

    def note(self, **kw):
        pass


class _Span:
    __slots__ = ("_tracer", "name", "args", "_t0", "_ann", "_notes")

    def __init__(self, tracer: "PhaseTracer", name: str, args: Dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._ann = None
        self._notes: Optional[Dict] = None

    def note(self, **kw):
        """Attach result attributes discovered inside the span (policy
        grants, reclaim counts, ...) — they land in the E-event args."""
        if self._notes is None:
            self._notes = {}
        self._notes.update(kw)

    def __enter__(self):
        tr = self._tracer
        if tr.annotate and tr._annotation is not None:
            self._ann = tr._annotation(self.name, **self.args)
            self._ann.__enter__()
        self._t0 = tr._clock()
        tr._push("B", self.name, self._t0, self.args)
        return self

    def __exit__(self, *exc):
        tr = self._tracer
        if self._ann is not None:
            self._ann.__exit__(*exc)
        t1 = tr._clock()
        args: Dict = {"dur_ms": round((t1 - self._t0) * 1e3, 4)}
        if self._notes:
            args.update(self._notes)
        tr._push("E", self.name, t1, args)
        return False


class PhaseTracer:
    def __init__(self, capacity: int = 8192, enabled: bool = False,
                 annotate: bool = False):
        if capacity < 2:
            raise ValueError("capacity must hold at least one B/E pair")
        self.enabled = enabled
        self.annotate = annotate
        self.capacity = capacity
        self._events: deque = deque(maxlen=capacity)
        self._clock = time.perf_counter
        self._t0: Optional[float] = None
        self.dropped = 0
        self._annotation = getattr(jax.profiler, "TraceAnnotation", None)

    # -- recording ---------------------------------------------------------
    def span(self, name: str, **args):
        """Context manager for one host span; ``args`` go to the
        annotation and to the ring's B event. With both switches off it
        returns the shared no-op span."""
        if self.enabled:
            return _Span(self, name, args)
        if self.annotate and self._annotation is not None:
            return _AnnotatedSpan(self._annotation(name, **args))
        return NULL_SPAN

    def instant(self, name: str, **args) -> None:
        """Thread-scoped instant event (admission decisions etc.)."""
        if not self.enabled:
            return
        self._push("i", name, self._clock(), args)

    def _push(self, ph: str, name: str, t: float, args: Dict) -> None:
        if self._t0 is None:
            self._t0 = t
        if len(self._events) == self.capacity:
            self.dropped += 1
        self._events.append((ph, name, t, args))

    def clear(self) -> None:
        self._events.clear()
        self._t0 = None
        self.dropped = 0

    # -- export ------------------------------------------------------------
    def events(self) -> List[tuple]:
        return list(self._events)

    def span_durations(self) -> Dict[str, List[float]]:
        """Per-name closed-span wall durations (seconds), B/E matched in
        ring order — the obs report's phase-table input. Spans whose B
        fell out of the bounded ring are skipped."""
        out: Dict[str, List[float]] = {}
        open_ts: Dict[str, List[float]] = {}
        for ph, name, t, _ in self._events:
            if ph == "B":
                open_ts.setdefault(name, []).append(t)
            elif ph == "E" and open_ts.get(name):
                t0 = open_ts[name].pop()
                out.setdefault(name, []).append(t - t0)
        return out

    def to_chrome_trace(self, t0: Optional[float] = None) -> Dict:
        """Chrome ``trace_event`` object-format dict: B/E duration events
        + thread-scoped instants, timestamps in microseconds since the
        first recorded event. Pass ``t0`` (perf_counter seconds) to pin
        a shared time origin when stitching with other event sources
        (``repro.obs.flight.stitch_chrome_trace``) — it must not exceed
        the first recorded stamp or timestamps would go negative."""
        if t0 is None:
            t0 = self._t0 or 0.0
        pid, tid = os.getpid(), 1
        events = []
        depth = 0           # ring overflow drops oldest-first, which can
        #                     orphan an E at the head — skip those so the
        #                     export always carries well-formed B/E pairs
        for ph, name, t, args in self._events:
            if ph == "B":
                depth += 1
            elif ph == "E":
                if depth == 0:
                    continue
                depth -= 1
            ev = {"name": name, "ph": ph, "ts": round((t - t0) * _US, 3),
                  "pid": pid, "tid": tid, "cat": "mvcc"}
            if ph == "i":
                ev["s"] = "t"
            if args:
                ev["args"] = args
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def export(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f, indent=1)


def validate_chrome_trace(trace: Dict) -> Dict[str, int]:
    """Validate a Chrome ``trace_event`` object-format dict: every event
    carries name/ph/ts/pid/tid, timestamps are monotonic non-decreasing
    in record order, and B/E events match LIFO per (pid, tid) with no
    unmatched E and no dangling B. Nestable async events (ph b/n/e —
    the flight recorder's per-ticket lanes) must additionally carry
    ``id`` and ``cat``, and b/e match LIFO per (pid, cat, id) with no
    unmatched e and no dangling b. Counter events (ph C — the health
    monitor's gauge tracks) must carry non-empty ``args`` (the sample
    values ARE the event). Returns summary counts; raises
    ``ValueError`` on the first violation (CI gates exported artifacts
    on this)."""
    events = trace.get("traceEvents")
    if not isinstance(events, list):
        raise ValueError("traceEvents missing or not a list")
    stacks: Dict[tuple, List[str]] = {}
    async_stacks: Dict[tuple, List[str]] = {}
    last_ts = None
    n_spans = n_instants = n_async = n_counters = 0
    async_lanes = set()
    for i, ev in enumerate(events):
        for field in ("name", "ph", "ts", "pid", "tid"):
            if field not in ev:
                raise ValueError(f"event {i} missing '{field}'")
        ph, ts = ev["ph"], ev["ts"]
        if last_ts is not None and ts < last_ts:
            raise ValueError(f"event {i} ts {ts} < previous {last_ts}")
        last_ts = ts
        key = (ev["pid"], ev["tid"])
        if ph == "B":
            stacks.setdefault(key, []).append(ev["name"])
        elif ph == "E":
            stack = stacks.get(key)
            if not stack:
                raise ValueError(f"event {i}: E without open B")
            top = stack.pop()
            if top != ev["name"]:
                raise ValueError(
                    f"event {i}: E '{ev['name']}' closes B '{top}'")
            n_spans += 1
        elif ph == "i":
            n_instants += 1
        elif ph == "C":
            if not ev.get("args"):
                raise ValueError(f"event {i}: counter 'C' without args")
            n_counters += 1
        elif ph in ("b", "n", "e"):
            for field in ("id", "cat"):
                if field not in ev:
                    raise ValueError(
                        f"event {i}: async '{ph}' missing '{field}'")
            akey = (ev["pid"], ev["cat"], ev["id"])
            async_lanes.add(akey)
            if ph == "b":
                async_stacks.setdefault(akey, []).append(ev["name"])
            elif ph == "e":
                stack = async_stacks.get(akey)
                if not stack:
                    raise ValueError(f"event {i}: 'e' without open 'b' "
                                     f"in lane {akey}")
                top = stack.pop()
                if top != ev["name"]:
                    raise ValueError(
                        f"event {i}: 'e' '{ev['name']}' closes '{top}'")
                n_async += 1
        else:
            raise ValueError(f"event {i}: unknown ph '{ph}'")
    dangling = sum(len(s) for s in stacks.values())
    if dangling:
        raise ValueError(f"{dangling} B events never closed")
    dangling = sum(len(s) for s in async_stacks.values())
    if dangling:
        raise ValueError(f"{dangling} async 'b' events never closed")
    return {"spans": n_spans, "instants": n_instants,
            "async_spans": n_async, "async_lanes": len(async_lanes),
            "counters": n_counters, "events": len(events)}
