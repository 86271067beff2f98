"""Engine step timeline: a bounded ring answering "why was THIS token
slow?".

The SLO histograms say a p99 token took 300 ms; this recorder says what
the engine was doing at that moment: one row per ``DecodeEngine.step()``
with the step's phases (admission prefill, interleaved prefill chunk,
decode, and on a disaggregated decode fleet
``handoff``, the adopt splice of a prefill fleet's published KV pages,
tagged with the page count) and batch occupancy, plus
the discrete events that explain latency cliffs — page alloc/free,
recompute preemption (with the prompt tokens and pages it throws
away), jit compiles (first dispatch of a program key), and
``stream-end``: a closed stream's record (``serve/replica.py``), which
a pull's thread posts through ``DecodeEngine.post_event`` and the
loop enters at its next step.

Beside the phases a row carries ``slices``: named host intervals that
TILE the step with no hole (``reap``, ``admit``, ``pages``, ``launch``,
``fetch``, ``sample_emit``, ``finish``), led by ``park``, the time from
the end of the previous step to this one's start. Phases say what the
device was asked to do; slices say where the host's time went, so an
idle gap of the device has an owner. Every slice is also entered as a
``jax.profiler.TraceAnnotation("engine:<name>")``: in a profiler trace
the same slices lie on the profiler's clock beside the device
operations (attributes such as ``program`` and ``tokens`` become the
event's stats), and the jitted programs there are named
``jit_engine_<program>``. One pair of annotations nests where the row's
slices tile: the decode's ``engine:fetch`` opens round the
``engine:launch`` of a chunk dispatched ahead of it (``enclose``). A row
also counts what the step worked on:
``ctx_tokens`` (KV positions the decode really needs), ``view_pages``
(pages wide the view it read: the rung of the engine's ladder) and
``pages_pinned`` (pages the prefix index holds).

Recording is a deque append + one ``time.time()`` read and one
annotation per slice, per STEP (never per token), so the decode loop
pays microseconds against a device call that costs milliseconds
(``tests/test_step_slices.py`` holds it under 50 us a step).
The ring is host memory only; it
is dumped on demand through ``engine.timeline()`` -> the replica RPC ->
``python -m ray_tpu timeline --serve``, which merges every replica's
rows into the cross-process Chrome trace.

Timestamps are wall-clock (``time.time``) so rows align with the task
-event spans in the same trace; phase durations are measured with the
same clock (the ~us drift vs monotonic is far below a step).
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional


class StepTimeline:
    """Bounded per-engine step recorder. Not thread-safe by design: it
    is only touched from the engine's decode-loop thread; ``dump()``
    snapshots via list() which is atomic enough for a diagnostic read
    from the actor RPC thread (rows are immutable once appended)."""

    __slots__ = ("capacity", "_rows", "_events", "dropped", "_slices",
                 "_open", "_ann", "_annotate", "_enclose", "_outer")

    def __init__(self, capacity: int = 256):
        self.capacity = max(0, int(capacity))
        self._rows: deque = deque(maxlen=self.capacity or None)
        self._events: List[Dict[str, Any]] = []  # pending, next row's
        self.dropped = 0
        self._slices: List[Dict[str, Any]] = []  # the row being built
        self._open: Optional[Dict[str, Any]] = None  # slice being timed
        self._ann = None  # ... and its annotation on the profiler's clock
        self._enclose = None  # (name, attrs) asked for by ``enclose``
        self._outer = None    # (name, annotation) opened early for it
        self._annotate = None
        if self.capacity:
            # Only a recording engine pays the import; the timeline CLI
            # renders dumps without JAX.
            from jax.profiler import TraceAnnotation

            self._annotate = TraceAnnotation

    @property
    def enabled(self) -> bool:
        return self.capacity > 0

    @property
    def pending_events(self) -> bool:
        return bool(self._events)

    # ------------------------------------------------------------ events

    def event(self, kind: str, **attrs: Any) -> None:
        """Queue a discrete event (page alloc/free, preempt, jit
        compile); it attaches to the next recorded step row."""
        if not self.capacity:
            return
        e = {"kind": kind, "ts": time.time()}
        if attrs:
            e.update(attrs)
        self._events.append(e)

    # ------------------------------------------------------------ slices

    def begin(self, name: str, **attrs: Any) -> float:
        """End the open slice and start ``name``, on one clock read, so
        consecutive slices tile with no hole. Returns that instant.
        ``attrs`` ride on the slice and on its annotation. Callers gate
        on ``enabled``, as they do for events."""
        now = time.time()
        self._switch(name, now, attrs)
        return now

    def amend(self, name: str, program: str, **attrs: Any) -> None:
        """Add ``attrs`` to the newest slice ``name`` of ``program`` in
        the row being built: what a launch counted is known only once
        its output is fetched (and another program's launch may lie in
        between). The slice's annotation has closed by then, so the
        profiler's trace does not get them from here."""
        if not attrs:
            return
        for s in reversed(self._slices):
            if s["name"] == name and s.get("program") == program:
                s.update(attrs)
                return

    def enclose(self, name: str, **attrs: Any) -> None:
        """The slice begun next lies, ON THE PROFILER'S CLOCK ONLY,
        inside the annotation of the slice ``name`` that follows it:
        that annotation opens (with ``attrs``) when the next slice
        begins and is the one ``begin(name)`` goes on in. The row's
        slices tile as ever.

        For a dispatch whose program the device runs AFTER the one the
        host is about to wait for (a prefill chunk sent ahead, behind a
        decode whose ids are yet to be fetched): a reader of the trace
        looks for a run on the device between its ``engine:launch`` and
        the end of the first ``engine:fetch`` that starts after it
        (``benchmarks/progtrace.py::launches``). With the decode's
        ``engine:fetch`` open round the chunk's ``engine:launch`` that
        is the next step's fetch, under which the chunk does end."""
        self._enclose = (name, attrs)

    def _switch(self, name: str, now: float, attrs: Dict[str, Any]
                ) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if self._open is not None:
            self._open["t1"] = now
        s = {"name": name, "t0": now, "t1": now}
        if attrs:
            s.update(attrs)
        self._slices.append(s)
        self._open = s
        outer, self._outer = self._outer, None
        if outer is not None:
            if outer[0] == name:
                self._ann = outer[1]    # open already: go on in it
                return
            outer[1].__exit__(None, None, None)
        ask, self._enclose = self._enclose, None
        if ask is not None and ask[0] != name:
            early = self._annotate("engine:" + ask[0], **ask[1])
            early.__enter__()
            self._outer = (ask[0], early)
        self._ann = ann = self._annotate("engine:" + name, **attrs)
        ann.__enter__()

    def step_begin(self) -> float:
        """Start a step's row: ``park`` (open since the last step ended)
        closes and ``reap`` opens at the returned instant, the row's
        ``t0``. Slices begun outside a step (warm-up dispatches) are
        annotated but belong to no row."""
        park = self._open
        now = self.begin("reap")
        self._slices = [self._open]
        if park is not None and park["name"] == "park":
            self._slices.insert(0, park)
        return now

    def park(self, active: int = 0) -> None:
        """End a step that records no row: its slices are dropped and
        ``park`` opens, as ``record`` opens it after a row."""
        self._slices = []
        self._switch("park", time.time(), {"active": active})

    # -------------------------------------------------------------- rows

    def record(self, t0: float, t1: float, phases:
               List[Dict[str, Any]], active: int, prefilling: int,
               queued: int, pages_free: Optional[int] = None,
               pages_pinned: Optional[int] = None,
               ctx_tokens: Optional[int] = None,
               view_pages: Optional[int] = None,
               **counts: int) -> None:
        """One engine step: ``phases`` are the step's timed sub-slices
        ([{phase, t0, t1, ...attrs}]); occupancy is sampled at the step
        boundary; queued events ride along and clear. ``counts`` are
        further row keys (``pages_<kind>``: pages in use a page kind). The slices begun
        since ``step_begin`` close at ``t1`` and ride along too, and
        ``park`` opens for the time until the next step."""
        if not self.capacity:
            self._events.clear()
            return
        if len(self._rows) == self._rows.maxlen:
            self.dropped += 1
        row = {"t0": t0, "t1": t1, "phases": phases,
               "active": active, "prefilling": prefilling,
               "queued": queued}
        if pages_free is not None:
            row["pages_free"] = pages_free
        if pages_pinned is not None:
            row["pages_pinned"] = pages_pinned
        if ctx_tokens is not None:
            row["ctx_tokens"] = ctx_tokens
        if view_pages is not None:
            row["view_pages"] = view_pages
        row.update(counts)
        if self._open is not None:
            self._switch("park", t1, {"active": active})
            row["slices"] = self._slices[:-1]
            self._slices = self._slices[-1:]
        if self._events:
            row["events"] = self._events
            self._events = []
        self._rows.append(row)

    def dump(self) -> Dict[str, Any]:
        return {"capacity": self.capacity, "dropped": self.dropped,
                "rows": list(self._rows)}


def timeline_chrome_events(dump: Dict[str, Any], pid: str
                           ) -> List[Dict[str, Any]]:
    """Render one engine's timeline dump as Chrome trace events: phase
    slices on an ``engine-step`` track, the host slices that tile the
    step on an ``engine-host`` track below it, occupancy as counters,
    discrete events as instants. Shared by the timeline CLI and
    trace-demo."""
    out: List[Dict[str, Any]] = []
    for row in dump.get("rows", []):
        for sl in row.get("slices", []):
            out.append({
                "name": sl["name"], "cat": "engine-host", "ph": "X",
                "ts": sl["t0"] * 1e6,
                "dur": max(0.0, (sl["t1"] - sl["t0"]) * 1e6),
                "pid": pid, "tid": "engine-host",
                "args": {k: v for k, v in sl.items()
                         if k not in ("name", "t0", "t1")},
            })
        for ph in row.get("phases", []):
            out.append({
                "name": ph.get("phase", "step"),
                "cat": "engine-step", "ph": "X",
                "ts": ph["t0"] * 1e6,
                "dur": max(0.0, (ph["t1"] - ph["t0"]) * 1e6),
                "pid": pid, "tid": "engine-step",
                "args": {k: v for k, v in ph.items()
                         if k not in ("phase", "t0", "t1")},
            })
        out.append({
            "name": "occupancy", "ph": "C", "pid": pid,
            "ts": row["t0"] * 1e6,
            "args": {"active": row.get("active", 0),
                     "prefilling": row.get("prefilling", 0),
                     "queued": row.get("queued", 0)},
        })
        for e in row.get("events", []):
            out.append({
                "name": e.get("kind", "event"), "cat": "engine-event",
                "ph": "i", "s": "t", "ts": e.get("ts", row["t0"]) * 1e6,
                "pid": pid, "tid": "engine-step",
                "args": {k: v for k, v in e.items()
                         if k not in ("kind", "ts")},
            })
    return out
