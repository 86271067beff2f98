"""What the readers of set-up's phases share (PR 55). The program leaves
one ``setup.phase`` event of its flight recorder a phase of a start, in the
process that did the work: ``{"ev": "setup.phase", "phase", "t0", "t1",
...}``, both stamps ``time.time()`` of one host, the clock ``open_wall`` is
read on (``docs/OBSERVABILITY.md`` "Set-up phases" names sites and attrs).
The recorder's files outlive their processes, so the readers find them
after ``ray_tpu.shutdown()``.

ONE timeline a run: every process's records clipped to ``[t_proc,
open_wall]`` (``t_proc = open_wall - setup_s``; whatever lies outside is
another run's, or the window's) and every second given to exactly one
phase, the innermost: a ``first_dispatch`` inside ``warm_decode`` is taken
out of it, not added to it. The tiling phases and ``unattributed`` sum to
``setup_s``; ``probe`` and the compiler's counters are views of seconds
counted elsewhere. A program that leaves no such event (the parent of PR
55) gives every reader here nothing to read: ``None``."""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Tuple

from benchmarks import progtrace

EVENT = "setup.phase"
# Phases whose own interval is a piece of the timeline.
SPANS = ("device_init", "weights", "engine_build", "warm_decode",
         "first_dispatch")


def edges(ctx) -> Tuple[float, float]:
    """``(t_proc, open_wall)``: the benchmark process's start and the
    window's opening, on the host's wall clock."""
    open_wall = (ctx["marks"]["open_wall"] if ctx["kind"] == "serve"
                 else ctx["final"]["open_wall"])
    return open_wall - ctx["end_to_end"]["setup_s"], open_wall


def events(ctx) -> List[Dict]:
    """The ``setup.phase`` events of THIS run that touch its set-up, oldest
    first, each with the ``pid`` of its recorder's file: this process's
    own, and those of the workers that answered its ``placement.begin``
    (same name, same cluster). The recorder's directory is shared by
    every process under one temporary directory, and runs AT THE SAME
    TIME do write there: the tier-1 tests run six at once, traced
    rehearsals among them, so the clip in time alone is not enough.
    ``ctx["setup_events"]`` and ``ctx["setup_pid"]`` stand in for the
    files and this process (tests)."""
    t_proc, open_wall = edges(ctx)
    found = ctx.get("setup_events")
    if found is None:
        from ray_tpu.util import flightrec

        flightrec.flush_now()
        # A file last flushed before this process began holds none of it.
        dumps = flightrec.dump_all(max_age_s=time.time() - t_proc)
        found = [dict(e, pid=doc["pid"]) for doc in dumps.values()
                 for e in doc["events"]]
    found = [e for e in found if e.get("ev") == EVENT
             and e["t1"] >= t_proc and e["t0"] <= open_wall]
    me = ctx.get("setup_pid", os.getpid())
    asked = {_asking(e) for e in found
             if e["pid"] == me and e["phase"] == "placement.begin"}
    pids = {me} | {e["pid"] for e in found if e["phase"] == "placement.end"
                   and _asking(e) in asked}
    return sorted((e for e in found if e["pid"] in pids),
                  key=lambda e: e["t0"])


def _asking(e: Dict) -> Tuple[str, str]:
    return e["name"], e["cluster"]


def placements(evs: List[Dict]) -> List[Tuple[float, float]]:
    """Each ``placement.begin`` (the asking process) joined to the LAST
    ``placement.end`` (a worker) before the next asking, on the one host
    clock; an asking nobody answered raises."""
    out = []
    begins = [e["t0"] for e in evs if e["phase"] == "placement.begin"]
    for asked, until in zip(begins, begins[1:] + [float("inf")]):
        ends = [e["t0"] for e in evs if e["phase"] == "placement.end"
                and asked <= e["t0"] < until]
        if not ends:
            raise progtrace.MissingName(
                f"placement.begin at {asked:.3f} has no placement.end: "
                f"the worker's recorder file is missing or was not "
                f"flushed")
        out.append((asked, max(ends)))
    return out


def tile(intervals: List[Tuple[float, float, str]], lo: float, hi: float
         ) -> Dict[str, float]:
    """Seconds of ``[lo, hi]`` a phase: each instant goes to the interval
    that covers it and began last (of nested ones, the innermost), and to
    ``unattributed`` where none does."""
    clipped = [(max(a, lo), min(b, hi), p) for a, b, p in intervals
               if min(b, hi) > max(a, lo)]
    cuts = sorted({lo, hi} | {t for a, b, _ in clipped for t in (a, b)})
    out: Dict[str, float] = {"unattributed": 0.0}
    for a, b in zip(cuts, cuts[1:]):
        over = [(s, -e, p) for s, e, p in clipped if s <= a and b <= e]
        phase = max(over)[2] if over else "unattributed"
        out[phase] = out.get(phase, 0.0) + (b - a)
    return out


def split(ctx) -> Optional[Dict[str, float]]:
    """The run's set-up, seconds a phase, or None without records. Kept on
    ``ctx``: eleven readers share one reading."""
    if "_setup_split" in ctx:
        return ctx["_setup_split"]
    evs = events(ctx)
    out = None
    if evs:
        t_proc, open_wall = edges(ctx)
        spans = [(e["t0"], e["t1"], e["phase"]) for e in evs
                 if e["phase"] in SPANS]
        # The harness's imports before ``init`` are runtime start too.
        spans += [(t_proc, e["t1"], "runtime_start") for e in evs
                  if e["phase"] == "runtime_start"]
        spans += [(a, b, "placement") for a, b in placements(evs)]
        ready = [e["t1"] for e in evs if e["phase"] == "ready"]
        if ready:
            spans.append((min(ready), open_wall, "before_window"))
        out = tile(spans, t_proc, open_wall)
        probes = [min(e["t1"], open_wall) - max(e["t0"], t_proc)
                  for e in evs if e["phase"] == "probe"]
        if probes:
            out["probe"] = sum(probes)
        out.update(_compiler(ctx, evs, open_wall))
    ctx["_setup_split"] = out
    return out


def _compiler(ctx, evs: List[Dict], open_wall: float) -> Dict[str, float]:
    """The compiler's own counters before the window. A replica: what
    ``ready`` had counted, plus the first dispatches that ended after it
    (those before it are in its count already). A trainer: the worker's
    snapshot at the window's close, which had no compile in it."""
    ready = {e["pid"]: e for e in evs if e["phase"] == "ready"}
    if ready:
        counted = list(ready.values()) + [
            e for e in evs if e["phase"] == "first_dispatch"
            and e["pid"] in ready
            and ready[e["pid"]]["t1"] < e["t1"] <= open_wall]
    elif ctx["kind"] == "train":
        counted = [ctx["final"]["compiles"]]
    else:
        return {}
    return {key: sum(e[key] for e in counted)
            for key in ("compiles", "compile_s", "cache_hits")}


def total(ctx, *phases: str) -> Optional[float]:
    """Sum of the named parts of the split; None where the run left no record
    of any of them."""
    parts = split(ctx)
    if parts is None or not any(p in parts for p in phases):
        return None
    return sum(parts.get(p, 0.0) for p in phases)
