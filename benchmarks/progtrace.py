"""The program's own names in a profiler trace, and its step slices.

``tracered.py`` reduces a trace to busy and idle time by XLA's instruction
names. This file reads what the PROGRAM wrote there (PR 24):

- host events ``engine:<slice>`` (``serve/steplog.py``: every slice of an
  engine step is a ``TraceAnnotation``; ``program``, ``tokens``, ``batch``
  and ``ctx_tokens`` are the event's stats),
- the device's per-program line ``XLA Modules``, whose events are named
  ``jit_<program>(<fingerprint>)``: ``jit_engine_decode``,
  ``jit_engine_paged_suffix``, ``jit_train_step``, ...
- the scope of every device operation. On a TPU v5e it is NOT in the event:
  it is the stat ``tf_op`` of the event's METADATA (one record per HLO
  instruction), e.g. ``jit(engine_decode)/while/body/closed_call/
  paged_gather/gather:`` (my chip run, PR 24). ``jax.profiler.ProfileData``
  shows an event's own stats only, so the file is decoded here, from the
  protobuf wire format, with nothing imported. A Pallas kernel's ``name``
  is the HLO instruction's name: ``%flash_fwd.1 = ... custom-call(...)``.

The trace becomes a plain dict (times in ns on the trace's clock)::

    {"host":     [[name, start, dur, {stat: value}], ...],   engine:* only
     "programs": [[name, start, dur], ...],                  device 0
     "ops":      [[hlo_text, start, dur, tf_op], ...]}       device 0

and all arithmetic runs on it, so it is tested on a small recorded trace
without JAX (``tests/benchmark_harness/test_progtrace.py``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
import struct
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from benchmarks import tracered

HOST_PREFIX = "engine:"
MODULES_LINE = "XLA Modules"
# One jitted program, two roles: the launch slice says which.
ROLE_PROGRAM = {"prefill_chunk": "paged_suffix"}
PREFILL_PROGRAMS = ("paged_prefill", "paged_suffix", "prefill_chunk",
                    "prefill", "suffix")
SCOPES = ("paged_gather", "paged_attn", "weight_cast", "flash_fwd",
          "flash_bwd_dkv", "flash_bwd_dq")
MIN_GAP_NS = 0.5e6        # idle intervals are attributed down to 0.5 ms
PAIR_SLACK_NS = 5e6       # host and device clocks were seen ~1 ms apart
ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "u8": 1,
            "f8e4m3fn": 1, "f8e5m2": 1}


class MissingName(RuntimeError):
    """A name the program should have written is not in a TPU trace."""


# ------------------------------------------------------- the wire format


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message; a length-delimited
    value is a view of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, val


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    """One XStat as (name, value)."""
    name, value = "", None
    for f, _, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif f in (3, 4):
            value = v - (1 << 64) if f == 4 and v >= 1 << 63 else v
        elif f == 5:
            value = _text(v)
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _plane(buf, want_ops: bool) -> Dict:
    """One XPlane: its name, and per line the events as
    (metadata id, start ns, duration ns, raw stats)."""
    name = ""
    lines, meta_raw, stat_names = [], [], {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            meta_raw.append(v)
        elif f == 5:
            sid, sname = 0, ""
            for f2, _, v2 in _fields(v):
                if f2 == 2:
                    for f3, _, v3 in _fields(v2):
                        if f3 == 1:
                            sid = v3
                        elif f3 == 2:
                            sname = _text(v3)
            stat_names[sid] = sname
    meta: Dict[int, Tuple[str, str]] = {}      # id -> (name, tf_op)
    for entry in meta_raw:
        for f2, _, v2 in _fields(entry):
            if f2 != 2:
                continue
            mid, mname, tf_op = 0, "", ""
            for f3, _, v3 in _fields(v2):
                if f3 == 1:
                    mid = v3
                elif f3 == 2:
                    mname = _text(v3)
                elif f3 == 5 and want_ops:
                    sname, sval = _stat(v3, stat_names)
                    if sname == "tf_op":
                        tf_op = sval or ""
            meta[mid] = (mname, tf_op)
    out_lines = []
    for raw in lines:
        lname, t0_ns, events = "", 0, []
        for f, _, v in _fields(raw):
            if f == 2:
                lname = _text(v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                events.append(v)
        out_lines.append((lname, t0_ns, events))
    return {"name": name, "lines": out_lines, "meta": meta,
            "stat_names": stat_names}


def _event(buf) -> Tuple[int, int, int, List]:
    mid = off_ps = dur_ps = 0
    stats = []
    for f, _, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            off_ps = v
        elif f == 3:
            dur_ps = v
        elif f == 4:
            stats.append(v)
    return mid, off_ps, dur_ps, stats


def load(trace_dir: str, device: int = 0) -> Dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as the plain dict of
    the module docstring: ``engine:*`` host events, and the programs and
    operations of one device."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    with open(paths[-1], "rb") as f:
        space = memoryview(f.read())
    host, programs, ops = [], [], []
    for f, _, raw in _fields(space):
        if f != 1:
            continue
        pname = next((_text(v) for f2, _, v in _fields(raw) if f2 == 2), "")
        m = tracered.DEVICE_PLANE.match(pname)
        if m and int(m.group(1)) != device:
            continue
        if not m and not pname.startswith("/host:"):
            continue
        plane = _plane(raw, want_ops=bool(m))
        for lname, t0_ns, events in plane["lines"]:
            if m and lname not in (tracered.OPS_LINE, MODULES_LINE):
                continue
            for ev in events:
                mid, off_ps, dur_ps, stats = _event(ev)
                ename, tf_op = plane["meta"].get(mid, ("", ""))
                start, dur = t0_ns + off_ps / 1e3, dur_ps / 1e3
                if not m:
                    if ename.startswith(HOST_PREFIX):
                        host.append([ename, start, dur, dict(
                            _stat(s, plane["stat_names"]) for s in stats)])
                elif lname == MODULES_LINE:
                    programs.append([ename.split("(")[0], start, dur])
                else:
                    ops.append([ename, start, dur, tf_op])
    host.sort(key=lambda e: e[1])
    programs.sort(key=lambda e: e[1])
    ops.sort(key=lambda e: e[1])
    return {"host": host, "programs": programs, "ops": ops}


# ------------------------------------------------- arithmetic on the dict


def scopes_of(tf_op: str) -> List[str]:
    """The program's scopes on an operation's path, outermost first."""
    return [p for p in tf_op.split("/") if p in SCOPES]


def result_shape(hlo_text: str) -> Optional[Tuple[str, Tuple[int, ...]]]:
    """``%x = (bf16[1,16,4096,128]{...}, ...) op(...)`` -> the first
    result's dtype and shape."""
    m = re.search(r" = \(?([a-z]+[0-9a-z]*)\[([\d,]*)\]", hlo_text)
    if not m:
        return None
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


def kernel_of(hlo_text: str) -> Optional[str]:
    """A Pallas kernel's ``name`` is its HLO instruction's name."""
    m = re.match(r"%?([A-Za-z_]+?)[.\d]* = ", hlo_text)
    if m and m.group(1) in SCOPES and "custom-call(" in hlo_text:
        return m.group(1)
    return None


def program_runs(trace: Dict) -> List[Dict]:
    """Every run of a program on the device, with the operations inside
    it (by time: one device runs one program at a time)."""
    ops = [o for o in trace["ops"] if not tracered.WRAPPER.match(o[0])]
    starts = [o[1] for o in ops]
    runs = []
    for name, start, dur in trace["programs"]:
        lo = bisect.bisect_left(starts, start)
        hi = bisect.bisect_right(starts, start + dur)
        inside = [o for o in ops[lo:hi] if o[1] + o[2] <= start + dur + 1]
        runs.append({"program": name, "t0": start, "t1": start + dur,
                     "ops": inside})
    return runs


def launches(trace: Dict) -> List[Dict]:
    """The ``engine:launch`` slices, each with the bracket a device run of
    its program must lie in: from the launch's start to the end of the
    first ``engine:fetch`` that starts after it (a prefill chunk has no
    fetch of its own; the decode's fetch of the same step waits for both)."""
    fetch = [e for e in trace["host"] if e[0] == HOST_PREFIX + "fetch"]
    fstarts = [e[1] for e in fetch]
    out = []
    for name, start, dur, stats in trace["host"]:
        if name != HOST_PREFIX + "launch":
            continue
        i = bisect.bisect_left(fstarts, start)
        end = fetch[i][1] + fetch[i][2] if i < len(fetch) else start + dur
        role = str(stats.get("program", ""))
        out.append({"role": role, "t0": start, "t1": end, "stats": stats,
                    "program": "jit_engine_"
                    + ROLE_PROGRAM.get(role, role)})
    return out


def pair(runs: Sequence[Dict], lns: Sequence[Dict],
         slack_ns: float = PAIR_SLACK_NS) -> List[Optional[Dict]]:
    """For every device run, the launch that brackets it (same program,
    the run inside the bracket give or take the clocks' distance), the
    latest one if several do; ``None`` where the trace began or ended in
    between."""
    out: List[Optional[Dict]] = []
    for run in runs:
        best = None
        for ln in lns:
            if (ln["program"] == run["program"]
                    and ln["t0"] - slack_ns <= run["t0"]
                    and run["t1"] <= ln["t1"] + slack_ns
                    and (best is None or ln["t0"] > best["t0"])):
                best = ln
        out.append(best)
    return out


def clock_offset_ns(runs: Sequence[Dict], pairs: Sequence[Optional[Dict]]
                    ) -> Optional[Tuple[float, float, float]]:
    """What to ADD to a device time to put it on the host's clock, as
    (least, most, estimate). A program's first operation cannot start
    before its launch starts, so the offset is at least the largest
    ``launch start - run start``; its last cannot end after its fetch
    ends, so it is at most the smallest ``fetch end - run end``. The
    estimate is the middle; where the two cross (clocks that drift, a
    mis-paired run) it still is, and the caller sees ``least > most``."""
    lo = [ln["t0"] - run["t0"] for run, ln in zip(runs, pairs) if ln]
    hi = [ln["t1"] - run["t1"] for run, ln in zip(runs, pairs) if ln]
    if not lo:
        return None
    return max(lo), min(hi), (max(lo) + min(hi)) / 2


def idle_by_slice(trace: Dict, offset_ns: float,
                  min_gap_ns: float = MIN_GAP_NS) -> Dict:
    """Every idle interval of the device of at least ``min_gap_ns``, put
    down to the host slices that overlap it, BY OVERLAP: a gap that
    ``sample_emit`` covers for 6 ms and ``park`` for 2 ms gives 6 to one
    and 2 to the other. What no slice covers is ``unattributed``."""
    busy = tracered.union([(o[1], o[1] + o[2]) for o in trace["ops"]])
    t0, t1 = busy[0][0], busy[-1][1]
    gaps = [(a + offset_ns, b + offset_ns)
            for (_, a), (b, _) in zip(busy, busy[1:])]
    short = sum(b - a for a, b in gaps if b - a < min_gap_ns)
    gaps = [g for g in gaps if g[1] - g[0] >= min_gap_ns]
    by: Dict[str, float] = {}
    covered = 0.0
    host = trace["host"]
    hstarts = [e[1] for e in host]
    each = []
    for a, b in gaps:
        mine: Dict[str, float] = {}
        # Slices are disjoint (one thread, they tile), so overlaps add.
        i = max(0, bisect.bisect_right(hstarts, a) - 1)
        while i < len(host) and host[i][1] < b:
            s, e = host[i][1], host[i][1] + host[i][2]
            over = min(b, e) - max(a, s)
            if over > 0:
                key = host[i][0][len(HOST_PREFIX):]
                mine[key] = mine.get(key, 0.0) + over
            i += 1
        for k, v in mine.items():
            by[k] = by.get(k, 0.0) + v
            covered += v
        each.append((a - offset_ns, b - offset_ns, mine))
    idle = sum(b - a for a, b in gaps)
    longest = max(each, key=lambda g: g[1] - g[0], default=(0.0, 0.0, {}))
    return {"window_ns": t1 - t0, "idle_ns": idle, "short_ns": short,
            "by_slice_ns": by, "unattributed_ns": max(0.0, idle - covered),
            "longest_gap_ns": longest[1] - longest[0],
            "longest_gap_by_ns": longest[2],
            # (start, end, by slice) of every gap, on the device's clock
            "gaps": each}


def time_under(ops: Sequence, scopes: Sequence[str]) -> float:
    """Device time (ns) of the operations under any of ``scopes``."""
    return sum(o[2] for o in ops if set(scopes_of(o[3])) & set(scopes))


def share_pct(useful: float, peak_rate: float, time_s: float,
              what: str) -> float:
    """``useful`` work over what the chip could do in ``time_s``. Over
    100 the count is wrong (or the time leaves work out): an error, never
    a clamp."""
    pct = 100.0 * useful / (peak_rate * time_s)
    if pct > 100.0:
        raise ValueError(
            f"{what}: {useful:.4g} useful in {time_s:.6g} s is {pct:.1f}% "
            f"of the peak {peak_rate:.4g}/s: the count is wrong")
    return pct


# ------------------------------------------------------ step-log slices


def slice_ms(row: Dict, names: Sequence[str]) -> float:
    return sum((s["t1"] - s["t0"]) * 1e3 for s in row.get("slices", [])
               if s["name"] in names)


def decodes(row: Dict) -> bool:
    return any(p["phase"] == "decode" for p in row["phases"])


def sliced_rows(ctx) -> List[Dict]:
    """The window's step-log rows, if the program writes slices."""
    from benchmarks.metrics import _common

    return [r for r in _common.rows_in_window(ctx) if r.get("slices")]


def median_ms(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def host_ms(row: Dict) -> float:
    """A step's length less the time its host waited for the device."""
    return (row["t1"] - row["t0"]) * 1e3 - slice_ms(row, ("fetch",))


def step_host_ms_p50(ctx) -> Optional[float]:
    """Median ``host_ms`` of the window's steps that decode."""
    return median_ms([host_ms(r) for r in sliced_rows(ctx) if decodes(r)])


def prefill_useful_ratio(rows: Sequence[Dict]) -> Optional[float]:
    """1 - prompt tokens whose prefill a preemption threw away over the
    prompt tokens of every prefill launch."""
    launched = sum(s.get("tokens", 0) for r in rows
                   for s in r.get("slices", [])
                   if s["name"] == "launch"
                   and s.get("program") in PREFILL_PROGRAMS)
    if not launched:
        return None
    discarded = sum(e.get("prefilled", 0) for r in rows
                    for e in r.get("events", []) if e["kind"] == "preempt")
    return 1.0 - discarded / launched


# -------------------------------------------- one analysis a traced run


def analysis(ctx) -> Optional[Dict]:
    """The traced run's trace, read once for all the readers: ``None``
    where there is no TPU trace (untraced, or the CPU rehearsal)."""
    if ctx.get("trace") is None or not ctx.get("trace_dir"):
        return None
    if "_progtrace" in ctx:
        return ctx["_progtrace"]
    trace = load(ctx["trace_dir"])
    runs = program_runs(trace)
    lns = launches(trace)
    pairs = pair(runs, lns)
    out = {"trace": trace, "runs": runs, "pairs": pairs,
           "instrumented": bool(trace["host"]), "offset": None,
           "idle": None}
    if out["instrumented"]:
        off = clock_offset_ns(runs, pairs)
        if off is None:
            raise MissingName(
                "engine: slices are in the trace, but no device run of a "
                "jit_engine_ program lies inside a launch/fetch bracket; "
                f"programs seen: {sorted({r['program'] for r in runs})}")
        out["offset"] = off
        out["idle"] = idle_by_slice(trace, off[2])
        _say(out, sliced_rows(ctx))
    ctx["_progtrace"] = out
    return out


def _say(a: Dict, rows: Sequence[Dict]) -> None:
    """The breakdown PERF.md section 5 is written from."""
    idle, (lo, hi, est) = a["idle"], a["offset"]
    win = idle["window_ns"]

    def ms(d):
        return {k: round(v / 1e6, 3) for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])}

    names = sorted({s["name"] for r in rows for s in r["slices"]})
    print(f"[bench] progtrace: {len(rows)} step-log rows in the window; "
          f"host ms a step by slice (mean / max): "
          + str({n: [round(statistics.fmean(slice_ms(r, (n,))
                                            for r in rows), 3),
                     round(max(slice_ms(r, (n,)) for r in rows), 1)]
                 for n in names}), flush=True)
    print(f"[bench] progtrace: device clock + {est / 1e6:.3f} ms = host "
          f"clock (bounds {lo / 1e6:.3f} .. {hi / 1e6:.3f}); traced span "
          f"{win / 1e6:.1f} ms, idle {idle['idle_ns'] / 1e6:.1f} ms in gaps "
          f">= 0.5 ms (+ {idle['short_ns'] / 1e6:.1f} ms in shorter ones); "
          f"idle by slice, ms: {ms(idle['by_slice_ns'])}; under no slice "
          f"{idle['unattributed_ns'] / 1e6:.3f} ms", flush=True)
    runs = a["runs"]
    starts = [r["t0"] for r in runs]
    top = []
    for g0, g1, mine in sorted(idle["gaps"], key=lambda g: g[0] - g[1])[:6]:
        i = bisect.bisect_left(starts, g0)      # runs[i - 1] began before it
        top.append([round((g1 - g0) / 1e6, 1),
                    runs[i - 1]["program"][4:] if i else "(trace start)",
                    runs[i]["program"][4:] if i < len(runs) else
                    "(trace end)", ms(mine)])
    print(f"[bench] progtrace: longest gaps [ms, program before, program "
          f"after, by slice]: {top}", flush=True)
    by_prog: Dict[str, List[float]] = {}
    by_scope: Dict[str, float] = {}
    for run, ln in zip(a["runs"], a["pairs"]):
        key = run["program"] + (f" as {ln['role']}" if ln and ln["role"]
                                in ROLE_PROGRAM else "")
        by_prog.setdefault(key, []).append((run["t1"] - run["t0"]) / 1e6)
        for o in run["ops"]:
            for s in scopes_of(o[3])[-1:]:
                by_scope[run["program"] + "/" + s] = by_scope.get(
                    run["program"] + "/" + s, 0.0) + o[2]
    print("[bench] progtrace: device time by program (runs, median ms, "
          "total ms): " + str({k: [len(v), round(statistics.median(v), 3),
                                   round(sum(v), 1)]
                               for k, v in sorted(by_prog.items())})
          + "; by scope, ms: " + str(ms(by_scope)), flush=True)


def unattributed_pct(ctx) -> Optional[float]:
    """Share of the traced span that is idle (gaps >= 0.5 ms) and under no
    ``engine:`` slice."""
    a = analysis(ctx)
    if a is None or not a["instrumented"]:
        return None
    return 100.0 * a["idle"]["unattributed_ns"] / a["idle"]["window_ns"]


def device_ms_p50(ctx, program: str, role: Optional[str] = None
                  ) -> Optional[float]:
    """Median device time of one run of ``program`` (``jit_engine_...``),
    of the runs whose launch says ``role`` if one is given."""
    a = analysis(ctx)
    if a is None or not a["instrumented"]:
        return None
    vals = [(run["t1"] - run["t0"]) / 1e6
            for run, ln in zip(a["runs"], a["pairs"])
            if run["program"] == program and ln is not None
            and (role is None or ln["role"] == role)]
    if not vals:
        raise MissingName(
            f"no run of {program}" + (f" launched as {role}" if role else "")
            + f" in the trace; programs seen: "
            f"{sorted({r['program'] for r in a['runs']})}")
    return statistics.median(vals)
