"""Reduction of a profiler trace to device numbers.

The trace is read into a plain dict (``{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``) so that the
arithmetic runs, and is tested, on a small recorded trace without JAX.

On a TPU the device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line
holds one event per executed HLO instruction (named by the instruction's
text) and the host's ``python3`` line holds the ``TraceAnnotation`` spans the
benchmark put round the program's calls. Host and device clocks of one
trace were seen about 1 ms apart (my chip run, PR 23), so a gap is named by
the span that covers its middle and gaps under 2 ms are not named.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# Control-flow instructions span the operations of their bodies, which the
# same line lists too: they count towards busy time once (the union) and
# are left out of the list of operations by time.
WRAPPER = re.compile(r"^%?(while|conditional|call)[.\d]* = ")
COLLECTIVE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(-start|-done)?\b")
Interval = Tuple[float, float]


def load_xplane(trace_dir: str) -> Dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as the plain dict."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        keep_all = DEVICE_PLANE.match(plane.name) is not None
        lines = []
        for line in plane.lines:
            if keep_all:
                if line.name != OPS_LINE:
                    continue
                events = [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events]
            else:
                events = [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events if e.name.startswith("bench:")]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` that no interval of the merged
    ``b`` covers."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_label(text: str) -> str:
    """``%fusion.173 = bf16[2048,64,8,128]{...} fusion(...)`` ->
    ``fusion.173 bf16[2048,64,8,128]``: the instruction's name and result
    shape, which survive from run to run."""
    m = re.match(r"%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?", text)
    if not m:
        return text[:64]
    return (m.group(1) + (" " + m.group(2) if m.group(2) else ""))[:64]


def device_ops(trace: Dict) -> Dict[int, List[Tuple[str, float, float]]]:
    """Per device ordinal: (name, start_s, end_s) of every operation."""
    out: Dict[int, List[Tuple[str, float, float]]] = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        for line in plane["lines"]:
            if line["name"] != OPS_LINE:
                continue
            out.setdefault(int(m.group(1)), []).extend(
                (n, s * 1e-9, (s + d) * 1e-9) for n, s, d in line["events"])
    return out


def host_spans(trace: Dict) -> List[Tuple[str, float, float]]:
    """The benchmark's own ``bench:<name>`` annotations on the host."""
    out = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            out.extend((n[len("bench:"):], s * 1e-9, (s + d) * 1e-9)
                       for n, s, d in line["events"]
                       if n.startswith("bench:"))
    return sorted(out, key=lambda x: x[1])


def reduce(trace: Dict, outside: str = "outside any span",
           min_gap_s: float = 0.002, top: int = 10) -> Optional[Dict]:
    """Busy time, idle share, exposed collective time, the operations that
    took most time and the longest idle gaps named by the host span that
    covers them. The traced window is the span from the first operation's
    start to the last one's end over all devices; ``None`` when no
    operation ran on a device."""
    ops = device_ops(trace)
    if not any(ops.values()):
        return None
    t0 = min(s for evs in ops.values() for _, s, _ in evs)
    t1 = max(e for evs in ops.values() for _, _, e in evs)
    spans = host_spans(trace)
    busy_each, exposed_each = [], []
    by_op: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for dev in sorted(ops):
        evs = ops[dev]
        busy = union([(s, e) for _, s, e in evs])
        busy_each.append(total(busy))
        coll = union([(s, e) for n, s, e in evs if COLLECTIVE.search(n)])
        comp = union([(s, e) for n, s, e in evs
                      if not COLLECTIVE.search(n) and not WRAPPER.match(n)])
        exposed_each.append(total(subtract(coll, comp)))
        if dev == min(ops):
            for n, s, e in evs:
                if WRAPPER.match(n):
                    continue
                key = op_label(n)
                by_op[key] = by_op.get(key, 0.0) + (e - s)
            edges = [(t0, t0)] + busy + [(t1, t1)]
            for (_, a), (b, _) in zip(edges, edges[1:]):
                if b - a >= min_gap_s:
                    mid = (a + b) / 2
                    name = next((n for n, s, e in spans if s <= mid < e),
                                outside)
                    gaps.append((name, b - a))
    n_dev = len(busy_each)
    window = t1 - t0
    busy_s = sum(busy_each) / n_dev
    return {
        "window_s": window, "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window),
        "collective_exposed_s": sum(exposed_each) / n_dev,
        "device_ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps, key=lambda kv: -kv[1])[:top],
    }
