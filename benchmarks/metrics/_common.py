"""Shared arithmetic of the per-layer readers. A reader is one file,
``metrics/<metric name>.py``, with ``read(ctx) -> float | None``; it takes
its number from what the run kept (``ctx``: client outcomes, engine clocks,
step-log rows, window marks, the reduced trace, train steps) and returns
``None`` where there is nothing to read, which leaves the metric out."""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional

from benchmarks import stats


def measured(ctx) -> List:
    return [o for o in ctx.get("outcomes", [])
            if o.request.phase == "window"]


def rows_in_window(ctx) -> List[Dict]:
    w0, w1 = ctx["wall_window"]
    return [r for r in ctx.get("rows", []) if w0 <= r["t0"] and r["t1"] <= w1]


def phase_ms(row: Dict, phase: str) -> float:
    return sum((p["t1"] - p["t0"]) * 1e3 for p in row["phases"]
               if p["phase"] == phase)


def step_ms_p50(ctx, with_chunk: bool) -> Optional[float]:
    """Median length of the steps that decoded and did (or did not) also
    carry a prefill chunk or an admission prefill."""
    out = []
    for r in rows_in_window(ctx):
        kinds = {p["phase"] for p in r["phases"]}
        if "decode" not in kinds:
            continue
        if bool(kinds & {"prefill_chunk", "admit"}) == with_chunk:
            out.append((r["t1"] - r["t0"]) * 1e3)
    return statistics.median(out) if out else None


def active_slots_mean(ctx) -> Optional[float]:
    rows = rows_in_window(ctx)
    return statistics.fmean(r["active"] for r in rows) if rows else None


def compiles_in_window(ctx) -> Optional[float]:
    return float(ctx["compiles_in_window"])


def device_idle_pct(ctx) -> Optional[float]:
    t = ctx.get("trace")
    return None if t is None else t["idle_pct"]


def client_percentile(ctx, what: str, q: float) -> Optional[float]:
    outs = measured(ctx)
    if not outs:
        return None
    ttft, tpot, _ = stats.request_latencies(outs)
    vals = ttft if what == "ttft" else tpot
    return stats.percentile(vals, q) if vals else None
