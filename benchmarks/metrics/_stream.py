"""What the readers of the stream records share. The program closes one
record a streamed request (``ray_tpu/serve/replica.py::StreamQueue``) and
enters it in the step log as a ``stream-end`` event, so a traced run has it
on its rows (``ctx["rows"]``); ``docs/OBSERVABILITY.md`` "Stream record"
names the fields. All stamps are ``time.time()`` of one host. A program
that writes no such event (the parent of PR 39) gives every reader here
nothing to read: ``None``."""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

from benchmarks import progtrace, stats
from benchmarks.metrics import _common


def records(ctx) -> List[Dict]:
    """The records of the requests this cell measures that succeeded: an
    open loop's window requests, a closed loop's requests that ended
    inside the window. Joined by ``request_id``; a request without a
    record is left out."""
    by_id = {e["request"]: e for row in ctx.get("rows", [])
             for e in row.get("events", []) if e["kind"] == "stream-end"}
    outs = _common.measured(ctx)
    if not outs:
        t_open, t_close = ctx["window"]
        outs = [o for o in ctx.get("outcomes", [])
                if o.request.phase == "closed" and o.ended is not None
                and t_open <= o.ended < t_close]
    return [by_id[o.request_id] for o in outs
            if o.ok and o.request_id in by_id]


def _values_ms(ctx, of: Callable[[Dict], Optional[float]]) -> List[float]:
    vals = [of(r) for r in records(ctx)]
    return [v * 1e3 for v in vals if v is not None]


def median_ms(ctx, of: Callable[[Dict], Optional[float]]) -> Optional[float]:
    return progtrace.median_ms(_values_ms(ctx, of))


def percentile_ms(ctx, of: Callable[[Dict], Optional[float]], q: float
                  ) -> Optional[float]:
    vals = _values_ms(ctx, of)
    return stats.percentile(vals, q) if vals else None


def between(later: str, earlier: str) -> Callable[[Dict], Optional[float]]:
    """``record[later] - record[earlier]``, or None where a stamp is
    missing (no acknowledged delivery; spans off, so no ``received``)."""
    def of(r: Dict) -> Optional[float]:
        a, b = r.get(later), r.get(earlier)
        return None if a is None or b is None else a - b
    return of


def token_delivery_s(r: Dict) -> Optional[float]:
    """Mean ``put`` -> acknowledged time of the request's deliveries after
    the first; None with fewer than two acknowledged."""
    if r["acked"] < 2:
        return None
    return (r["deliver_s_sum"] - r["first_deliver_s"]) / (r["acked"] - 1)


def items_per_pull(ctx) -> Optional[float]:
    recs = records(ctx)
    pulls = sum(r["pulls"] for r in recs)
    return sum(r["items"] for r in recs) / pulls if pulls else None
