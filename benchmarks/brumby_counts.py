"""Useful work of the Brumby cells, from the model's shapes: what the
published mathematics needs for the tokens served, and not what an
implementation spends. The retention's OPERATIONS are the recurrent form's
over the published 8,256 monomials a head (a state update a key-value head,
a read a query head; the quadratic pairs inside a sub-chunk are the
implementation's and are not counted); its BYTES are the state as held,
9,216 rows a head, read and written. Peaks come from ``peaks.py``;
``progtrace.share_pct`` divides and refuses a share over 100. The readers
in ``metrics/`` call these; each returns ``None`` where there is nothing to
read (no TPU trace, a program without the scopes or the counters)."""

from __future__ import annotations

import statistics
from typing import Dict, Optional, Sequence

from benchmarks import peaks, progtrace
# The configuration file of the cell this process runs, found as
# ``run.py`` found it (one function for every family's counts).
from benchmarks.deepseek_counts import model

SCOPES = ("retention_proj", "retention_chunk", "retention_step", "mlp",
          "head")
PREFILLS = ("jit_engine_paged_suffix", "jit_engine_paged_prefill")


# ------------------------------------------------------------- the counts


def sizes(m: Dict) -> Dict[str, int]:
    a = m["assumed"]
    return {"e": m["hidden_size"], "f": m["intermediate_size"],
            "v": m["vocab_size"], "layers": m["num_hidden_layers"],
            "h": m["num_attention_heads"], "j": m["num_key_value_heads"],
            "d": m["head_dim"], "rows": a["state_rows_published"],
            "held": a["state_rows_held"]}


def layer_params(m: Dict) -> int:
    """The matrices a token meets in one layer: q, k, v, the gate, o and
    the SwiGLU."""
    z = sizes(m)
    return (z["e"] * z["d"] * (2 * z["h"] + 2 * z["j"]) + z["e"] * z["j"]
            + 3 * z["e"] * z["f"])


def retention_flops(m: Dict) -> float:
    """One token in one layer, the recurrent form: ``phi(k) v^T`` into the
    state of each key-value head and ``phi(q)^T S`` out of it for each
    query head, 2 x rows x d each."""
    z = sizes(m)
    return 2.0 * z["rows"] * z["d"] * (z["j"] + z["h"])


def token_flops(m: Dict) -> float:
    """Every layer of one token: 2 x the matrices and the retention."""
    return sizes(m)["layers"] * (2.0 * layer_params(m) + retention_flops(m))


def head_flops(m: Dict) -> float:
    z = sizes(m)
    return 2.0 * z["e"] * z["v"]


def request_flops(m: Dict, prompt: int, answered: Sequence[int]) -> float:
    """Model operations of one request: its prompt's prefill if ``0`` is
    in ``answered`` (the head once, at its last position), one decode
    token, head and all, for every other ``j`` there."""
    total = 0.0
    for j in answered:
        total += (prompt if j == 0 else 1) * token_flops(m) + head_flops(m)
    return total


def state_slot_bytes(m: Dict) -> int:
    """One layer's state of one slot as held: ``S`` and ``z`` of every
    key-value head, float32."""
    z = sizes(m)
    return z["j"] * (z["d"] + 1) * z["held"] * 4


# ------------------------------------------------------------ the readers


def scope_of(op: Sequence) -> Optional[str]:
    """The innermost of this model's scopes an operation ``[hlo_text,
    start, dur, tf_op]`` lies under."""
    inner = [p for p in op[3].split("/") if p in SCOPES][-1:]
    return inner[0] if inner else None


def _time_under(ops: Sequence, scope: str) -> float:
    return sum(o[2] for o in ops if scope_of(o) == scope)


def _decode_launches(ctx) -> Sequence[Dict]:
    return [s for r in progtrace.sliced_rows(ctx) for s in r["slices"]
            if s["name"] == "launch" and "state_slots" in s]


def _say(ctx, a: Dict) -> None:
    """Once a traced run: device time of every program by this model's
    scopes, what PERF.md section 5 is written from."""
    if ctx.get("_brumby_said"):
        return
    ctx["_brumby_said"] = True
    by: Dict[str, Dict[str, float]] = {}
    for run in a["runs"]:
        mine = by.setdefault(run["program"], {"runs": 0, "all": 0.0})
        mine["runs"] += 1
        for o in run["ops"]:
            key = scope_of(o) or "(no scope)"
            mine[key] = mine.get(key, 0.0) + o[2]
            mine["all"] += o[2]
    steps = _decode_launches(ctx)
    if steps:
        def mean(key):
            return round(statistics.fmean(s.get(key, 0) for s in steps), 2)

        print(f"[bench] brumby: {len(steps)} decode steps in the window, "
              f"means a step: " + ", ".join(
                  f"{k} {mean(k)}" for k in (
                      "batch", "ctx_tokens", "view_pages", "state_slots")),
              flush=True)
    print("[bench] brumby: device ms by program and scope: " + str({
        prog: {k: (v if k == "runs" else round(v / 1e6, 1))
               for k, v in d.items()} for prog, d in sorted(by.items())}),
        flush=True)


def _runs(ctx, programs: Sequence[str]):
    """(run, launch) of every paired run of ``programs`` in the trace;
    ``None`` where there is no instrumented TPU trace."""
    a = progtrace.analysis(ctx)
    if a is None or not a["instrumented"]:
        return None
    _say(ctx, a)
    return [(run, ln) for run, ln in zip(a["runs"], a["pairs"])
            if run["program"] in programs and ln is not None]


def serve_mfu_pct(ctx) -> Optional[float]:
    """Model operations of the tokens credited in the window (as
    ``serve_tokens_per_s`` credits them) over the window x the chip's bf16
    peak. ``None`` off the chip."""
    dev = ctx["device"]
    if dev["platform"] != "tpu":
        return None
    m = model()
    t0, t1 = ctx["window"]
    useful = 0.0
    for o in ctx["outcomes"]:
        inside = [j for j, t in enumerate(o.arrivals) if t0 <= t < t1]
        useful += request_flops(m, o.request.prompt_len, inside)
    peak = peaks.peak(dev["kind"])["bf16_flops"]
    return progtrace.share_pct(useful, peak * dev["count"], t1 - t0,
                               "model operations of the window")


def _roofline_pct(ctx, programs: Sequence[str], counter: str, per: float,
                  scope: str, peak_key: str) -> Optional[float]:
    """Useful work of the traced runs of ``programs`` (their launch's
    ``counter`` x ``per``) over the time under ``scope`` x the chip's
    ``peak_key``."""
    runs = _runs(ctx, programs)
    if runs is None:
        return None
    useful = time_ns = 0.0
    for run, ln in runs:
        under = _time_under(run["ops"], scope)
        if not under or counter not in ln["stats"]:
            continue
        useful += float(ln["stats"][counter]) * per
        time_ns += under
    if not time_ns:
        return None     # a program without the scope or the counter
    peak = peaks.peak(ctx["device"]["kind"])[peak_key]
    return progtrace.share_pct(useful, peak, time_ns / 1e9, scope)


def retention_step_roofline_pct(ctx) -> Optional[float]:
    """The decode runs: ``state_slots`` x the layers x a slot's state a
    layer, read and written, over the time under ``retention_step`` x the
    chip's HBM peak: the same work whatever implements it."""
    m = model()
    return _roofline_pct(
        ctx, ("jit_engine_decode",), "state_slots",
        2.0 * sizes(m)["layers"] * state_slot_bytes(m), "retention_step",
        "hbm_bytes_per_s")


def retention_chunk_roofline_pct(ctx) -> Optional[float]:
    """The prefills: the recurrent form's operations of the launch's
    ``tokens`` over the time under ``retention_chunk`` x the chip's bf16
    peak."""
    m = model()
    return _roofline_pct(
        ctx, PREFILLS, "tokens", sizes(m)["layers"] * retention_flops(m),
        "retention_chunk", "bf16_flops")


def state_bytes_per_ctx_token(ctx) -> Optional[float]:
    """The seated slots' state (row key ``state_bytes``) over the tokens
    those slots hold (``kv_tokens``): mean over the window's step-log rows
    that hold any. There are no pages to add. 8 layers of keys and values
    of these heads would be 32,768 B a token at any length."""
    from benchmarks.metrics import _common

    vals = [r["state_bytes"] / r["kv_tokens"]
            for r in _common.rows_in_window(ctx)
            if r.get("kv_tokens") and "state_bytes" in r]
    return statistics.fmean(vals) if vals else None
