"""Useful work of the Command A+ cells, from the model's shapes: what the
published mathematics needs for the tokens served, for the share of a
layer this chip holds, and not what an implementation spends (a key tile
that is half masked is credited for its live pairs only; the decode's
queries laid over all eight key heads' lanes are credited for their own
head's). Peaks come from ``peaks.py``; ``progtrace.share_pct`` divides and
refuses a share over 100. The readers in ``metrics/`` call these; each
returns ``None`` where there is nothing to read (no TPU trace, a program
without the scopes or the counters)."""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence

from benchmarks import peaks, progtrace
# The configuration file of the cell this process runs, found as
# ``run.py`` found it; the launches that carry the experts' counters, and
# the counters of one launch in the trace (one function each for every
# family's counts).
from benchmarks.deepseek_counts import (_counted_launches, _counters_after,
                                        model)
# Causal and banded (query, key) pair counts: arithmetic, no model.
from benchmarks.mimo_counts import live_pairs

SCOPES = ("full_gather", "full_attn", "window_gather", "window_attn",
          "attn_proj", "moe_route", "moe_experts", "moe_shared")
# The chunk's Pallas kernels are found by their HLO names, and so are the
# grouped matmuls of ``jax.lax.ragged_dot`` (XLA's own kernels carry no
# scope path: ``deepseek_counts.KERNELS``).
KERNELS = ("chunk_attn_full", "chunk_attn_window")


# ------------------------------------------------------------- the counts


def windows_of(m: Dict) -> List[bool]:
    """For each layer that is run: whether it is a window layer."""
    return [t == "sliding_attention"
            for t in m["layer_types"][:m["num_hidden_layers"]]]


def attn_params(m: Dict) -> int:
    e, d = m["hidden_size"], m["head_dim"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    return e * (h + 2 * kv) * d + h * d * e


def expert_params(m: Dict) -> int:
    """One expert's three matrices, routed or shared."""
    return 3 * m["hidden_size"] * m["intermediate_size"]


def held_pairs_per_token(m: Dict) -> float:
    """(token, expert) pairs a token gives the experts held here, by
    expectation under even routing: top-k x held / all."""
    return (m["num_experts_per_tok"] * m["num_experts"]
            / m["share"]["published"]["num_experts"])


def token_matmul_flops(m: Dict, pairs: float) -> float:
    """Matmul operations one token needs through every layer, the head
    apart: 2 x the parameters it meets: attention, the router at its
    published width, the shared experts, and a routed expert's for each of
    the ``pairs`` (token, expert) pairs a layer that fall to the experts
    held here."""
    per_layer = (attn_params(m)
                 + m["hidden_size"] * m["share"]["published"]["num_experts"]
                 + (m["num_shared_experts"] + pairs) * expert_params(m))
    return 2.0 * m["num_hidden_layers"] * per_layer


def pairs_flops(m: Dict, pairs: float) -> float:
    """The routed experts' operations for ``pairs`` pairs, all layers'
    together (what the step log's ``moe_pairs`` counts)."""
    return 2.0 * pairs * expert_params(m)


def head_flops(m: Dict) -> float:
    return 2.0 * m["hidden_size"] * m["vocab_size"]


def attention_flops(m: Dict, first: int, n: int) -> float:
    """Scores and values of ``n`` queries from position ``first``: 2 x
    heads x (qk width + v width) a live (query, key) pair, a layer by its
    kind."""
    pairs = sum(live_pairs(first, n, m["sliding_window"] if w else None)
                for w in windows_of(m))
    return 2.0 * m["num_attention_heads"] * 2 * m["head_dim"] * pairs


def request_flops(m: Dict, prompt: int, answered: Sequence[int]) -> float:
    """Model operations of one request (``deepseek_counts.request_flops``):
    its prompt's prefill if ``0`` is in ``answered``, one decode token for
    every other ``j`` there. A prompt's tokens meet the held experts by
    expectation; a decode token's routed experts are NOT in here (the
    decode steps counted their pairs)."""
    total = 0.0
    for j in answered:
        if j == 0:
            total += (prompt * token_matmul_flops(m, held_pairs_per_token(m))
                      + head_flops(m) + attention_flops(m, 0, prompt))
        else:
            total += (token_matmul_flops(m, 0.0) + head_flops(m)
                      + attention_flops(m, prompt + j - 1, 1))
    return total


def kv_token_bytes(m: Dict, window: bool, itemsize: int = 2) -> int:
    """Keys and values of one token over the layers of a kind."""
    n = sum(w == window for w in windows_of(m))
    return n * m["num_key_value_heads"] * 2 * m["head_dim"] * itemsize


def experts_least_s(m: Dict, pairs: float, experts_hit: float,
                    peak: Dict, itemsize: int = 2) -> float:
    """The least time the chip could take over the routed experts of a
    program run: the larger of the pairs' operations over the peak and the
    hit experts' weights over the memory's."""
    weights = experts_hit * expert_params(m) * itemsize
    return max(pairs_flops(m, pairs) / peak["bf16_flops"],
               weights / peak["hbm_bytes_per_s"])


# ------------------------------------------------------------ the readers


def scope_of(op: Sequence) -> Optional[str]:
    """The innermost of this model's scopes an operation ``[hlo_text,
    start, dur, tf_op]`` lies under, or the kernel it is."""
    inner = [p for p in op[3].split("/") if p in SCOPES][-1:]
    if inner:
        return inner[0]
    for name in KERNELS:
        if name in op[3] or op[0].lstrip("%").startswith(name):
            return name
    if op[3].startswith("ragged-dot") or \
            op[0].lstrip("%").startswith("ragged-dot"):
        return "moe_experts"
    return None


def _time_under(ops: Sequence, scopes: Sequence[str]) -> float:
    return sum(o[2] for o in ops if scope_of(o) in scopes)


def _say(ctx, a: Dict) -> None:
    """Once a traced run: device time of every program by this model's
    scopes, what PERF.md section 5 is written from."""
    if ctx.get("_cohere2_said"):
        return
    ctx["_cohere2_said"] = True
    by: Dict[str, Dict[str, float]] = {}
    for run in a["runs"]:
        mine = by.setdefault(run["program"], {"runs": 0, "all": 0.0})
        mine["runs"] += 1
        for o in run["ops"]:
            key = scope_of(o) or "(no scope)"
            mine[key] = mine.get(key, 0.0) + o[2]
            mine["all"] += o[2]
    steps = _counted_launches(ctx)
    if steps:
        def mean(key):
            return round(statistics.fmean(s.get(key, 0) for s in steps), 2)

        print(f"[bench] cohere2: {len(steps)} decode steps in the window, "
              f"means a step: " + ", ".join(
                  f"{k} {mean(k)}" for k in (
                      "batch", "ctx_tokens", "view_pages", "live_pages",
                      "window_tokens", "window_pages", "moe_pairs",
                      "moe_experts_hit", "moe_max_load")), flush=True)
    print("[bench] cohere2: device ms by program and scope: " + str({
        prog: {k: (v if k == "runs" else round(v / 1e6, 1))
               for k, v in d.items()} for prog, d in sorted(by.items())}),
        flush=True)


def _runs(ctx, program: str, role: Optional[str] = None):
    """``(analysis, [(run, launch)])`` of every paired run of ``program``
    in the trace; ``None`` where there is no instrumented TPU trace."""
    a = progtrace.analysis(ctx)
    if a is None or not a["instrumented"]:
        return None
    _say(ctx, a)
    return a, [(run, ln) for run, ln in zip(a["runs"], a["pairs"])
               if run["program"] == program and ln is not None
               and (role is None or ln["role"] == role)]


def serve_mfu_pct(ctx) -> Optional[float]:
    """Model operations of the tokens credited in the window (as
    ``serve_tokens_per_s`` credits them) over the window x the chip's bf16
    peak: held experts only, the decode tokens' by the pairs the window's
    decode steps counted, attention's live pairs by kind. ``None`` off the
    chip."""
    dev = ctx["device"]
    if dev["platform"] != "tpu":
        return None
    m = model()
    t0, t1 = ctx["window"]
    useful = 0.0
    for o in ctx["outcomes"]:
        inside = [j for j, t in enumerate(o.arrivals) if t0 <= t < t1]
        useful += request_flops(m, o.request.prompt_len, inside)
    useful += pairs_flops(m, sum(s["moe_pairs"]
                                 for s in _counted_launches(ctx)))
    peak = peaks.peak(dev["kind"])["bf16_flops"]
    return progtrace.share_pct(useful, peak * dev["count"], t1 - t0,
                               "model operations of the window")


def decode_attn_roofline_pct(ctx, window: bool) -> Optional[float]:
    """Useful key-value bytes of the traced decode runs (their launch's
    ``ctx_tokens``, or ``window_tokens`` for the window kind, x the kind's
    bytes a token over its layers) over the time under the kind's
    ``_attn`` scope x the chip's HBM peak."""
    got = _runs(ctx, "jit_engine_decode")
    if got is None:
        return None
    m = model()
    kind = "window" if window else "full"
    key = "window_tokens" if window else "ctx_tokens"
    useful = time_ns = 0.0
    for run, ln in got[1]:
        under = _time_under(run["ops"], (f"{kind}_attn",))
        if not under or key not in ln["stats"]:
            continue
        useful += float(ln["stats"][key]) * kv_token_bytes(m, window)
        time_ns += under
    if not time_ns:
        return None     # a program without the scopes or the counter
    peak = peaks.peak(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return progtrace.share_pct(useful, peak, time_ns / 1e9, f"{kind}_attn")


def chunk_attn_roofline_pct(ctx) -> Optional[float]:
    """Useful attention operations of the traced prefill chunks (their
    launch's ``prefix`` and ``tokens``: the live (query, key) pairs of
    every layer by its kind) over the time of the ``chunk_attn_*`` kernels
    x the chip's bf16 peak."""
    got = _runs(ctx, "jit_engine_paged_suffix", role="prefill_chunk")
    if got is None:
        return None
    m = model()
    useful = time_ns = 0.0
    for run, ln in got[1]:
        under = _time_under(run["ops"], KERNELS)
        if not under or "prefix" not in ln["stats"]:
            continue
        useful += attention_flops(m, int(ln["stats"]["prefix"]),
                                  int(ln["stats"]["tokens"]))
        time_ns += under
    if not time_ns:
        return None
    peak = peaks.peak(ctx["device"]["kind"])["bf16_flops"]
    return progtrace.share_pct(useful, peak, time_ns / 1e9, "chunk_attn")


def moe_experts_roofline_pct(ctx) -> Optional[float]:
    """Over the traced decode runs whose counters are in the trace: the
    least time their routed experts could take (``experts_least_s``, from
    ``moe_pairs`` and ``moe_experts_hit``) over the time under the scope
    ``moe_experts``."""
    got = _runs(ctx, "jit_engine_decode")
    if got is None:
        return None
    a, runs = got
    m = model()
    peak = peaks.peak(ctx["device"]["kind"])
    host = a["trace"]["host"]
    starts = [e[1] for e in host]
    least = time_ns = 0.0
    for run, ln in runs:
        under = _time_under(run["ops"], ("moe_experts",))
        c = _counters_after(host, starts, ln["t0"])
        if not under or c is None:
            continue
        least += experts_least_s(m, float(c["moe_pairs"]),
                                 float(c["moe_experts_hit"]), peak)
        time_ns += under
    if not time_ns:
        return None
    return progtrace.share_pct(least, 1.0, time_ns / 1e9, "moe_experts")


def kv_bytes_per_ctx_token(ctx) -> Optional[float]:
    """Pool bytes of the pages in use, both kinds (row keys ``pages_full``,
    ``pages_window``), over the tokens the seated slots hold (``kv_tokens``),
    mean over the window's step-log rows that hold any. One kind of page
    would read the sum of both kinds' bytes a token."""
    from benchmarks.metrics import _common

    m = model()
    layout = m["serve"]["layouts"]["default"]
    page = layout["kv_page_tokens"]
    vals = [(r["pages_full"] * page * kv_token_bytes(m, False)
             + r["pages_window"] * page * kv_token_bytes(m, True))
            / r["kv_tokens"]
            for r in _common.rows_in_window(ctx)
            if r.get("kv_tokens") and "pages_window" in r]
    return statistics.fmean(vals) if vals else None
