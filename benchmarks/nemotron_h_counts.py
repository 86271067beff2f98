"""Useful work of the Nemotron-H cells, from the model's shapes: what the
published mathematics needs for the tokens served, for the share of a layer
this chip holds, and not what an implementation spends. A function of the
configuration and of a launch's ``tokens``, ``prefix``, ``batch`` and
``state_slots``, whatever implements the work. The Mamba-2 layer's
OPERATIONS are the recurrence's for a credited token (a state update and a
read a head, 4 x 64 x 128) and, for the chunk's roofline, the chunked
form's matmuls at the PUBLISHED sub-chunk of 128; its BYTES are the state
as held, float32, read and written. The held experts' pairs are counted by
expectation under even routing (top 22 x 128 / 512 = 5.5 a token a layer).
Peaks come from ``peaks.py``; ``progtrace.share_pct`` divides and refuses a
share over 100. The readers in ``metrics/`` call these; each returns
``None`` where there is nothing to read (no TPU trace, a program without
the scopes or the counters)."""

from __future__ import annotations

import statistics
from typing import Dict, Optional, Sequence

from benchmarks import peaks, progtrace
# The configuration file of the cell this process runs, found as
# ``run.py`` found it; the launches that carry the experts' counters, and
# the counters of one launch in the trace (one function each for every
# family's counts).
from benchmarks.deepseek_counts import (_counted_launches, _counters_after,
                                        model)
# Causal (query, key) pair counts: arithmetic, no model.
from benchmarks.mimo_counts import live_pairs

SCOPES = ("ssd_proj", "ssd_chunk", "ssd_step", "attn_proj", "full_gather",
          "paged_attn", "moe_router", "moe_latent", "moe_experts",
          "moe_shared", "head")
# The chunk's attention kernel is found by its HLO name, and so are the
# grouped matmuls of ``jax.lax.ragged_dot`` (XLA's own kernels carry no
# scope path: ``deepseek_counts.KERNELS``).
KERNELS = ("chunk_attn_full",)
PREFILLS = ("jit_engine_paged_suffix", "jit_engine_paged_prefill")


# ------------------------------------------------------------- the counts


def letters(m: Dict) -> str:
    """The letters of the layers that are run."""
    return m["hybrid_override_pattern"][:m["num_hidden_layers"]]


def layers_of(m: Dict, letter: str) -> int:
    return letters(m).count(letter)


def mamba_params(m: Dict) -> int:
    """The matrices a token meets in a Mamba-2 layer: ``W_in`` and
    ``W_out``."""
    e = m["hidden_size"]
    di = m["mamba_num_heads"] * m["mamba_head_dim"]
    bc = 2 * m["n_groups"] * m["ssm_state_size"]
    return e * (2 * di + bc + m["mamba_num_heads"]) + di * e


def attn_params(m: Dict) -> int:
    e, d = m["hidden_size"], m["head_dim"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    return e * (h + 2 * kv) * d + h * d * e


def expert_params(m: Dict) -> int:
    """One routed expert's two matrices, in the latent width."""
    return 2 * m["moe_latent_size"] * m["moe_intermediate_size"]


def experts_rest_params(m: Dict) -> int:
    """What a token meets in an expert layer outside its routed experts:
    the router at its PUBLISHED width, both latent projections and the
    shared expert."""
    e = m["hidden_size"]
    return (e * m["share"]["published"]["n_routed_experts"]
            + 2 * e * m["moe_latent_size"]
            + 2 * e * m["moe_shared_expert_intermediate_size"])


def held_pairs_per_token(m: Dict) -> float:
    """(token, expert) pairs a token gives the experts held here, by
    expectation under even routing: top-k x held / all."""
    return (m["num_experts_per_tok"] * m["n_routed_experts"]
            / m["share"]["published"]["n_routed_experts"])


def ssd_token_flops(m: Dict) -> float:
    """One token in one Mamba-2 layer by the recurrence: ``dt x B^T`` into
    the state of each head and ``S C`` out of it, 2 x 64 x 128 each."""
    return 4.0 * m["mamba_num_heads"] * m["mamba_head_dim"] \
        * m["ssm_state_size"]


def ssd_chunk_token_flops(m: Dict) -> float:
    """One token in one Mamba-2 layer by the chunked form's matmuls at the
    published sub-chunk: a head's masked product (2 x chunk x 64) and its
    state read and written (2 x 64 x 128 each), a group's ``C B^T`` (2 x
    chunk x 128)."""
    c, p, n = m["chunk_size"], m["mamba_head_dim"], m["ssm_state_size"]
    return (m["mamba_num_heads"] * (2.0 * c * p + 4.0 * p * n)
            + m["n_groups"] * 2.0 * c * n)


def token_matmul_flops(m: Dict) -> float:
    """Matmul operations one token needs through every layer, the head and
    the attention's scores apart: 2 x the parameters it meets, a routed
    expert's for each of the pairs that fall to the experts held here by
    expectation, and the Mamba-2 layers' recurrence."""
    return (layers_of(m, "M") * (2.0 * mamba_params(m) + ssd_token_flops(m))
            + layers_of(m, "*") * 2.0 * attn_params(m)
            + layers_of(m, "E") * 2.0 * (
                experts_rest_params(m)
                + held_pairs_per_token(m) * expert_params(m)))


def head_flops(m: Dict) -> float:
    return 2.0 * m["hidden_size"] * m["vocab_size"]


def attention_flops(m: Dict, first: int, n: int) -> float:
    """Scores and values of ``n`` queries from position ``first``: 2 x
    heads x (qk width + v width) a live (query, key) pair, an attention
    layer."""
    return (2.0 * m["num_attention_heads"] * 2 * m["head_dim"]
            * live_pairs(first, n, None) * layers_of(m, "*"))


def request_flops(m: Dict, prompt: int, answered: Sequence[int]) -> float:
    """Model operations of one request: its prompt's prefill if ``0`` is
    in ``answered`` (the head once, at its last position), one decode
    token, head and all, for every other ``j`` there."""
    total = 0.0
    for j in answered:
        if j == 0:
            total += (prompt * token_matmul_flops(m) + head_flops(m)
                      + attention_flops(m, 0, prompt))
        else:
            total += (token_matmul_flops(m) + head_flops(m)
                      + attention_flops(m, prompt + j - 1, 1))
    return total


def state_slot_bytes(m: Dict) -> int:
    """One Mamba-2 layer's state of one slot as held: a matrix a head,
    float32 (the convolution's tail, 1.5% of it, rides outside the
    kernel)."""
    return (m["mamba_num_heads"] * m["mamba_head_dim"]
            * m["ssm_state_size"] * 4)


def kv_token_bytes(m: Dict, itemsize: int = 2) -> int:
    """Keys and values of one token over the attention layers."""
    return (layers_of(m, "*") * m["num_key_value_heads"] * 2
            * m["head_dim"] * itemsize)


def experts_least_s(m: Dict, pairs: float, experts_hit: float,
                    peak: Dict, itemsize: int = 2) -> float:
    """The least time the chip could take over the routed experts of a
    program run: the larger of the pairs' operations over the peak and the
    hit experts' weights over the memory's."""
    weights = experts_hit * expert_params(m) * itemsize
    return max(2.0 * pairs * expert_params(m) / peak["bf16_flops"],
               weights / peak["hbm_bytes_per_s"])


# ------------------------------------------------------------ the readers


def scope_of(op: Sequence) -> Optional[str]:
    """The innermost of this model's scopes an operation ``[hlo_text,
    start, dur, tf_op]`` lies under, or the kernel it is."""
    inner = [p for p in op[3].split("/") if p in SCOPES][-1:]
    if inner:
        return inner[0]
    for name in KERNELS + ("ssd_step",):
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
    if ctx.get("_nemotronh_said"):
        return
    ctx["_nemotronh_said"] = True
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

        print(f"[bench] nemotronh: {len(steps)} decode steps in the window, "
              f"means a step: " + ", ".join(
                  f"{k} {mean(k)}" for k in (
                      "batch", "ctx_tokens", "view_pages", "live_pages",
                      "state_slots", "moe_pairs", "moe_experts_hit",
                      "moe_max_load")), flush=True)
    print("[bench] nemotronh: device ms by program and scope: " + str({
        prog: {k: (v if k == "runs" else round(v / 1e6, 1))
               for k, v in d.items()} for prog, d in sorted(by.items())}),
        flush=True)


def _runs(ctx, programs: Sequence[str]):
    """``(analysis, [(run, launch)])`` of every paired run of ``programs``
    in the trace; ``None`` where there is no instrumented TPU trace."""
    a = progtrace.analysis(ctx)
    if a is None or not a["instrumented"]:
        return None
    _say(ctx, a)
    return a, [(run, ln) for run, ln in zip(a["runs"], a["pairs"])
               if run["program"] in programs and ln is not None]


def serve_mfu_pct(ctx) -> Optional[float]:
    """Model operations of the tokens credited in the window (as
    ``serve_tokens_per_s`` credits them) over the window x the chip's bf16
    peak: the held experts' pairs by expectation, attention's live pairs,
    the Mamba-2 layers by the recurrence. ``None`` off the chip."""
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
    got = _runs(ctx, programs)
    if got is None:
        return None
    useful = time_ns = 0.0
    for run, ln in got[1]:
        under = _time_under(run["ops"], (scope,))
        if not under or counter not in ln["stats"]:
            continue
        useful += float(ln["stats"][counter]) * per
        time_ns += under
    if not time_ns:
        return None     # a program without the scope or the counter
    peak = peaks.peak(ctx["device"]["kind"])[peak_key]
    return progtrace.share_pct(useful, peak, time_ns / 1e9, scope)


def ssd_step_roofline_pct(ctx) -> Optional[float]:
    """The decode runs: ``state_slots`` x the Mamba-2 layers x a slot's
    state a layer, read and written, over the time under ``ssd_step`` x the
    chip's HBM peak: the same work whatever implements it."""
    m = model()
    return _roofline_pct(
        ctx, ("jit_engine_decode",), "state_slots",
        2.0 * layers_of(m, "M") * state_slot_bytes(m), "ssd_step",
        "hbm_bytes_per_s")


def ssd_chunk_roofline_pct(ctx) -> Optional[float]:
    """The prefills: the chunked form's matmuls, at the published
    sub-chunk, of the launch's ``tokens`` over the time under ``ssd_chunk``
    x the chip's bf16 peak."""
    m = model()
    return _roofline_pct(
        ctx, PREFILLS, "tokens",
        layers_of(m, "M") * ssd_chunk_token_flops(m), "ssd_chunk",
        "bf16_flops")


def moe_experts_roofline_pct(ctx) -> Optional[float]:
    """Over the traced decode runs whose counters are in the trace: the
    least time their routed experts could take (``experts_least_s``, from
    ``moe_pairs`` and ``moe_experts_hit``) over the time under the scope
    ``moe_experts``."""
    got = _runs(ctx, ("jit_engine_decode",))
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


def cache_bytes_per_ctx_token(ctx) -> Optional[float]:
    """The seated slots' state (row key ``state_bytes``) plus the pool
    bytes of the pages in use (``pages_full``), over the tokens those slots
    hold (``kv_tokens``): mean over the window's step-log rows that hold
    any. Five attention layers in the Mamba-2 layers' place would read
    6,144 B a token at any length."""
    from benchmarks.metrics import _common

    m = model()
    page = m["serve"]["layouts"]["default"]["kv_page_tokens"]
    vals = [(r["state_bytes"] + r["pages_full"] * page * kv_token_bytes(m))
            / r["kv_tokens"]
            for r in _common.rows_in_window(ctx)
            if r.get("kv_tokens") and "state_bytes" in r
            and "pages_full" in r]
    return statistics.fmean(vals) if vals else None
