"""Useful work of the DeepSeek-V2 cells, from the model's shapes: what the
published mathematics needs for the tokens served, for the share of a
layer this chip holds, and not what an implementation spends (the
absorbed decode does more operations a score than the count credits; a
chunk that up-projects a latent again is credited once). Peaks come from
``peaks.py``; ``progtrace.share_pct`` divides and refuses a share over
100. The readers in ``metrics/`` call these; each returns ``None`` where
there is nothing to read (no TPU trace, a program without the counters)."""

from __future__ import annotations

import argparse
import bisect
import functools
from typing import Dict, Optional, Sequence

from benchmarks import peaks, progtrace

ATTN_SCOPES = ("latent_gather", "latent_attn")
EXPERT_SCOPES = ("moe_experts",)
SCOPES = ("latent_gather", "latent_up", "latent_attn", "moe_route",
          "moe_experts", "moe_shared")


@functools.lru_cache(maxsize=None)
def model() -> Dict:
    """The configuration file of the cell this process runs: the published
    keys at its top level, and its ``share``. ``ctx`` names neither the
    cell nor its configuration, so both are found as ``run.py`` found
    them: the ``--workload`` of this process's command line, in the
    ``BENCHMARK.json`` of its ``--bench-root``. A second configuration of
    the family is counted from its own file."""
    from benchmarks import run

    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--workload")
    ap.add_argument("--bench-root", default=run.ROOT)
    ap.add_argument("--rehearse", action="store_true")
    args, _ = ap.parse_known_args()
    if args.workload is None:
        raise ValueError("deepseek_counts: no --workload on the command "
                         "line to find the cell's configuration by")
    return run.load_cell(args.bench_root, args.workload,
                         args.rehearse)["config"]


# ------------------------------------------------------------- the counts


def mla_params(m: Dict) -> int:
    e, h = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (e * m["q_lora_rank"] + m["q_lora_rank"] * h * qk
            + e * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * h * (m["qk_nope_head_dim"]
                                       + m["v_head_dim"])
            + h * m["v_head_dim"] * e)


def expert_params(m: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def held_pairs_per_token(m: Dict) -> float:
    """(token, expert) pairs a token gives the experts held here, by
    expectation under even routing: top-k x held / all."""
    return (m["num_experts_per_tok"] * m["n_routed_experts"]
            / m["share"]["published"]["n_routed_experts"])


def token_matmul_flops(m: Dict, pairs: float) -> float:
    """Matmul operations one token needs through every layer, the head
    apart: 2 x the parameters it meets, a routed expert's for each of the
    ``pairs`` (token, expert) pairs a layer that fall to the experts held
    here."""
    e = m["hidden_size"]
    dense = m["first_k_dense_replace"]
    sparse = m["num_hidden_layers"] - dense
    per_moe = (e * m["share"]["published"]["n_routed_experts"]
               + m["n_shared_experts"] * expert_params(m)
               + pairs * expert_params(m))
    return 2.0 * (m["num_hidden_layers"] * mla_params(m)
                  + dense * 3 * e * m["intermediate_size"]
                  + sparse * per_moe)


def pairs_flops(m: Dict, pairs: float) -> float:
    """The routed experts' operations for ``pairs`` pairs, all layers'
    together (what the step log's ``moe_pairs`` counts)."""
    return 2.0 * pairs * expert_params(m)


def head_flops(m: Dict) -> float:
    return 2.0 * m["hidden_size"] * m["vocab_size"]


def attention_flops(m: Dict, first: int, n: int) -> float:
    """Scores and values of ``n`` queries at positions ``first`` ...,
    each over the keys up to its own, in the published (per-head) form: 2
    x heads x (qk width + v width) a (query, key) pair a layer."""
    keys = n * first + n * (n + 1) / 2.0
    width = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
             + m["v_head_dim"])
    return (2.0 * m["num_attention_heads"] * width * keys
            * m["num_hidden_layers"])


def request_flops(m: Dict, prompt: int, answered: Sequence[int]) -> float:
    """Model operations of one request: its prompt's prefill (one head)
    if ``0`` is in ``answered``, and one decode token (the token at
    position ``prompt + j - 1``, one head) for every other ``j`` there:
    ``answered`` lists the answer tokens that are credited. A prompt's
    tokens meet the held experts by expectation (a chunk's counters are
    not fetched); a decode token's routed experts are NOT in here: the
    decode steps counted their pairs (``pairs_flops``)."""
    total = 0.0
    for j in answered:
        if j == 0:
            total += (prompt * token_matmul_flops(m, held_pairs_per_token(m))
                      + head_flops(m) + attention_flops(m, 0, prompt))
        else:
            total += (token_matmul_flops(m, 0.0) + head_flops(m)
                      + attention_flops(m, prompt + j - 1, 1))
    return total


def latent_bytes(m: Dict, ctx_tokens: int, itemsize: int = 2) -> float:
    """The cached rows of the positions a decode step's contexts hold,
    read once a layer: 512 + 64 numbers a token."""
    return float(ctx_tokens * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
                 * itemsize * m["num_hidden_layers"])


def experts_least_s(m: Dict, pairs: float, experts_hit: float,
                    peak: Dict, itemsize: int = 2) -> float:
    """The least time the chip could take over the routed experts of a
    program run: the larger of the pairs' operations over the peak and the
    hit experts' weights over the memory's."""
    weights = experts_hit * expert_params(m) * itemsize
    return max(pairs_flops(m, pairs) / peak["bf16_flops"],
               weights / peak["hbm_bytes_per_s"])


# ------------------------------------------------------------ the readers


# Two kernels carry no scope path in a trace and are found by name: the
# grouped matmuls of ``jax.lax.ragged_dot`` become XLA's own Mosaic kernels
# (``%ragged-dot-none.N``, op_name ``ragged-dot-none``), and a Pallas
# kernel's instruction is named by its ``name=`` (``%latent_attn.N``).
KERNELS = {"ragged-dot": "moe_experts", "latent_attn": "latent_attn"}


def scope_of(op: Sequence) -> Optional[str]:
    """The innermost of this model's scopes an operation ``[hlo_text,
    start, dur, tf_op]`` lies under, or the scope its kernel belongs
    to."""
    inner = [p for p in op[3].split("/") if p in SCOPES][-1:]
    if inner:
        return inner[0]
    for prefix, scope in KERNELS.items():
        if op[3].startswith(prefix) or op[0].lstrip("%").startswith(prefix):
            return scope
    return None


def _time_under(ops: Sequence, scopes: Sequence[str]) -> float:
    return sum(o[2] for o in ops if scope_of(o) in scopes)


def _say(ctx, a: Dict) -> None:
    """Once a traced run: device time of every program by this model's
    scopes (innermost one of an operation's path), what PERF.md section 5
    is written from. ``progtrace``'s own breakdown knows llama's scopes
    only."""
    if ctx.get("_deepseek_said"):
        return
    ctx["_deepseek_said"] = True
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
            return round(sum(s[key] for s in steps) / len(steps), 2)

        print(f"[bench] deepseek: {len(steps)} decode steps in the window,"
              f" means a step: batch {mean('batch')}, ctx_tokens "
              f"{mean('ctx_tokens')}, view_pages {mean('view_pages')}, "
              f"moe_pairs {mean('moe_pairs')}, moe_experts_hit "
              f"{mean('moe_experts_hit')}, moe_max_load "
              f"{mean('moe_max_load')}", flush=True)
        ends = [o.tokens[-8:] for o in ctx.get("outcomes", [])
                if getattr(o, "tokens", None)][:4]
        print(f"[bench] deepseek: the last tokens of four answers: {ends}",
              flush=True)
    print("[bench] deepseek: device ms by program and scope: " + str({
        prog: {k: (v if k == "runs" else round(v / 1e6, 1))
               for k, v in d.items()} for prog, d in sorted(by.items())}),
        flush=True)


def _counted_launches(ctx) -> Sequence[Dict]:
    """The window's decode ``launch`` slices that carry the model's
    counters (step-log rows)."""
    return [s for r in progtrace.sliced_rows(ctx) for s in r["slices"]
            if s["name"] == "launch" and "moe_pairs" in s]


def _decode_runs(ctx):
    """(run, launch) of every paired ``jit_engine_decode`` run in the
    trace; ``None`` where there is no instrumented TPU trace."""
    a = progtrace.analysis(ctx)
    if a is None or not a["instrumented"]:
        return None
    _say(ctx, a)
    return a, [(run, ln) for run, ln in zip(a["runs"], a["pairs"])
               if run["program"] == "jit_engine_decode" and ln is not None]


def _counters_after(host: Sequence, starts: Sequence[float], t0: float
                    ) -> Optional[Dict]:
    """The model's counters of the launch that began at ``t0``: they ride
    on the first ``engine:sample_emit`` after it (a launch's own
    annotation closes before its output is fetched)."""
    i = bisect.bisect_left(starts, t0)
    while i < len(host):
        name, _, _, stats = host[i]
        if name == progtrace.HOST_PREFIX + "sample_emit":
            return stats if "moe_pairs" in stats else None
        if name == progtrace.HOST_PREFIX + "launch" and host[i][1] > t0 \
                and stats.get("program") == "decode":
            return None
        i += 1
    return None


def serve_mfu_pct(ctx) -> Optional[float]:
    """Model operations of the tokens credited in the window (as
    ``serve_tokens_per_s`` credits them: a prompt with its first token's
    arrival, an answer token with its own) over the window x the chip's
    bf16 peak; the routed experts of the decode tokens by the pairs the
    window's decode steps counted (``moe_pairs``), not by expectation.
    ``None`` off the chip."""
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


def latent_attn_roofline_pct(ctx) -> Optional[float]:
    """Useful latent bytes of the traced decode runs (their launch's
    ``ctx_tokens`` x 576 x 2 B x layers) over the time under the scopes
    ``latent_gather`` + ``latent_attn`` x the chip's HBM peak."""
    got = _decode_runs(ctx)
    if got is None:
        return None
    _, runs = got
    m = model()
    useful = time_ns = 0.0
    for run, ln in runs:
        under = _time_under(run["ops"], ATTN_SCOPES)
        if not under:
            continue
        useful += latent_bytes(m, int(ln["stats"].get("ctx_tokens", 0)))
        time_ns += under
    if not time_ns:
        return None     # a program without the scopes
    peak = peaks.peak(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return progtrace.share_pct(useful, peak, time_ns / 1e9,
                               "latent_gather+latent_attn")


def moe_experts_roofline_pct(ctx) -> Optional[float]:
    """Over the traced decode runs whose counters are in the trace: the
    least time their routed experts could take (``experts_least_s``, from
    ``moe_pairs`` and ``moe_experts_hit``) over the time under the scope
    ``moe_experts``."""
    got = _decode_runs(ctx)
    if got is None:
        return None
    a, runs = got
    m = model()
    peak = peaks.peak(ctx["device"]["kind"])
    host = a["trace"]["host"]
    starts = [e[1] for e in host]
    least = time_ns = 0.0
    for run, ln in runs:
        under = _time_under(run["ops"], EXPERT_SCOPES)
        c = _counters_after(host, starts, ln["t0"])
        if not under or c is None:
            continue
        least += experts_least_s(m, float(c["moe_pairs"]),
                                 float(c["moe_experts_hit"]), peak)
        time_ns += under
    if not time_ns:
        return None
    return progtrace.share_pct(least, 1.0, time_ns / 1e9, "moe_experts")


def moe_tokens_per_expert_mean(ctx) -> Optional[float]:
    """(token, expert) pairs a held expert that was hit got, over the
    window's decode steps: ``moe_pairs`` over ``moe_experts_hit`` of the
    step-log rows' ``launch`` slices."""
    steps = _counted_launches(ctx)
    hit = sum(s["moe_experts_hit"] for s in steps)
    return sum(s["moe_pairs"] for s in steps) / hit if hit else None
