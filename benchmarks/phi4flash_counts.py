"""Useful work of the Phi-4-flash cells, from the model's shapes: what the
published mathematics needs for the tokens served, and not what an
implementation spends (the cross-decoder is counted where its output is
read: for a decode token and a prompt's LAST position; the shared cache is
counted once a reading layer whether its pages are gathered once or eight
times). Peaks come from ``peaks.py``; ``progtrace.share_pct`` divides and
refuses a share over 100. The readers in ``metrics/`` call these; each
returns ``None`` where there is nothing to read (no TPU trace, a program
without the scopes or the counters)."""

from __future__ import annotations

import statistics
from typing import Dict, Optional, Sequence

from benchmarks import peaks, progtrace
# The configuration file of the cell this process runs, found as
# ``run.py`` found it (one function for every family's counts).
from benchmarks.deepseek_counts import model

SCOPES = ("ssm_conv", "ssm_scan", "ssm_step", "gmu", "window_gather",
          "window_attn", "full_gather", "full_attn", "cross_attn")
# The chunk's Pallas kernel, found by its HLO name where no scope is.
KERNELS = ("chunk_attn_window",)


# ------------------------------------------------------------- the counts


def sizes(m: Dict) -> Dict[str, int]:
    """The layer counts and widths the counts below read."""
    n, e = m["num_hidden_layers"], m["hidden_size"]
    a = m["assumed"]
    return {"e": e, "f": m["intermediate_size"], "v": m["vocab_size"],
            "di": a["expand"] * e, "n": a["d_state"], "r": a["dt_rank"],
            "k": a["d_conv"], "heads": m["num_attention_heads"],
            "d": e // m["num_attention_heads"],
            "kv": m["num_key_value_heads"] * (e // m["num_attention_heads"]),
            "mamba": n // 4 + 1, "window": n // 4, "back": n // 4 - 1,
            "window_tokens": m["sliding_window"]}


def mlp_params(z: Dict) -> int:
    return 3 * z["e"] * z["f"]


def self_decoder_flops(m: Dict) -> float:
    """Matmul operations EVERY token needs: the Mamba and window layers
    whole, and the full layer's key-value projection (2 x the parameters it
    meets; the scan's and the convolution's elementwise work, ~1 MFLOP a
    layer beside 240, is left out)."""
    z = sizes(m)
    mamba = (z["e"] * 2 * z["di"] + z["di"] * (z["r"] + 2 * z["n"])
             + z["r"] * z["di"] + z["di"] * z["e"] + mlp_params(z))
    window = z["e"] * (z["e"] + 2 * z["kv"]) + z["e"] * z["e"] \
        + mlp_params(z)
    return 2.0 * (z["mamba"] * mamba + z["window"] * window
                  + z["e"] * 2 * z["kv"])


def cross_decoder_flops(m: Dict) -> float:
    """Matmul operations of a token whose output is READ (a decode token, a
    prompt's last position): the full layer's queries, output projection
    and feed-forward, the GMU and cross-attention layers, the head."""
    z = sizes(m)
    attn = 2 * z["e"] * z["e"] + mlp_params(z)
    gmu = 2 * z["e"] * z["di"] + mlp_params(z)
    return 2.0 * ((1 + z["back"]) * attn + z["back"] * gmu
                  + z["e"] * z["v"])


def live_pairs(first: int, n: int, window: Optional[int]) -> float:
    """(query, key) pairs of ``n`` queries at positions ``first`` ...,
    each over the keys up to its own and, under ``window``, no further
    back than ``window`` - 1."""
    if window is None:
        return n * first + n * (n + 1) / 2.0
    short = max(0, min(n, window - 1 - first))
    return (short * first + short * (short + 1) / 2.0
            + (n - short) * float(window))


def pair_flops(z: Dict) -> float:
    """One (query, key) pair of one layer: every head scores over its 64
    and weighs its pair's 128 values."""
    return 2.0 * z["heads"] * 3 * z["d"]


def request_flops(m: Dict, prompt: int, answered: Sequence[int]) -> float:
    """Model operations of one request: its prompt's prefill if ``0`` is
    in ``answered`` (the cross-decoder once, at its last position), one
    decode token for every other ``j`` there."""
    z = sizes(m)
    layers_shared = 1 + z["back"]
    total = 0.0
    for j in answered:
        if j == 0:
            total += (prompt * self_decoder_flops(m)
                      + cross_decoder_flops(m) + pair_flops(z) * (
                          z["window"] * live_pairs(
                              0, prompt, z["window_tokens"])
                          + layers_shared * prompt))
        else:
            pos = prompt + j - 1
            total += (self_decoder_flops(m) + cross_decoder_flops(m)
                      + pair_flops(z) * (
                          z["window"] * min(pos + 1, z["window_tokens"])
                          + layers_shared * (pos + 1)))
    return total


def kv_token_bytes(m: Dict, itemsize: int = 2) -> int:
    """Keys and values of one token in ONE layer."""
    return 2 * sizes(m)["kv"] * itemsize


def state_slot_bytes(m: Dict, itemsize: int = 2) -> int:
    """One Mamba layer's state of one slot: the scan's in float32 and the
    convolution's last inputs."""
    z = sizes(m)
    return z["n"] * z["di"] * 4 + (z["k"] - 1) * z["di"] * itemsize


def scan_bytes(m: Dict, tokens: int, rows: int) -> float:
    """What a prefill's selective scans read and write over all Mamba
    layers: ``x``, ``dt`` and ``y`` (float32, d_inner) and ``B``, ``C``
    (float32, d_state) a token, a state in and out a row."""
    z = sizes(m)
    return z["mamba"] * 4.0 * (tokens * (3 * z["di"] + 2 * z["n"])
                               + rows * 2 * z["n"] * z["di"])


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
    return None


def _time_under(ops: Sequence, scopes: Sequence[str]) -> float:
    return sum(o[2] for o in ops if scope_of(o) in scopes)


def _decode_launches(ctx) -> Sequence[Dict]:
    return [s for r in progtrace.sliced_rows(ctx) for s in r["slices"]
            if s["name"] == "launch" and "state_slots" in s]


def _say(ctx, a: Dict) -> None:
    """Once a traced run: device time of every program by this model's
    scopes, what PERF.md section 5 is written from."""
    if ctx.get("_phi4flash_said"):
        return
    ctx["_phi4flash_said"] = True
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

        print(f"[bench] phi4flash: {len(steps)} decode steps in the window, "
              f"means a step: " + ", ".join(
                  f"{k} {mean(k)}" for k in (
                      "batch", "ctx_tokens", "view_pages", "window_tokens",
                      "window_pages", "state_slots")), flush=True)
    print("[bench] phi4flash: device ms by program and scope: " + str({
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


def _decode_roofline_pct(ctx, counter: str, per: float,
                         scopes: Sequence[str]) -> Optional[float]:
    """Useful bytes of the traced decode runs (their launch's ``counter`` x
    ``per``) over the time under ``scopes`` x the chip's HBM peak."""
    runs = _runs(ctx, ("jit_engine_decode",))
    if runs is None:
        return None
    useful = time_ns = 0.0
    for run, ln in runs:
        under = _time_under(run["ops"], scopes)
        if not under or counter not in ln["stats"]:
            continue
        useful += float(ln["stats"][counter]) * per
        time_ns += under
    if not time_ns:
        return None     # a program without the scopes or the counter
    peak = peaks.peak(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return progtrace.share_pct(useful, peak, time_ns / 1e9,
                               "+".join(scopes))


def shared_kv_roofline_pct(ctx) -> Optional[float]:
    """``ctx_tokens`` x the one cache's bytes a token x the layers that
    read it (the full layer and the cross-attention layers), over the time
    under ``full_gather`` + ``full_attn`` + ``cross_attn``."""
    m = model()
    return _decode_roofline_pct(
        ctx, "ctx_tokens", kv_token_bytes(m) * (1 + sizes(m)["back"]),
        ("full_gather", "full_attn", "cross_attn"))


def swa_roofline_pct(ctx) -> Optional[float]:
    """``window_tokens`` x a token's bytes x the window layers, over the
    time under ``window_gather`` + ``window_attn``."""
    m = model()
    return _decode_roofline_pct(
        ctx, "window_tokens", kv_token_bytes(m) * sizes(m)["window"],
        ("window_gather", "window_attn"))


def ssm_step_roofline_pct(ctx) -> Optional[float]:
    """``state_slots`` x the Mamba layers x a slot's state, read and
    written, over the time under ``ssm_step``."""
    m = model()
    return _decode_roofline_pct(
        ctx, "state_slots",
        2.0 * sizes(m)["mamba"] * state_slot_bytes(m), ("ssm_step",))


def ssm_scan_roofline_pct(ctx) -> Optional[float]:
    """The traced prefills' selective scans: their inputs, outputs and
    states in bytes (``scan_bytes`` of the launch's ``tokens`` and rows)
    over the time under ``ssm_scan`` x the chip's HBM peak. The scan is a
    loop of elementwise updates bound by the vector unit and by launches,
    for which ``peaks.py`` has no peak: this is the bandwidth share of a
    compute-bound loop."""
    runs = _runs(ctx, ("jit_engine_paged_suffix", "jit_engine_paged_prefill"))
    if runs is None:
        return None
    m = model()
    useful = time_ns = 0.0
    for run, ln in runs:
        under = _time_under(run["ops"], ("ssm_scan",))
        if not under or "tokens" not in ln["stats"]:
            continue
        rows = 1 if ln["role"] == "prefill_chunk" \
            else int(ln["stats"].get("cross_rows", 1))
        useful += scan_bytes(m, int(ln["stats"]["tokens"]), rows)
        time_ns += under
    if not time_ns:
        return None
    peak = peaks.peak(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return progtrace.share_pct(useful, peak, time_ns / 1e9, "ssm_scan")


def kv_bytes_per_ctx_token(ctx) -> Optional[float]:
    """Pool bytes of the pages in use of both kinds (row keys
    ``pages_full``, ``pages_window``) plus the seated slots' state
    (``state_bytes``), over the tokens those slots hold (``kv_tokens``):
    mean over the window's step-log rows that hold any."""
    from benchmarks.metrics import _common

    m = model()
    z = sizes(m)
    page = m["serve"]["layouts"]["default"]["kv_page_tokens"]
    vals = [(r["pages_full"] * page * kv_token_bytes(m)
             + r["pages_window"] * page * kv_token_bytes(m) * z["window"]
             + r["state_bytes"]) / r["kv_tokens"]
            for r in _common.rows_in_window(ctx)
            if r.get("kv_tokens") and "state_bytes" in r]
    return statistics.fmean(vals) if vals else None
