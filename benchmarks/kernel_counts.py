"""Useful work of the program's kernels, from their shapes: what the
algorithm needs, not what an implementation spends. Recomputed work and
positions read beyond a sequence's length do not count. Peaks come from
``peaks.py``; ``progtrace.share_pct`` divides and refuses a share over 100.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

from benchmarks import peaks, progtrace

FLASH_BWD_OVER_FWD = 2.5


def flash_fwd_flops(items: int, heads: int, seq: int, head_dim: int,
                    causal: bool = True) -> float:
    """Attention forward of ``items`` sequences: two matmuls (scores and
    values) of ``2 * seq * seq * head_dim`` operations a head, half of
    them above the diagonal of a causal mask."""
    full = 2 * (2 * seq * seq * head_dim) * heads * items
    return full / 2 if causal else float(full)


def flash_bwd_flops(items: int, heads: int, seq: int, head_dim: int,
                    causal: bool = True) -> float:
    """The backward's five matmuls (dV, dP, dS^T Q, dS K and the scores
    once more) against the forward's two."""
    return FLASH_BWD_OVER_FWD * flash_fwd_flops(items, heads, seq, head_dim,
                                                causal)


def paged_attn_bytes(ctx_tokens: int, kv_heads: int, head_dim: int,
                     itemsize: int = 2, layers: int = 1) -> float:
    """K and V of the positions the step's contexts hold, read once."""
    return float(ctx_tokens * 2 * kv_heads * head_dim * itemsize * layers)


def _peaks(ctx) -> Dict:
    return peaks.peak(ctx["device"]["kind"])


def _train_steps(a: Dict) -> Sequence[Dict]:
    return [r for r in a["runs"] if r["program"] == "jit_train_step"]


def flash_roofline_pct(ctx, kernels: Sequence[str], backward: bool
                       ) -> Optional[float]:
    """Useful attention FLOPs of the whole ``train_step`` runs in the trace
    over the time their ``kernels`` took times the chip's peak. The pairs
    of (layer, microbatch) are counted by the ``flash_bwd_dq`` calls: a
    forward that remat runs twice is useful once."""
    a = progtrace.analysis(ctx)
    if a is None:
        return None
    runs = _train_steps(a)
    if not runs:
        # A program from before the names (its step is ``jit_step``).
        return None
    useful = time_ns = 0.0
    for run in runs:
        calls = [(progtrace.kernel_of(o[0]), o) for o in run["ops"]]
        mine = [o for k, o in calls if k in kernels]
        pairs = [o for k, o in calls if k == "flash_bwd_dq"]
        if not mine or not pairs:
            seen = sorted({k for k, _ in calls if k})
            raise progtrace.MissingName(
                f"jit_train_step ran, but no kernel named {list(kernels)} "
                f"with flash_bwd_dq calls beside it is in the trace "
                f"(kernels seen: {seen})")
        for o in pairs:
            _, (items, heads, seq, head_dim) = progtrace.result_shape(o[0])
            count = flash_bwd_flops if backward else flash_fwd_flops
            useful += count(items, heads, seq, head_dim)
        time_ns += sum(o[2] for o in mine)
    return progtrace.share_pct(useful, _peaks(ctx)["bf16_flops"],
                               time_ns / 1e9, "/".join(kernels))


def paged_attn_roofline_pct(ctx) -> Optional[float]:
    """Useful KV bytes of the ``engine_decode`` runs in the trace (their
    launch's ``ctx_tokens``; kv heads, head size and layers from the
    gathers' own shapes) over the time under ``paged_gather`` and
    ``paged_attn`` times the chip's HBM peak."""
    a = progtrace.analysis(ctx)
    if a is None or not a["instrumented"]:
        return None
    useful = time_ns = 0.0
    for run, ln in zip(a["runs"], a["pairs"]):
        if run["program"] != "jit_engine_decode" or ln is None:
            continue
        ctx_tokens = int(ln["stats"].get("ctx_tokens", 0))
        gathers = []
        for o in run["ops"]:
            if "paged_gather" not in progtrace.scopes_of(o[3]):
                continue
            shaped = progtrace.result_shape(o[0])
            # K or V of one layer, as pages: [pages, page_tokens, kv, d].
            if shaped and len(shaped[1]) == 4 and \
                    shaped[0] in progtrace.ITEMSIZE:
                gathers.append(shaped)
        if not gathers:
            raise progtrace.MissingName(
                "jit_engine_decode ran, but no operation under the scope "
                "paged_gather is in the trace")
        for dtype, (_, _, kv_heads, head_dim) in gathers:
            # One gather is K or V of one layer: half of a layer's bytes.
            useful += paged_attn_bytes(ctx_tokens, kv_heads, head_dim,
                                       progtrace.ITEMSIZE[dtype]) / 2
        time_ns += progtrace.time_under(run["ops"],
                                        ("paged_gather", "paged_attn"))
    if not time_ns:
        raise progtrace.MissingName(
            "engine: slices are in the trace, but no paired run of "
            "jit_engine_decode")
    return progtrace.share_pct(useful, _peaks(ctx)["hbm_bytes_per_s"],
                               time_ns / 1e9, "paged_gather+paged_attn")
