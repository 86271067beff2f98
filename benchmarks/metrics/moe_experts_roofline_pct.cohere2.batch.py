"""The least time the routed experts of the traced decode steps could take
(the larger of their pairs' operations over the bf16 peak and the hit
experts' weights over the HBM peak) over the device time under the scope
``moe_experts``: Command A+'s widths."""

from benchmarks import cohere2_moe_counts


def read(ctx):
    return cohere2_moe_counts.moe_experts_roofline_pct(ctx)
