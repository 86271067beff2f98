"""The least time the routed experts of the traced decode steps could take
(the larger of their pairs' operations over the bf16 peak and the hit
experts' weights over the HBM peak) over the device time under the scope
``moe_experts``: Nemotron-H's latent widths."""

from benchmarks import nemotron_h_counts


def read(ctx):
    return nemotron_h_counts.moe_experts_roofline_pct(ctx)
