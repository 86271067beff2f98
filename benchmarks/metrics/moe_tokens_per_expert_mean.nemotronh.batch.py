"""(token, expert) pairs a held expert that was hit computed, over the
window's decode steps: ``moe_pairs`` / ``moe_experts_hit`` of the step-log
rows' ``launch`` slices. Beside a chunk's 88 tokens an expert it says how
near the cell comes to a deployment's load."""

# The count knows no model: deepseek's cells' reader, on this cell's rows.
from benchmarks import deepseek_counts


def read(ctx):
    return deepseek_counts.moe_tokens_per_expert_mean(ctx)
