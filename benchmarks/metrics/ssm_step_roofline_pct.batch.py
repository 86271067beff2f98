"""The recurrent state the traced decode steps advance (``state_slots`` x 9
Mamba layers x 358,400 B, read and written) over (device time under the
scope ``ssm_step``) x the chip's HBM peak."""

from benchmarks import phi4flash_counts


def read(ctx):
    return phi4flash_counts.ssm_step_roofline_pct(ctx)
