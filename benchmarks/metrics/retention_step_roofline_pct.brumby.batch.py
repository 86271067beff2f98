"""The state the traced decode steps advance (``state_slots`` x 8 layers x
38,043,648 B a slot a layer as held, read and written) over (device time
under the scope ``retention_step``) x the chip's HBM peak."""

from benchmarks import brumby_counts


def read(ctx):
    return brumby_counts.retention_step_roofline_pct(ctx)
