"""The state the traced decode steps advance (``state_slots`` x 5 layers x
4,194,304 B a slot a layer, read and written) over (device time under the
scope ``ssd_step``) x the chip's HBM peak."""

from benchmarks import nemotron_h_counts


def read(ctx):
    return nemotron_h_counts.ssd_step_roofline_pct(ctx)
