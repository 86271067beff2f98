"""What the traced prefills' selective scans read and write (inputs,
outputs and states, from the launch's ``tokens`` and rows) over (device time
under the scope ``ssm_scan``) x the chip's HBM peak: the bandwidth share of
a loop the vector unit and launches bound (``peaks.py`` has no such peak)."""

from benchmarks import phi4flash_counts


def read(ctx):
    return phi4flash_counts.ssm_scan_roofline_pct(ctx)
