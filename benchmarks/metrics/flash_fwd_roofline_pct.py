"""Useful causal attention FLOPs of the forward over (device time of the
``flash_fwd`` kernels) x the chip's bf16 peak, whole train steps in the
trace; a forward that remat runs again is useful once."""

from benchmarks import kernel_counts


def read(ctx):
    return kernel_counts.flash_roofline_pct(ctx, ("flash_fwd",),
                                            backward=False)
