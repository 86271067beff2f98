"""Useful causal attention FLOPs of the backward (2.5 x the forward's) over
(device time of the ``flash_bwd_dkv`` + ``flash_bwd_dq`` kernels) x the
chip's bf16 peak, whole train steps in the trace."""

from benchmarks import kernel_counts


def read(ctx):
    return kernel_counts.flash_roofline_pct(
        ctx, ("flash_bwd_dkv", "flash_bwd_dq"), backward=True)
