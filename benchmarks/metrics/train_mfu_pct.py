"""Operations the forward and backward passes need per token (recompute not
counted, benchmarks/flops.py) x tokens/s/chip over the chip's published bf16
peak (benchmarks/peaks.py). The rate is that of the median whole step, so the
stall of starting and stopping the profiler in the traced run is not in it."""


def read(ctx):
    from benchmarks import peaks

    dev = ctx["device"]
    if dev["platform"] != "tpu":
        return None  # a CPU has no published peak to take a share of
    import statistics

    final = ctx["final"]
    tokens = final["items"] * final["tokens_per_item"]
    rate = tokens / (statistics.median(ctx["step_ms"]) * 1e-3) / dev["count"]
    peak = peaks.peak(dev["kind"])["bf16_flops"]
    return 100.0 * final["flops_per_token"] * rate / peak
