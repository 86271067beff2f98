"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

The benchmark keeps its own table because a PR may change the program's
(``ray_tpu/tpu.py``). A device that is not here is an error, never a
default."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s. JAX names the chip "TPU v5 lite".
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add the "
            f"chip with its source to benchmarks/peaks.py") from None
