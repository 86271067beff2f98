"""TPU detection and pod-slice topology as first-class scheduler resources.

Analogue of the reference's ``python/ray/_private/accelerators/tpu.py``
(``TPUAcceleratorManager`` :71 — chip detection :274, pod topology :198, GCE
metadata polling :49, and the ``TPU-{pod_type}-head`` gang resource :381).
Detection here is JAX-native — ask the runtime what is attached — and the
gang primitive is a real placement group over per-host bundles rather than
a synthetic head resource.

A chip belongs to ONE process at a time. The process that asks JAX for its
devices holds every chip it can see until it exits; a second process that
needs one of them fails at backend start-up (libtpu's multi-process
lockfile). So the cluster hands chips out explicitly: the node supervisor
gives a ``TPU: n`` lease n particular local chips through libtpu's
visibility variables (:func:`visible_chip_env`) and pins every other
worker to the CPU platform (``core/node.py::_spawn_env``).
"""

from __future__ import annotations

import glob
import logging
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

from ray_tpu.core.errors import RayTpuError
from ray_tpu.util import flightrec

logger = logging.getLogger(__name__)

# chips per TPU-VM host for common generations (v4/v5p: 4 chips/host;
# v5e/v6e: up to 8 chips/host depending on slice shape).
_CHIPS_PER_HOST_DEFAULT = 4

# Per-chip peak dense bf16 matmul FLOP/s, keyed by the EXACT
# ``jax.Device.device_kind`` string. A kind is listed once it has been seen
# on a machine this repo ran on; anything else is an error, not a default.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip).
_PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e; kind string as reported by libtpu 0.0.34
}


class TpuProbeError(RayTpuError):
    """The machine has accelerator device files but the chip probe could
    not initialize the TPU runtime (busy chip, broken runtime, timeout)."""


class ChipLeaseError(RayTpuError):
    """A ``TPU: n`` lease cannot be mapped onto n particular local chips."""


def accelerator_device_files() -> List[str]:
    """Device files a TPU chip shows up as: ``/dev/accel*`` (older VM
    images) or numbered VFIO groups ``/dev/vfio/<n>`` (v5e and newer)."""
    return sorted(glob.glob("/dev/accel*")
                  + [p for p in glob.glob("/dev/vfio/*")
                     if os.path.basename(p).isdigit()])


def detect_chip_count(timeout_s: float = 120.0) -> Tuple[int, Optional[str]]:
    """Return (local chip count, pod type) without initializing JAX in
    THIS process. Returns (0, pod_type) when the process is pinned to the
    CPU platform or the machine has no accelerator device files.

    The count comes from a SUBPROCESS that initializes the TPU runtime,
    prints what it sees and exits — so the chips are free again before
    the first TPU worker starts, and a wedged runtime cannot poison this
    process's backend-init lock. On a machine that HAS device files, a
    probe that fails or times out is an error carrying the probe's
    stderr (most often: some process, possibly this one, already holds
    the chip) — never a silent CPU-only cluster, and never a chip count
    taken from ``TPU_ACCELERATOR_TYPE`` that nobody saw."""
    pod_type = os.environ.get("TPU_ACCELERATOR_TYPE")  # e.g. "v5litepod-4"
    platforms = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if platforms and "tpu" not in platforms.split(","):
        return 0, pod_type  # explicitly pinned elsewhere: nothing to probe
    dev_files = accelerator_device_files()
    if not dev_files:
        return 0, pod_type
    probe_src = (
        "import jax, sys\n"
        "n = sum(1 for d in jax.local_devices() if d.platform == 'tpu')\n"
        "sys.stdout.write(str(n))\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "tpu"  # a TPU failure must not fall back to cpu
    # A chip whose last holder has just exited can still be busy
    # (``open(/dev/vfio/1): Device or resource busy``: a four-chip run
    # started right after another's exit died here, PERF.md section 7):
    # such a probe is tried again every ``_BUSY_RETRY_S`` inside the same
    # ``timeout_s``; any other failure is final at once.
    t0, wall0 = time.monotonic(), time.time()
    tries = 0
    busy_wait_s = 0.0
    while True:
        left = timeout_s - (time.monotonic() - t0)
        try:
            tries += 1
            out = subprocess.run(
                [sys.executable, "-c", probe_src], capture_output=True,
                timeout=max(left, 1.0), text=True, env=env)
        except subprocess.TimeoutExpired as e:
            raise TpuProbeError(
                f"TPU probe did not finish in {timeout_s:.0f}s on a "
                f"machine with accelerator device files {dev_files} "
                f"({tries} tries); stderr tail:\n{_tail(e.stderr)}"
            ) from None
        if out.returncode == 0 and out.stdout.strip().isdigit():
            break
        waited = time.monotonic() - t0
        if _BUSY in out.stderr and waited + _BUSY_RETRY_S < timeout_s:
            logger.info("TPU probe: device busy at try %d, %.1f s waited so "
                        "far; next try in %.0f s", tries, waited,
                        _BUSY_RETRY_S)
            time.sleep(_BUSY_RETRY_S)
            busy_wait_s += _BUSY_RETRY_S
            continue
        hint = ""
        if "lockfile" in out.stderr or "already in use" in out.stderr:
            hint = (" — another process (this driver, if it has already "
                    "touched JAX) holds the chip; a chip belongs to one "
                    "process, so either the driver stays off JAX or "
                    "everything runs in the driver")
        elif _BUSY in out.stderr:
            hint = (f" — the device stayed busy through {tries} tries "
                    f"over {waited:.0f}s")
        raise TpuProbeError(
            f"TPU probe failed (exit {out.returncode}) on a machine with "
            f"accelerator device files {dev_files}{hint}; stderr tail:\n"
            f"{_tail(out.stderr)}")
    flightrec.record("setup.phase", phase="probe", t0=wall0, t1=time.time(),
                     tries=tries, busy_wait_s=busy_wait_s)
    return int(out.stdout.strip()), pod_type


_BUSY = "Device or resource busy"
_BUSY_RETRY_S = 2.0


def init_devices(since: float) -> list:
    """``jax.devices()`` of a worker that holds chips, as the
    ``device_init`` phase of the set-up record (docs/OBSERVABILITY.md,
    "Set-up phases"): the process's backend comes up here, by name, and
    not inside whichever operation would have met it first. ``since`` is
    when the caller began importing what it runs (``time.time()``); the
    phase starts there, and ``import_s`` is its share before this call."""
    import jax

    t_open = time.time()
    devices = jax.devices()
    flightrec.record("setup.phase", phase="device_init", t0=since,
                     t1=time.time(), import_s=t_open - since,
                     platform=devices[0].platform,
                     device_count=len(devices))
    return devices


def _tail(text, limit: int = 1500) -> str:
    if isinstance(text, bytes):
        text = text.decode(errors="replace")
    return (text or "")[-limit:]


def peak_flops_per_chip(kind: str) -> float:
    """Peak bf16 FLOP/s of one chip of ``kind`` (the exact
    ``device_kind`` string JAX reports). Unknown kinds raise: a
    utilization computed against another chip's peak is a wrong number
    that looks right."""
    try:
        return _PEAK_BF16_FLOPS[kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device kind {kind!r}; known "
            f"kinds: {sorted(_PEAK_BF16_FLOPS)} (add the kind with its "
            f"source to ray_tpu/tpu.py once it has been seen)") from None


# ------------------------------------------------ chips of one lease

# Lease sizes below a whole host that libtpu 0.0.34 has been SEEN to serve
# on a v5e 2x2 host: two concurrent one-chip processes, each started with
# the variables of :func:`visible_chip_env`, both ran (each reports its chip
# as device id 0). Two-chip blocks ("1,2,1") were not tried, so they are
# refused rather than guessed at. A lease of ALL local chips sets nothing
# and takes libtpu's default.
_CHIP_BOUNDS = {1: "1,1,1"}


def pick_chips(free: Sequence[int], n: int, total: int
               ) -> Optional[Tuple[int, ...]]:
    """Choose ``n`` of the node's ``free`` chip indices for one worker
    process, or None when they are not free right now. A lease is one
    chip or the whole host; any other size raises
    :class:`ChipLeaseError`."""
    if n == total:
        return tuple(range(total)) if len(free) == total else None
    if n not in _CHIP_BOUNDS or n > total:
        raise ChipLeaseError(
            f"a TPU lease is 1 chip or all {total} chips of the host "
            f"(one process each); got TPU: {n}")
    return (min(free),) if free else None


def visible_chip_env(chips: Sequence[int], total: int) -> Dict[str, str]:
    """libtpu visibility variables that make a process see exactly
    ``chips`` of the host's ``total``. Empty for a whole-host lease."""
    if len(chips) == total:
        return {}
    return {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _CHIP_BOUNDS[len(chips)],
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def pod_slice_hosts(pod_type: str) -> int:
    """Number of TPU-VM hosts in a slice, e.g. v5e-16 -> 4 hosts (4 chips/host
    assumed for pod slices; reference derives this from GCE metadata,
    ``tpu.py:198-274``)."""
    chips = int(pod_type.rsplit("-", 1)[1])
    return max(1, chips // _CHIPS_PER_HOST_DEFAULT)


def slice_placement_group(pod_type: str,
                          chips_per_host: int = _CHIPS_PER_HOST_DEFAULT,
                          extra_cpu: float = 1.0):
    """Reserve an entire pod slice as one gang: a STRICT_SPREAD placement
    group with one bundle per TPU-VM host.

    This is the scheduler-native generalization of the reference's
    ``TPU-{pod_type}-head`` resource trick (``tpu.py:362-385``): instead of a
    synthetic head resource plus implicit co-scheduling, every host of the
    slice is explicitly reserved, so trainers can pin one worker per host and
    ``jax.distributed`` forms the mesh across exactly those hosts.
    """
    from ray_tpu.core.placement import placement_group

    n_hosts = pod_slice_hosts(pod_type)
    chips = int(pod_type.rsplit("-", 1)[1])
    per_host_chips = min(chips, chips_per_host)
    bundles: List[Dict[str, float]] = [
        {"TPU": float(per_host_chips), "CPU": extra_cpu}
        for _ in range(n_hosts)
    ]
    return placement_group(bundles, strategy="STRICT_SPREAD")
