"""Build the native shared library (g++ -shared), cached by source hash.

The reference builds its native layer with bazel (``BUILD.bazel``); here the
native surface is small enough that a direct g++ invocation at first import
keeps the dev loop to sub-second rebuilds. The built ``.so`` lands next to the
sources in ``build/`` (git-ignored: a checkout builds its own), with the hash
of the sources and flags it was built from stored beside it — a copy or a
checkout does not preserve mtimes, so only content decides a rebuild.
"""

from __future__ import annotations

import hashlib
import os
import shutil
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_DIR, "build")
_LOCK = threading.Lock()


def _source_hash(srcs: list, flags: list) -> str:
    h = hashlib.sha256(" ".join(flags).encode())
    for path in srcs:
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def build_library(name: str, sources: list, extra_flags: list = ()) -> str:
    """Compile ``sources`` (relative to _native/) into build/lib<name>.so,
    rebuilding only when the hash of the sources differs from the one stored
    beside the output. Returns the path."""
    out = os.path.join(_BUILD_DIR, f"lib{name}.so")
    stamp = out + ".sha256"
    srcs = [os.path.join(_DIR, s) for s in sources]
    flags = ["-O2", "-g", "-shared", "-fPIC", "-std=c++17", "-pthread",
             *extra_flags]
    want = _source_hash(srcs, flags)
    with _LOCK:
        if os.path.exists(out) and _read(stamp) == want:
            return out
        if shutil.which("g++") is None:
            raise RuntimeError(
                f"g++ not found: cannot build {out} (the native object "
                f"store is compiled at first import; install g++)")
        os.makedirs(_BUILD_DIR, exist_ok=True)
        tmp = out + f".tmp.{os.getpid()}"
        # Compiling under _LOCK is deliberate: one build per process,
        # everyone else waits for the .so instead of racing g++.
        # graftlint: disable=lock-held-blocking
        proc = subprocess.run(["g++", *flags, "-o", tmp, *srcs],
                              capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"g++ failed building {out} (exit {proc.returncode}):\n"
                f"{proc.stderr[-4000:]}")
        os.replace(tmp, out)  # atomic: concurrent builders race safely
        with open(stamp + f".tmp.{os.getpid()}", "w") as f:
            f.write(want)
        os.replace(stamp + f".tmp.{os.getpid()}", stamp)
    return out


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return ""
