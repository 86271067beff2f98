"""Device ownership, the compile-cache helper and chip_smoke.py's refusals
(ISSUE 21). All CPU, no compile: stub processes stand in for workers."""

import os
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu import tpu
from ray_tpu.core.node import Node, WorkerHandle
from ray_tpu.core.ids import WorkerID
from ray_tpu.util import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _StubProc:
    """Popen-shaped stand-in: alive until told otherwise."""

    pid = 4242

    def __init__(self):
        self.returncode = None

    def poll(self):
        return self.returncode

    def kill(self):
        self.returncode = -9

    terminate = kill

    def wait(self, timeout=None):
        return self.returncode


def _bare_node(chips: int) -> Node:
    """A Node with just the state device ownership touches (no servers,
    no controller, no shm store)."""
    node = Node.__new__(Node)
    node._lock = threading.Lock()
    node._workers = {}
    node._idle = []
    node._waiters = []
    node._extra_env = {}
    node._chips_total = chips
    node._free_chips = list(range(chips))
    return node


def _hold(node: Node, n_chips: int) -> WorkerHandle:
    """What _fork_worker does around the spawn, with a stub process."""
    handle = WorkerHandle(WorkerID.from_random(), _StubProc())
    handle.chips = node._acquire_chips(n_chips)
    node._workers[handle.worker_id] = handle
    return handle


# --------------------------------------------------- one process per chip


def test_spawn_env_pins_cpu_without_a_tpu_lease(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    node = _bare_node(4)
    env = node._spawn_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in env
    # every worker shares the one compile cache
    assert env[compile_cache.ENV_VAR] == compile_cache.cache_dir()
    # runtime-env env_vars are applied after the pin: a deliberate override
    env = node._spawn_env(extra_vars={"JAX_PLATFORMS": "tpu"})
    assert env["JAX_PLATFORMS"] == "tpu"


def test_spawn_env_names_the_lease_chips(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    node = _bare_node(4)
    env = node._spawn_env(chips=(2,))
    assert env["JAX_PLATFORMS"] == "tpu,cpu"  # inherited, not pinned
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # a whole-host lease takes libtpu's default: nothing set
    env = node._spawn_env(chips=(0, 1, 2, 3))
    assert not any(k in env for k in ("TPU_VISIBLE_CHIPS",
                                      "TPU_CHIPS_PER_PROCESS_BOUNDS"))


def test_tpu_leases_get_disjoint_chips_that_return_on_exit():
    node = _bare_node(4)
    a, b = _hold(node, 1), _hold(node, 1)
    assert a.chips and b.chips and set(a.chips).isdisjoint(b.chips)
    assert sorted(node._free_chips + list(a.chips) + list(b.chips)) == \
        [0, 1, 2, 3]
    # all four are not free while two processes hold one each
    with pytest.raises(RuntimeError, match="free chips"):
        node._acquire_chips(4)
    # worker a exits: the next acquire sweeps it and its chip is back
    a.proc.returncode = 0
    held = b.chips
    c = _hold(node, 1)
    assert a.worker_id not in node._workers and a.chips is None
    assert set(c.chips).isdisjoint(held)
    # removal of a LIVE holder kills it before the chips are handed on
    with node._lock:
        node._remove_worker_locked(b)
    assert b.proc.returncode == -9 and b.chips is None
    with node._lock:
        node._remove_worker_locked(c)
    assert sorted(node._free_chips) == [0, 1, 2, 3]
    assert node._acquire_chips(4) == (0, 1, 2, 3)


def test_idle_tpu_worker_is_evicted_not_shared():
    """An idle pooled worker still has its chips open: a lease that cannot
    reuse it (different size) must not fork a second process onto them."""
    node = _bare_node(1)
    w = _hold(node, 1)
    w.idle = True
    node._idle.append(w)
    assert node._take_idle_worker(1, "") is w  # same size: reuse
    w.idle = True
    node._idle.append(w)
    assert node._take_idle_worker(0, "") is None  # CPU lease: no match
    assert node._acquire_chips(1) == (0,)  # evicts w, then hands chip 0 on
    assert w.proc.returncode == -9 and w.worker_id not in node._workers


def test_unsupported_lease_sizes_are_typed_refusals():
    with pytest.raises(tpu.ChipLeaseError):
        tpu.pick_chips([0, 1, 2, 3], 2, 4)
    with pytest.raises(tpu.ChipLeaseError):
        tpu.pick_chips([0], 2, 1)
    with pytest.raises(tpu.ChipLeaseError, match="whole chips"):
        Node._lease_chip_count({"TPU": 0.5})
    assert Node._lease_chip_count({"CPU": 1}) == 0
    assert Node._lease_chip_count({"TPU": 4.0}) == 4


# ------------------------------------------------------------ chip probe


def test_probe_failure_is_an_error_not_zero_chips(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    # no device files: nothing to probe, and the environment's claim of
    # four chips is NOT advertised
    monkeypatch.setattr(tpu, "accelerator_device_files", lambda: [])
    assert tpu.detect_chip_count() == (0, "v5litepod-4")
    # device files but a busy chip: the probe's stderr comes back
    monkeypatch.setattr(tpu, "accelerator_device_files",
                        lambda: ["/dev/vfio/0"])
    busy = subprocess.CompletedProcess(
        [], 1, "", "ABORTED: ... libtpu multi-process lockfile ...")
    monkeypatch.setattr(tpu.subprocess, "run", lambda *a, **k: busy)
    with pytest.raises(tpu.TpuProbeError, match="lockfile") as e:
        tpu.detect_chip_count()
    assert "holds the chip" in str(e.value)

    def slow(*a, **k):
        raise subprocess.TimeoutExpired("probe", 1.0, stderr=b"starting")

    monkeypatch.setattr(tpu.subprocess, "run", slow)
    with pytest.raises(tpu.TpuProbeError, match="did not finish"):
        tpu.detect_chip_count(timeout_s=1.0)
    # pinned to the CPU: nothing is probed at all
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert tpu.detect_chip_count() == (0, "v5litepod-4")


def test_a_busy_device_is_probed_again_until_it_answers(monkeypatch):
    """A chip whose last holder has just exited: the probe that says
    ``Device or resource busy`` is tried again (every 2 s, inside the
    function's own time limit), and the error that is finally raised says
    how long it waited."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
    monkeypatch.setattr(tpu, "accelerator_device_files",
                        lambda: ["/dev/vfio/1"])
    naps = []
    monkeypatch.setattr(tpu.time, "sleep", naps.append)
    busy = subprocess.CompletedProcess(
        [], 1, "", "open(/dev/vfio/1): Device or resource busy")
    answers = [busy, busy, subprocess.CompletedProcess([], 0, "4", "")]
    monkeypatch.setattr(tpu.subprocess, "run",
                        lambda *a, **k: answers.pop(0))
    assert tpu.detect_chip_count() == (4, None)
    assert naps == [2.0, 2.0] and not answers
    # Busy to the end: no retry once the next nap would pass the limit.
    naps.clear()
    monkeypatch.setattr(tpu.subprocess, "run", lambda *a, **k: busy)
    with pytest.raises(tpu.TpuProbeError, match="stayed busy") as e:
        tpu.detect_chip_count(timeout_s=1.0)
    assert naps == [] and "1 tries" in str(e.value)
    # Another failure is final at once.
    other = subprocess.CompletedProcess([], 1, "", "no such device")
    monkeypatch.setattr(tpu.subprocess, "run", lambda *a, **k: other)
    with pytest.raises(tpu.TpuProbeError, match="no such device"):
        tpu.detect_chip_count()
    assert naps == []


def test_peak_flops_unknown_kind_raises():
    assert tpu.peak_flops_per_chip("TPU v5 lite") == 197e12
    for kind in ("no such chip", "", "cpu", "TPU v5", "v5e"):
        with pytest.raises(ValueError, match="no peak"):
            tpu.peak_flops_per_chip(kind)


# ---------------------------------------------------------- compile cache


def test_cache_dir_env_wins_else_checkout(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "x"))
    assert compile_cache.cache_dir() == str(tmp_path / "x")
    assert compile_cache.configure() == str(tmp_path / "x")
    monkeypatch.delenv(compile_cache.ENV_VAR)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.cache_dir() == want == compile_cache.cache_dir()
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    out = subprocess.run(
        [sys.executable, "-c",
         "from ray_tpu.util import compile_cache as c; print(c.cache_dir())"],
        capture_output=True, text=True, cwd=str(tmp_path),
        env=dict(env, PYTHONPATH=REPO), timeout=60)
    assert out.stdout.strip() == want, out.stderr


@pytest.mark.slow  # two jax processes (~8 s): the 0.9.0 re-proof, kept
def test_donated_executable_reloads_from_the_persistent_cache(tmp_path):
    """Compile a donated program with a cache directory, exit, reload it
    in a new process, compare results (what let the decode engine drop its
    cache detach): the second run must hit the cache and agree bit for
    bit, on subset meshes too."""
    src = '''
import os, json, hashlib
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ray_tpu.util.compile_cache import compile_watch
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
watch = compile_watch()
h = hashlib.sha1()
for n in (2, 8):
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    rep, sh = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    w = jax.device_put(np.linspace(0, 1, 4096, dtype=np.float32)
                       .reshape(64, 64), rep)
    m = jax.device_put(np.zeros((64, 64), np.float32), sh)
    x = jax.device_put(np.ones((8, 64), np.float32), sh)
    def step(w, m, x):
        g = jax.grad(lambda w: jnp.mean((x @ w) ** 2))(w)
        m = 0.9 * m + g
        return w - 0.01 * m, m
    f = jax.jit(step, donate_argnums=(0, 1), out_shardings=(rep, sh))
    for _ in range(4):
        w, m = f(w, m, x)
    h.update(np.array(w).tobytes()); h.update(np.array(m).tobytes())
print(json.dumps({"digest": h.hexdigest(), **watch.snapshot()}))
'''
    import json

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    runs = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", src],
                             capture_output=True, text=True, env=env,
                             timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    assert runs[0]["cache_hits"] == 0 and runs[1]["cache_hits"] >= 2
    assert runs[0]["digest"] == runs[1]["digest"]


# ---------------------------------------------------------- object store


def test_object_store_is_cut_to_the_file_size_limit(tmp_path, caplog):
    """The driver's chip machine runs the program under a file-size limit
    (``ulimit -f``) below the 2 GiB default store: ftruncate gave EFBIG and
    ``init()`` died there. The store is sized to what may be created."""
    import resource

    from ray_tpu._native.objstore import ShmStore

    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    try:
        resource.setrlimit(resource.RLIMIT_FSIZE, (64 << 20, hard))
        with caplog.at_level("WARNING", logger="ray_tpu._native.objstore"):
            store = ShmStore.create(str(tmp_path / "cut.store"), 2 << 30)
        assert "RLIMIT_FSIZE" in caplog.text
        assert (32 << 20) < store.capacity() < (64 << 20)
        assert os.path.getsize(tmp_path / "cut.store") <= (64 << 20)
        assert store.put_bytes(b"o" * 20, b"x" * (1 << 20)) is True
        store.close()
        # a store that fits is left as asked
        store = ShmStore.create(str(tmp_path / "fits.store"), 8 << 20)
        assert store.capacity() == 8 << 20
        store.close()
        # no room for the index plus a useful store: the reason, not errno
        resource.setrlimit(resource.RLIMIT_FSIZE, (2 << 20, hard))
        with pytest.raises(OSError, match="RLIMIT_FSIZE"):
            ShmStore.create(str(tmp_path / "none.store"), 2 << 30)
    finally:
        resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))


# ------------------------------------------------------------ chip_smoke


def test_chip_smoke_refuses_the_cpu_at_once(tmp_path):
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, cwd=REPO, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert time.monotonic() - t0 < 10
    assert out.returncode != 0
    assert "JAX_PLATFORMS" in out.stderr
    assert "cluster up" not in out.stdout and '"ok"' not in out.stdout
    # alone in a directory (nothing else of the repo): non-zero, no result
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    out = subprocess.run([sys.executable, str(alone)], capture_output=True,
                         text=True, cwd=str(tmp_path), env=env, timeout=60)
    assert out.returncode != 0 and '"ok"' not in out.stdout
    assert "ray_tpu" in out.stderr


@pytest.mark.slow  # ~25 s: the smoke's whole control flow at debug size
def test_chip_smoke_cpu_dry_run():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--dry-run-cpu"], capture_output=True, text=True, cwd=REPO,
        timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "DRY RUN" in out.stdout and '"ok"' not in out.stdout
    assert "FAIL" not in out.stdout
