"""The set-up record (ISSUE 55): ``setup.phase`` events of the flight
recorder, written where a start's work happens, by two reads of the host's
clock and nothing else. ``docs/OBSERVABILITY.md`` "Set-up phases" is the
catalog; ``benchmarks/metrics/_setup.py`` reads them (its arithmetic is
tested in ``tests/benchmark_harness/test_setup_metrics.py``)."""

import logging
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from ray_tpu.core.config import config
from ray_tpu.util import flightrec


@pytest.fixture()
def record(tmp_path):
    """This process's ring, empty, flushing into a directory of its own."""
    saved = config.flightrec_dir
    config.flightrec_dir = str(tmp_path)
    flightrec.reset()
    yield lambda: [e for e in flightrec.dump() if e["ev"] == "setup.phase"]
    flightrec.reset()
    config.flightrec_dir = saved


def _tiny():
    from ray_tpu.models import llama

    # A vocabulary no other test file uses: every program of this file
    # has a compile-cache key of its own, so no process beside it writes
    # the entry this one reads (ROADMAP D5: the cache writes in place).
    return llama.LlamaConfig(vocab_size=59, dim=32, n_layers=2, n_heads=4,
                             n_kv_heads=2, mlp_dim=64, max_seq_len=128)


def _deployment():
    from ray_tpu.serve.decode import LlamaDecodeDeployment

    return LlamaDecodeDeployment(config=_tiny(), slots=4, capacity=128,
                                 kv_page_tokens=4, kv_pool_pages=128)


def _by_phase(events):
    out = {}
    for e in events:
        out.setdefault(e["phase"], []).append(e)
    return out


def test_a_deployment_leaves_its_start_phase_by_phase(record):
    """``device_init``, ``weights``, ``engine_build``, ``warm_decode`` and
    ``ready`` once each, one ``first_dispatch`` a program key; no interval
    negative, any two nested or disjoint; a second dispatch of a key
    leaves nothing."""
    dep = _deployment()
    eng = dep.engine
    try:
        at_ready = record()
        phases = _by_phase(at_ready)
        for once in ("device_init", "weights", "engine_build", "warm_decode",
                     "ready"):
            assert len(phases[once]) == 1, once
        assert phases["device_init"][0]["platform"] == "cpu"
        assert phases["device_init"][0]["device_count"] >= 1
        assert 0 <= phases["device_init"][0]["import_s"] <= (
            phases["device_init"][0]["t1"] - phases["device_init"][0]["t0"])
        assert phases["weights"][0]["bytes"] > 0
        assert phases["engine_build"][0]["slots"] == 4
        assert phases["engine_build"][0]["pool_bytes"] > 0
        assert phases["warm_decode"][0]["rungs"] == len(eng._view_ladder) == 2
        ready = phases["ready"][0]
        assert ready["t0"] == ready["t1"]
        assert ready["compiles"] >= 2 and ready["compile_s"] > 0
        firsts = phases["first_dispatch"]
        assert sorted(e["key"] for e in firsts) == ["decode/128", "decode/64"]
        assert {e["key"] for e in firsts} == {
            "/".join(str(k) for k in key) for key in eng._compiled}
        warm = phases["warm_decode"][0]
        for e in firsts:
            assert warm["t0"] <= e["t0"] <= e["t1"] <= warm["t1"]
            assert e["compiles"] >= 1 and e["compile_s"] >= 0
        assert sum(e["compiles"] for e in firsts) <= ready["compiles"]

        spans = [e for e in at_ready if e["phase"] != "ready"]
        for e in spans:
            assert e["t1"] >= e["t0"], e
        for a in spans:
            for b in spans:
                nested = (a["t0"] <= b["t0"] and b["t1"] <= a["t1"]) or (
                    b["t0"] <= a["t0"] and a["t1"] <= b["t1"])
                assert nested or a["t1"] <= b["t0"] or b["t1"] <= a["t0"], (
                    a, b)
        order = [phases[p][0] for p in ("device_init", "weights",
                                        "engine_build", "warm_decode")]
        assert all(a["t1"] <= b["t0"] for a, b in zip(order, order[1:]))
        assert order[-1]["t1"] <= ready["t0"]

        # Traffic: a prefill key is new and leaves its record (with the
        # step log's event, which now says how long); decode's keys do not.
        req = eng.submit(list(range(1, 40)), max_new_tokens=6)
        assert req.done.wait(60) and req.status == "completed"
        later = _by_phase(record()[len(at_ready):])
        assert set(later) == {"first_dispatch"}
        keys = [e["key"] for e in later["first_dispatch"]]
        assert len(keys) == len(set(keys))
        assert not any(k.startswith("decode/") for k in keys)
        assert len(_by_phase(record())["first_dispatch"]) == len(
            eng._compiled)
        events = {e["key"]: e for r in eng.timeline()["rows"]
                  for e in r.get("events", ()) if e["kind"] == "jit-compile"}
        for e in later["first_dispatch"]:
            assert events[e["key"]]["dt"] == pytest.approx(e["t1"] - e["t0"])
    finally:
        eng.shutdown()


class _NoWaiting:
    """What a program returns, if anyone were to wait for it."""

    def block_until_ready(self):
        raise AssertionError("a first dispatch waited for its program")

    def __array__(self, *a, **k):
        raise AssertionError("a first dispatch fetched its program's result")


@pytest.mark.parametrize("step_timeline", [0, 64])
def test_a_first_dispatch_adds_no_wait(record, step_timeline):
    """With and without the step log: the first dispatch of a key hands
    back what ``call`` returned, untouched, and leaves one record; the
    second leaves none."""
    from ray_tpu.models import llama
    from ray_tpu.serve.decode import DecodeEngine

    import jax

    cfg = _tiny()
    eng = DecodeEngine(llama.init_params(cfg, jax.random.key(0)), cfg,
                       slots=2, capacity=64, page_tokens=4,
                       step_timeline=step_timeline)
    callers = []

    def call():
        callers.append(sys._getframe(1).f_code.co_name)
        return out

    try:
        before = len(record())
        out = _NoWaiting()
        assert eng._dispatch_fresh(("stub", 1), call) is out
        assert eng._dispatch_fresh(("stub", 1), call) is out
        # The program leaves from ``_dispatch_fresh``'s own frame, first
        # dispatch or not: the stack under a program is in its lowered
        # text's locations, and a frame more cost mixed 19 s of set-up and
        # every program its compile-cache entry (PERF.md section 6, PR 55).
        assert callers == ["_dispatch_fresh", "_dispatch_fresh"]
        new = record()[before:]
        assert [(e["phase"], e["key"]) for e in new] == [
            ("first_dispatch", "stub/1")]
        assert (new[0]["compiles"], new[0]["cache_hits"]) == (0, 0)
    finally:
        eng.shutdown()


def test_a_bare_engine_warms_nothing_and_says_what_it_built(record):
    from ray_tpu.models import llama
    from ray_tpu.serve.decode import DecodeEngine

    import jax

    cfg = _tiny()
    eng = DecodeEngine(llama.init_params(cfg, jax.random.key(0)), cfg,
                       slots=2, capacity=64, page_tokens=4)
    eng.shutdown()
    assert [e["phase"] for e in record()] == ["engine_build"]
    assert record()[0]["slots"] == 2


def _probe_env(monkeypatch, answers):
    from ray_tpu import tpu

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(tpu, "accelerator_device_files",
                        lambda: ["/dev/vfio/0"])
    replies = iter(answers)

    def run(cmd, **kwargs):
        code, out, err = next(replies)
        return subprocess.CompletedProcess(cmd, code, out, err)

    monkeypatch.setattr(tpu.subprocess, "run", run)
    monkeypatch.setattr(tpu, "_BUSY_RETRY_S", 0.05)
    return tpu


def test_the_probe_records_its_tries_and_its_busy_wait(record, monkeypatch,
                                                       caplog):
    busy = (1, "", "open(/dev/vfio/1): Device or resource busy")
    tpu = _probe_env(monkeypatch, [busy, busy, (0, "4", "")])
    with caplog.at_level(logging.INFO, logger="ray_tpu.tpu"):
        t0 = time.time()
        assert tpu.detect_chip_count()[0] == 4
        t1 = time.time()
    (e,) = record()
    assert e["phase"] == "probe" and e["tries"] == 3
    assert e["busy_wait_s"] == 2 * tpu._BUSY_RETRY_S
    assert t0 <= e["t0"] <= e["t1"] <= t1
    assert e["t1"] - e["t0"] >= e["busy_wait_s"]
    retries = [r.getMessage() for r in caplog.records
               if "device busy" in r.getMessage()]
    assert len(retries) == 2 and "try 2" in retries[1]
    assert "waited so far" in retries[0]


def test_a_probe_that_fails_records_nothing(record, monkeypatch):
    tpu = _probe_env(monkeypatch, [(1, "", "no such runtime")])
    with pytest.raises(tpu.TpuProbeError):
        tpu.detect_chip_count()
    assert record() == []


def test_with_the_recorder_off_a_start_records_nothing(record, monkeypatch):
    monkeypatch.setattr(config, "flightrec_enabled", False)
    tpu = _probe_env(monkeypatch, [(0, "1", "")])
    assert tpu.detect_chip_count()[0] == 1
    dep = _deployment()
    try:
        req = dep.engine.submit([1, 2, 3], max_new_tokens=2)
        assert req.done.wait(60) and req.status == "completed"
    finally:
        dep.engine.shutdown()
    assert flightrec.dump() == []


def test_train_state_leaves_two_weights_phases(record):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshSpec

    mesh = MeshSpec(fsdp=len(jax.devices())).build()
    params = ts.init_sharded_params(
        lambda key: {"w": jax.random.normal(key, (64, 8), jnp.float32)},
        {"w": ("embed", None)}, mesh, jax.random.key(0))
    ts.init_optimizer_state(optax.adamw(1e-3), params)
    first, second = record()
    assert first["phase"] == second["phase"] == "weights"
    assert first["bytes"] == 64 * 8 * 4
    assert second["bytes"] >= 2 * first["bytes"]      # adam's mu and nu
    assert first["t0"] <= first["t1"] <= second["t0"] <= second["t1"]


def test_setup_phase_sites_keep_one_schema_a_phase():
    """The lint that holds every flight-recorder event to one schema holds
    ``setup.phase`` to one a PHASE (its sites differ by design), and still
    flags two sites of one phase that disagree."""
    from ray_tpu.analysis import repo_root, run_analysis

    from test_core_observability import _lint_project, _run_metrics_lint

    findings, _ = run_analysis(root=repo_root(),
                               select=["metrics-name-collision"], jobs=1)
    assert findings == [], [f.render() for f in findings]
    project = _lint_project(fr_phase="""
        from ray_tpu.util import flightrec
        def probe(t0, t1, tries):
            flightrec.record("setup.phase", phase="probe", t0=t0, t1=t1,
                             tries=tries)
        def weights(t0, t1, n):
            flightrec.record("setup.phase", phase="weights", t0=t0, t1=t1,
                             bytes=n)
        def weights_elsewhere(t0, t1):
            flightrec.record("setup.phase", phase="weights", t0=t0, t1=t1)
        """)
    findings = _run_metrics_lint(project)
    assert len(findings) == 1
    assert findings[0].rule == "metrics-name-collision"
    assert "setup.phase[weights]" in findings[0].message


def test_placement_runtime_start_and_device_init_cross_processes(
        tmp_path, monkeypatch):
    """A serve app and a trainer on a real (CPU) cluster: the driver's file
    has ``runtime_start`` and both ``placement.begin``; the replica's and
    the train worker's have the ``placement.end`` that answers it, on the
    same host clock, and a worker whose lease names chips opens its devices
    under ``device_init`` ahead of its loop."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.decode import LlamaDecodeDeployment
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    # The workers take the directory from the environment they inherit.
    monkeypatch.setenv("RAY_TPU_FLIGHTREC_DIR", str(tmp_path))
    monkeypatch.setattr(config, "flightrec_dir", str(tmp_path))
    flightrec.reset()
    t_start = time.time()
    ray_tpu.init(num_cpus=4, resources={"TPU": 1})
    try:
        dep = serve.deployment(LlamaDecodeDeployment).bind(
            config=_tiny(), slots=2, capacity=64, kv_page_tokens=4)
        handle = serve.run(dep, name="tiny", ready_timeout_s=120)
        # An answer means ``__init__`` has returned; a replica is killed
        # at shutdown, so let its recorder flush first.
        assert handle.health.remote().result(timeout=120)["slots"] == 2
        time.sleep(2 * config.flightrec_flush_s + 0.2)
        serve.shutdown()

        def loop():
            from ray_tpu import train

            # A worker is killed when its loop ends: outlive one flush.
            time.sleep(1.5)
            train.report({"done": True})

        result = JaxTrainer(
            loop, run_config=RunConfig(name="setup-record"),
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True, tpu_chips_per_worker=1)).fit()
        assert result.error is None and result.metrics["done"]
        time.sleep(2 * config.flightrec_flush_s + 0.2)
        dumps = flightrec.cluster_dump()
    finally:
        ray_tpu.shutdown()
        flightrec.reset()
    mine = f"pid{os.getpid()}"
    events = [dict(e, source=src) for src, doc in dumps.items()
              for e in doc["events"] if e["ev"] == "setup.phase"
              and e["t0"] >= t_start]
    phases = _by_phase(events)
    (start,) = phases["runtime_start"]
    assert start["source"].endswith(mine) and start["chips"] == 1
    begins = {e["name"]: e for e in phases["placement.begin"]}
    ends = {e["name"]: e for e in phases["placement.end"]}
    assert set(begins) == set(ends) == {"tiny", "setup-record"}
    for name, begin in begins.items():
        assert begin["source"].endswith(mine)
        assert not ends[name]["source"].endswith(mine)
        assert start["t1"] <= begin["t0"] <= ends[name]["t0"]
    inits = {e["source"]: e for e in phases["device_init"]}
    assert set(inits) == {e["source"] for e in ends.values()}
    for name in begins:
        init = inits[ends[name]["source"]]
        assert ends[name]["t0"] <= init["t0"] <= init["t1"]
        assert init["platform"] == "cpu"
    replica = [e for e in events if e["source"] == ends["tiny"]["source"]]
    assert [e["phase"] for e in replica if e["phase"] != "first_dispatch"] \
        == ["placement.end", "device_init", "weights", "engine_build",
            "warm_decode", "ready"]
    assert np.all(np.diff([e["t1"] for e in replica
                           if e["phase"] != "first_dispatch"]) >= 0)


def test_the_post_mortem_reads_a_replicas_start_from_the_recorder(record):
    """``ray_tpu doctor --post-mortem`` with no benchmark at hand: the
    replica's phases in order, its first dispatches counted and summed."""
    from ray_tpu import doctor

    dep = _deployment()
    dep.engine.shutdown()
    dumps = {"worker-pid7": {"pid": 7, "role": "worker",
                             "events": flightrec.dump()},
             "driver-pid1": {"pid": 1, "role": "driver", "events": [
                 {"ev": "gang.register", "ts": 1.0, "group": "g",
                  "epoch": 1, "hosts": 2}]}}
    (line,) = doctor.setup_phases(dumps)
    assert line.lstrip().startswith("worker-pid7 from ")
    at = [line.index(p) for p in ("device_init", "weights", "engine_build",
                                  "warm_decode", "ready",
                                  "first_dispatch x2")]
    assert at == sorted(at)
    assert "from the cache" in line
    text = doctor.render_post_mortem(doctor.post_mortem(dumps), dumps)
    assert "no deaths or stalls" in text and "set-up on record:" in text
    assert line in text
    assert "set-up on record" not in doctor.render_post_mortem(
        [], {"driver-pid1": dumps["driver-pid1"]})
