"""Pipeline-parallel training plane (ISSUE 14, ROADMAP #5).

Contract under test, all on ONE module-scoped cluster (virtual 4-host
slice) against tiny-llama configs:

* the 1F1B schedule completes (no deadlock) at in-flight windows 1, 2
  and 4, and the WINDOW NEVER CHANGES THE MATH — per-stage gradients
  accumulate in microbatch order regardless of overlap;
* loss parity: a pipelined run matches the single-process full-model
  baseline within the repo's relative-tolerance bounds (f32
  reduction-order drift), and is BIT-EXACT against the local chain of
  the same stage programs; the 1-stage degenerate config is bit-exact
  too;
* ZeRO-1: optimizer-state bytes per replica drop to ~1/N over the data
  axis with the loss curve matching the unsharded optimizer;
* stage SIGKILL reconciles the WHOLE gang (epoch+1), training resumes
  from the last completed optimizer step with the SAME loss curve as an
  uninterrupted run, and zero activation refs leak;
* stage RPCs carry descriptors, never tensors (p99 serialized size
  within PIPE_DESC_BYTE_BUDGET, read off the pipeline_desc_bytes
  histogram like every other surface);
* `ray_tpu doctor` names the straggler stage of a stalled pipeline
  (faultinject delay at the stage-forward site).
"""

import os
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core import coremetrics
from ray_tpu.core.config import config
from ray_tpu.core.rpc_stubs import ControllerStub
from ray_tpu.core.runtime import get_core_worker
from ray_tpu.util import faultinject, metrics as um
from ray_tpu.util.faultinject import Faults
from ray_tpu.util.metrics import _Registry

_FAULTS = "/tmp/ray_tpu_pipe_faults.json"


@pytest.fixture(scope="module")
def pipe_cluster(tmp_path_factory):
    """One cluster for the whole module: a virtual 4-host slice (4
    chips per host) with fault injection AND the flight recorder
    plumbed into every process (env set BEFORE init so spawned stage
    workers inherit both; a per-run recorder dir keeps stale fr-<pid>
    files from other sessions out of the post-mortem)."""
    fr_dir = str(tmp_path_factory.mktemp("flightrec"))
    saved = {k: os.environ.get(k)
             for k in ("RAY_TPU_VIRTUAL_SLICE",
                       "RAY_TPU_FAULTINJECT_PATH",
                       "RAY_TPU_FLIGHTREC_DIR")}
    os.environ["RAY_TPU_VIRTUAL_SLICE"] = "4x4/4"
    os.environ["RAY_TPU_FAULTINJECT_PATH"] = _FAULTS
    os.environ["RAY_TPU_FLIGHTREC_DIR"] = fr_dir
    old_path = config.faultinject_path
    old_fr = config.flightrec_dir
    config.faultinject_path = _FAULTS
    config.flightrec_dir = fr_dir
    faultinject.reset_counters()
    core = ray_tpu.init(num_cpus=8)
    yield core
    ray_tpu.shutdown()
    config.faultinject_path = old_path
    config.flightrec_dir = old_fr
    faultinject.reset_counters()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _tiny_cfg():
    from ray_tpu.models import llama

    return llama.LlamaConfig(vocab_size=64, dim=32, n_layers=4,
                             n_heads=4, n_kv_heads=2, mlp_dim=64,
                             max_seq_len=64)


def _setup(seed=0, n_steps=3, n_micro=4, batch=8, seq=17):
    import jax

    from ray_tpu.models import llama
    from ray_tpu.train.pipeline_plane import microbatches

    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(seed)
    steps = [microbatches(
        {"tokens": rng.integers(0, cfg.vocab_size,
                                (batch, seq)).astype(np.int32)},
        n_micro) for _ in range(n_steps)]
    return cfg, params, steps


# -------------------------------------------- schedule + loss parity


def test_window_invariance_and_parity_2_stages(pipe_cluster):
    """Windows 1/2/4 all complete (no deadlock — the step timeout in
    pipe_step_timeout_s would convert one into a typed PipelineError)
    and produce the SAME losses: overlap must never change the
    accumulation order. The curve is bit-exact vs the local chain of
    the same stage programs and matches the independent full-model
    baseline within relative tolerance. Descriptors stay within
    budget."""
    from ray_tpu.train.pipeline_plane import (PIPE_DESC_BYTE_BUDGET,
                                              PipelinePlane,
                                              single_process_baseline)

    cfg, params, steps = _setup(n_steps=3)
    base, _ = single_process_baseline(cfg, params, 1e-2, steps)
    stage_base, _ = single_process_baseline(cfg, params, 1e-2, steps,
                                            n_stages=2)
    plane = PipelinePlane(cfg, params, n_stages=2, n_microbatches=4,
                          lr=1e-2, window=2, name="win-pipe").start()
    try:
        got = []
        for window, mbs in zip((1, 2, 4), steps):
            plane.window = window
            got.append(plane.train_step(mbs))
        assert got == stage_base, (got, stage_base)
        np.testing.assert_allclose(got, base, rtol=2e-4)
        # Stage RPCs carried descriptors, never tensors: p99 within
        # the budget, straight off the production histogram.
        snap = {"local": _Registry.get().snapshot()}
        merged = um.merge_histograms(snap, "pipeline_desc_bytes")
        entry = merged.get((("pipeline", "win-pipe"),))
        assert entry and entry["count"] > 0
        p99 = um.histogram_quantile(entry, 0.99)
        assert p99 is not None and p99 <= PIPE_DESC_BYTE_BUDGET, entry
        # ...and the shared core_summary read path surfaces the plane.
        summary = coremetrics.core_summary(snap)
        assert summary["pipeline"]["desc_bytes"]["count"] > 0
        st = plane.stats()
        assert st["ledger_refs"] == 0 and st["inflight_microbatches"] == 0
    finally:
        report = plane.stop()
    assert report["inflight_refs_dropped"] == 0
    assert report["ledger_refs"] == 0
    assert plane.registry_state() is None  # record dropped


@pytest.mark.slow  # 25 s: 4-stage parity sweep
def test_loss_parity_4_stages(pipe_cluster):
    """Four 1-layer stages, 8 microbatches: bit-exact vs the local
    4-stage chain, tolerance-parity vs the full model."""
    from ray_tpu.train.pipeline_plane import (PipelinePlane,
                                              single_process_baseline)

    cfg, params, steps = _setup(n_steps=2, n_micro=8, batch=8)
    base, _ = single_process_baseline(cfg, params, 1e-2, steps)
    stage_base, _ = single_process_baseline(cfg, params, 1e-2, steps,
                                            n_stages=4)
    plane = PipelinePlane(cfg, params, n_stages=4, n_microbatches=8,
                          lr=1e-2, window=4, name="four-pipe").start()
    try:
        got = plane.run(steps)
    finally:
        plane.stop()
    assert got == stage_base, (got, stage_base)
    np.testing.assert_allclose(got, base, rtol=2e-4)


def test_one_stage_degenerate_bitexact(pipe_cluster):
    """The 1-stage pipeline is the degenerate config: distribution
    must add NOTHING — bit-exact against the local run of the same
    stage program."""
    from ray_tpu.train.pipeline_plane import (PipelinePlane,
                                              single_process_baseline)

    cfg, params, steps = _setup(n_steps=2)
    stage_base, _ = single_process_baseline(cfg, params, 1e-2, steps,
                                            n_stages=1)
    plane = PipelinePlane(cfg, params, n_stages=1, n_microbatches=4,
                          lr=1e-2, name="one-pipe").start()
    try:
        got = plane.run(steps)
    finally:
        plane.stop()
    assert got == stage_base, (got, stage_base)


# --------------------------------------------------------- ZeRO-1


@pytest.mark.slow  # PR 20 rebudget (11.3s): ZeRO-1 parity also
# covered by the zero1 pipeline-parity sweep above
def test_zero1_state_bytes_and_parity():
    """ZeRO-1 sharding annotations on the optimizer state: per-replica
    state bytes drop to ~1/N (<= 0.6x at data=2 — the acceptance
    bound), params come back replicated (the once-per-step all-gather),
    and the loss curve matches the unsharded optimizer."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshSpec

    cfg = _tiny_cfg()
    base_params = llama.init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(3)
    batch = {"tokens": rng.integers(0, cfg.vocab_size,
                                    (8, 17)).astype(np.int32)}
    opt = optax.adam(1e-2)

    def lf(p, b):
        return llama.loss_fn(p, b, cfg)

    # np.array (copy=True): on the CPU backend np.asarray of a jax
    # array is a zero-copy VIEW, and device_put of a view can alias the
    # source buffer — a later donated step would clobber the "other"
    # run's params (silent corruption, found by this very test).
    def fresh_replicated(rep):
        return jax.device_put(
            jax.tree.map(lambda x: np.array(x), base_params),
            jax.tree.map(lambda _: rep, base_params))

    # State-bytes sweep: init only.
    ratios = {}
    for n_data in (2, 4, 8):
        mesh = MeshSpec(data=n_data, fsdp=1).build(
            jax.devices()[:n_data])
        rep = NamedSharding(mesh, P())
        params = fresh_replicated(rep)
        st_plain = ts.init_optimizer_state(opt, params)
        per_plain = ts.per_replica_state_bytes(st_plain)
        st_z1 = ts.init_zero1_opt_state(opt, params, mesh)
        ratios[n_data] = ts.per_replica_state_bytes(st_z1) \
            / per_plain

    # Parity: donated steps on the full 8-device mesh.
    mesh = MeshSpec(data=8, fsdp=1).build()
    rep = NamedSharding(mesh, P())
    step_plain = ts.build_train_step(lf, opt, mesh)
    params = fresh_replicated(rep)
    step_z1 = ts.build_zero1_train_step(lf, opt, mesh, params)
    p0, p1 = fresh_replicated(rep), fresh_replicated(rep)
    s0 = ts.init_optimizer_state(opt, p0)
    s1 = ts.init_zero1_opt_state(opt, p1, mesh)
    per_z1 = ts.per_replica_state_bytes(s1)
    plain_losses, z1_losses = [], []
    for _ in range(3):
        p0, s0, m0 = step_plain(p0, s0, batch)
        p1, s1, m1 = step_z1(p1, s1, batch)
        plain_losses.append(float(m0["loss"]))
        z1_losses.append(float(m1["loss"]))
    np.testing.assert_allclose(z1_losses, plain_losses, rtol=2e-4)
    # State stays sharded THROUGH the step (donated in/out),
    # params stay replicated (the once-per-step all-gather).
    assert ts.per_replica_state_bytes(s1) == per_z1
    assert all(l.sharding.is_fully_replicated
               for l in jax.tree.leaves(p1))

    # ~1/N + the all-gather working buffers: the acceptance bound is
    # 0.6x at data=2; deeper meshes keep shrinking (indivisible tiny
    # leaves replicate, so the curve flattens above 1/N).
    assert ratios[2] <= 0.6, ratios
    assert ratios[4] < ratios[2] and ratios[8] < ratios[4], ratios


def test_zero1_rules_namespaces_split_and_guarded():
    """Regression: build_zero1_train_step's single ``rules`` parameter
    used to feed BOTH the step body's model-axis table AND the ZeRO-1
    state table — a model table made ``zero1_shard`` miss and the state
    silently replicated (no error, just 1x memory). The namespaces are
    now split (``rules`` vs ``zero1_rules``) and the state-table
    resolution refuses a table without the ``zero1_shard`` key. jit is
    lazy, so none of this compiles anything."""
    import jax
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshSpec

    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(2))
    opt = optax.adam(1e-2)
    mesh = MeshSpec(data=2, fsdp=1).build(jax.devices()[:2])
    state_shape = jax.eval_shape(opt.init, params)
    model_rules = {"batch": "data"}  # model-axis table: no zero1_shard

    def lf(p, b):
        return llama.loss_fn(p, b, cfg)

    # The state-table resolution refuses a model-axis table outright
    # instead of silently replicating the state.
    with pytest.raises(ValueError, match="zero1_shard"):
        ts.zero1_state_shardings(mesh, state_shape, model_rules)
    with pytest.raises(ValueError, match="zero1_shard"):
        ts.init_zero1_opt_state(opt, params, mesh, model_rules)
    # ...and the builder no longer routes the model table there: with
    # the old single-parameter wiring this call would now raise (and
    # before the guard, silently disable ZeRO-1).
    step = ts.build_zero1_train_step(lf, opt, mesh, params,
                                     rules=model_rules)
    assert callable(step)
    # The default state table shards over the data axis; an explicit
    # zero1_rules override takes the same path.
    for shardings in (
            ts.zero1_state_shardings(mesh, state_shape),
            ts.zero1_state_shardings(mesh, state_shape,
                                     {"zero1_shard": "data"})):
        assert any(any(ax == "data" for ax in leaf.spec)
                   for leaf in jax.tree.leaves(shardings)), shardings


# ------------------------------------- stage death + gang reconcile


@pytest.mark.chaos
def test_stage_sigkill_reconciles_and_resumes(pipe_cluster):
    """SIGKILL one stage mid-run (faultinject die at its member beat
    site): the WHOLE gang re-forms under epoch+1, the interrupted step
    replays from the driver snapshot, and the final loss curve is
    IDENTICAL to an uninterrupted run. Zero refs leak; the deposed
    incarnation's step reports are fenced."""
    from ray_tpu.train.pipeline_plane import (PipelinePlane,
                                              single_process_baseline)

    cfg, params, steps = _setup(seed=7, n_steps=3)
    stage_base, _ = single_process_baseline(cfg, params, 1e-2, steps,
                                            n_stages=2)
    plane = PipelinePlane(cfg, params, n_stages=2, n_microbatches=4,
                          lr=1e-2, window=2, name="kill-pipe").start()
    try:
        got = []
        for i, mbs in enumerate(steps):
            if i == 1:
                with Faults(_FAULTS) as f:
                    rule = f.add(
                        "multihost.member.kill-pipe-gang.host-1.beat",
                        "die", once_global=True, rule_id="kill-s1")
                    deadline = time.monotonic() + 30.0
                    while (not f.marker_fired(rule)
                           and time.monotonic() < deadline):
                        time.sleep(0.02)
                    assert f.marker_fired(rule)
                    got.append(plane.train_step(mbs))
            else:
                got.append(plane.train_step(mbs))
        assert got == stage_base, (got, stage_base)
        st = plane.stats()
        assert st["gang_epoch"] == 2          # whole-gang restart
        assert st["epoch"] == 2               # pipeline re-registered
        assert st["ledger_refs"] == 0
        assert st["group"]["restarts"] == 1
        # Controller record: resumed progress, deposed epoch fenced.
        reg = plane.registry_state()
        assert reg["epoch"] == 2 and reg["last_step"] == 2
        stub = ControllerStub(get_core_worker().controller)
        stale = stub.pipe_step_complete("kill-pipe", 99, 1)
        assert stale == {"ok": False, "reason": "stale_epoch",
                         "epoch": 2}
        assert reg["last_step"] == plane.registry_state()["last_step"]
    finally:
        report = plane.stop()
    assert report["ledger_refs"] == 0


# ----------------------------------------- doctor: pipeline-stall


def _agg(source="n1/node/pid1"):
    return {source: _Registry.get().snapshot()}


@pytest.mark.chaos
def test_doctor_names_pipeline_stall_straggler(pipe_cluster):
    """Delay stage 1's forward (faultinject at the pipeline.stage site)
    mid-step: stage 1 stays busy while stage 0 idles for the whole
    doctor window, and the doctor names s1 as the straggler. The delay
    elapses, the step completes, and the signature clears."""
    from ray_tpu import doctor
    from ray_tpu.train.pipeline_plane import PipelinePlane

    cfg, params, steps = _setup(n_steps=1)
    plane = PipelinePlane(cfg, params, n_stages=2, n_microbatches=4,
                          lr=1e-2, window=2, name="stall-pipe").start()
    result, errs = [], []

    def run_step():
        try:
            result.append(plane.train_step(steps[0]))
        except Exception as e:  # surfaced via errs below
            errs.append(e)

    try:
        with Faults(_FAULTS) as f:
            f.add("pipeline.stage.stall-pipe.1.fwd", "delay",
                  delay_s=3.0)
            t = threading.Thread(target=run_step, daemon=True)
            t.start()
            # Let the schedule reach the stalled stage, then take the
            # doctor window while it is wedged (the starved stage needs
            # > pipe_stall_idle_s of idle in BOTH snapshots).
            time.sleep(1.2)
            before = _agg()
            time.sleep(1.0)
            after = _agg()
        findings = doctor.diagnose(before, after, 1.0)
        stalls = [x for x in findings
                  if x["signature"] == "pipeline-stall"
                  and "stall-pipe" in x["source"]]
        assert stalls, findings
        assert stalls[0]["severity"] == "critical"
        assert "s1" in stalls[0]["evidence"]["stragglers"]
        assert "s0" in stalls[0]["evidence"]["starved"]
        assert "s1" in stalls[0]["summary"]
        t.join(timeout=60.0)
        assert not t.is_alive() and not errs, errs
        assert len(result) == 1
        # Stall over: uniform gauges again, signature gone.
        snap = _agg()
        assert [x for x in doctor.diagnose(snap, snap, 1.0)
                if x["signature"] == "pipeline-stall"] == []
    finally:
        plane.stop()


# --------------------------------- transient disruptions (no reconcile)


@pytest.mark.chaos
def test_transient_stage_error_replay_does_not_double_accumulate(
        pipe_cluster):
    """One stage RPC fails transiently mid-step (injected error at the
    stage-forward site; every member still answers ping, so no gang
    reconcile happens) and the step replays on the SURVIVING gang.
    Regression: the replay used to run against the ``_g_acc``/``_stash``
    the aborted attempt left behind — gradients from backwards that
    completed before the disruption were accumulated a SECOND time and
    silently applied, so later steps drifted off the baseline with no
    error. ``begin_step`` now resets per-step stage state; the full
    curve stays bit-exact and the gang never restarts."""
    from ray_tpu.train.pipeline_plane import (PipelinePlane,
                                              single_process_baseline)

    cfg, params, steps = _setup(seed=11, n_steps=3)
    stage_base, _ = single_process_baseline(cfg, params, 1e-2, steps,
                                            n_stages=2)
    plane = PipelinePlane(cfg, params, n_stages=2, n_microbatches=4,
                          lr=1e-2, window=2, name="flake-pipe").start()
    try:
        with Faults(_FAULTS) as f:
            # Stage 0's THIRD forward of the first step: by then the
            # first microbatch's backward has already accumulated into
            # _g_acc on both stages — exactly the state a replay must
            # not count twice. once_global gives the cross-process
            # marker the test asserts on (a renamed site must not turn
            # this into a trivial pass).
            rule = f.add("pipeline.stage.flake-pipe.0.fwd", "error",
                         after=2, times=1, once_global=True,
                         rule_id="flake-s0-fwd")
            got = [plane.train_step(steps[0])]
            assert f.marker_fired(rule)  # the disruption happened
        got += [plane.train_step(mbs) for mbs in steps[1:]]
        assert got == stage_base, (got, stage_base)
        st = plane.stats()
        # Transient: same gang incarnation end to end, nothing leaked.
        assert st["gang_epoch"] == 1 and st["epoch"] == 1
        assert st["group"]["restarts"] == 0
        assert st["ledger_refs"] == 0 and st["step"] == 3
    finally:
        report = plane.stop()
    assert report["ledger_refs"] == 0


@pytest.mark.chaos
def test_transient_snapshot_failure_commits_step_on_live_gang(
        pipe_cluster):
    """The post-apply snapshot pull fails transiently while the gang
    stays ALIVE (injected error at the stage snapshot site).
    Regression: the failure used to escape as a whole-step replay —
    but the stages had already applied the update, so every replayed
    ``apply_update`` failed the stage clock guard and a HEALTHY gang
    died a fatal PipelineError after the attempt budget. The snapshot
    is now retried on a live gang and the step commits."""
    from ray_tpu.train.pipeline_plane import (PipelinePlane,
                                              single_process_baseline)

    cfg, params, steps = _setup(seed=13, n_steps=2)
    stage_base, _ = single_process_baseline(cfg, params, 1e-2, steps,
                                            n_stages=2)
    plane = PipelinePlane(cfg, params, n_stages=2, n_microbatches=4,
                          lr=1e-2, window=2, name="snap-pipe").start()
    try:
        with Faults(_FAULTS) as f:
            rule = f.add("pipeline.stage.snap-pipe.1.snap", "error",
                         times=1, once_global=True, rule_id="snap-s1")
            got = [plane.train_step(mbs) for mbs in steps]
            assert f.marker_fired(rule)  # the pull did fail once
        assert got == stage_base, (got, stage_base)
        st = plane.stats()
        assert st["step"] == 2
        assert st["gang_epoch"] == 1 and st["epoch"] == 1
        assert st["group"]["restarts"] == 0
        assert st["ledger_refs"] == 0
        # The retried pull landed: the driver owns a current snapshot.
        assert plane.snapshot_params() is not None
    finally:
        plane.stop()


# ------------------------------- train-plane trace + step breakdown


@pytest.mark.slow  # 19.5s: traced 4-stage run; PR 16 tier-1 rebudget
def test_train_trace_rows_bubble_and_step_breakdown(pipe_cluster):
    """ISSUE 15 acceptance: a traced 4-stage step renders per-stage
    process rows whose spans carry {step, mb, stage} attrs, and the
    TRACE-derived bubble fraction (train_trace_summary — what
    `ray_tpu timeline --train` prints) matches the driver-clock
    bubble (bench_pipeline.py's method) within 10%. Plus the per-step
    phase breakdown: stage-seconds split across fwd/bwd/apply/
    allgather/idle that adds up to stages x wall, surfaced through
    core_summary.pipeline with the MFU estimate gauge."""
    from ray_tpu.scripts import build_chrome_trace, train_trace_summary
    from ray_tpu.train.pipeline_plane import PipelinePlane

    cfg, params, steps = _setup(n_steps=2, n_micro=8, batch=16)
    plane = PipelinePlane(cfg, params, n_stages=4, n_microbatches=8,
                          lr=1e-2, window=4, name="trace-pipe",
                          snapshot_every=0).start()
    old_trace = config.pipe_trace_spans
    old_peak = config.pipe_peak_tflops
    old_sample = config.pipe_trace_sample_every
    try:
        # Warm the stage jits UNTRACED: compile time is not schedule
        # shape, and the trace window must cover exactly one warm step.
        config.pipe_trace_spans = False
        plane.train_step(steps[0])
        config.pipe_trace_spans = True
        config.pipe_trace_sample_every = 1  # trace THIS step (index 1)
        busy0 = plane.stats()["stage_busy_s"]
        t0 = time.monotonic()
        plane.train_step(steps[1])
        wall = time.monotonic() - t0
        busy = [b - a for a, b in
                zip(busy0, plane.stats()["stage_busy_s"])]
        bubble_stats = 1.0 - sum(busy) / (4 * wall)

        # ---- step breakdown: every stage-second of the step has a row
        bd = plane.stats()["step_breakdown"]
        assert bd["fwd_s"] > 0 and bd["bwd_s"] > 0 and bd["apply_s"] > 0
        assert bd["allgather_s"] == 0.0  # ZeRO-1-in-stage: real rig
        total = (bd["fwd_s"] + bd["bwd_s"] + bd["apply_s"]
                 + bd["allgather_s"] + bd["idle_s"])
        assert abs(total - 4 * bd["wall_s"]) <= 0.02 * 4 * bd["wall_s"]
        assert bd["tokens"] == 256  # 8 mbs x 2 rows x 16 tokens
        assert bd["model_tflops"] > 0

        # ---- the shared read path: breakdown + MFU through
        # core_summary (the dashboard train panel and `ray_tpu
        # metrics` read exactly this).
        config.pipe_peak_tflops = 0.001
        snap = {"local": _Registry.get().snapshot()}
        summary = coremetrics.core_summary(snap)["pipeline"]
        for phase in ("fwd", "bwd", "apply", "allgather", "idle"):
            assert phase in summary["step_breakdown_s"]
        assert summary["step_breakdown_s"]["fwd"] > 0
        assert summary["model_tflops"]["trace-pipe"] > 0
        assert summary["mfu_pct"]["trace-pipe"] > 0

        # ---- spans reached the controller: per-stage rows + attrs
        ctl = get_core_worker().controller
        deadline = time.monotonic() + 15.0
        summ = {}
        while time.monotonic() < deadline:
            events = ctl.call("list_task_events", 20000)
            summ = train_trace_summary(events).get("trace-pipe", {})
            # 8 fwd + 8 bwd driver cells per stage = 64 cells
            if summ.get("cells", 0) >= 64:
                break
            time.sleep(0.25)
        assert summ.get("cells", 0) >= 64, summ
        assert summ["n_stages"] == 4
        trace = build_chrome_trace(events)
        row_names = {t["args"]["name"] for t in trace
                     if t.get("ph") == "M"
                     and t["name"] == "process_name"}
        assert {"stage s0", "stage s1", "stage s2",
                "stage s3"} <= row_names
        fwd = [t for t in trace if t.get("cat") == "span"
               and t["name"] == "fwd"]
        assert fwd and {"step", "mb", "stage"} <= set(fwd[0]["args"])

        # ---- trace-derived bubble tracks the driver-clock bubble
        bubble_trace = summ["bubble_fraction"]
        assert abs(bubble_trace - bubble_stats) \
            <= 0.10 * max(bubble_stats, bubble_trace), \
            (bubble_trace, bubble_stats)
    finally:
        config.pipe_trace_spans = old_trace
        config.pipe_peak_tflops = old_peak
        config.pipe_trace_sample_every = old_sample
        plane.stop()


# --------------------------------------- crash forensics: post-mortem


@pytest.mark.chaos
@pytest.mark.slow  # 26 s: SIGKILL + dump collection
def test_post_mortem_names_killed_stage_from_dumps(pipe_cluster):
    """ISSUE 15 acceptance: SIGKILL a StageActor (faultinject die at
    its member beat site), let the gang reconcile and training resume —
    then `doctor.post_mortem` must name the killed stage/member and the
    surviving gang's epoch FROM DUMPS ALONE (a pure function over the
    fr_dump merge; no live cluster queries)."""
    from ray_tpu import doctor
    from ray_tpu.train.pipeline_plane import PipelinePlane

    cfg, params, steps = _setup(seed=17, n_steps=3)
    plane = PipelinePlane(cfg, params, n_stages=2, n_microbatches=4,
                          lr=1e-2, window=2, name="pm-pipe").start()
    try:
        got = []
        for i, mbs in enumerate(steps):
            if i == 1:
                with Faults(_FAULTS) as f:
                    rule = f.add(
                        "multihost.member.pm-pipe-gang.host-1.beat",
                        "die", once_global=True, rule_id="pm-kill-s1")
                    deadline = time.monotonic() + 30.0
                    while (not f.marker_fired(rule)
                           and time.monotonic() < deadline):
                        time.sleep(0.02)
                    assert f.marker_fired(rule)
                    got.append(plane.train_step(mbs))
            else:
                got.append(plane.train_step(mbs))
        assert plane.stats()["gang_epoch"] == 2  # resumed under epoch 2
        # Let the surviving stages' background flush land their rings.
        time.sleep(1.5)
        stub = ControllerStub(get_core_worker().controller)
        dumps = stub.fr_dump()
        # The analysis is a PURE function of the dumps dict — nothing
        # else from the live cluster goes in.
        findings = doctor.post_mortem(dumps)
        deaths = [x for x in findings if x["signature"] == "gang-death"
                  and x["source"] == "group:pm-pipe-gang"]
        assert deaths, findings
        d = deaths[0]
        assert d["evidence"]["first_dying"] == "host-1"
        assert d["evidence"]["surviving_epoch"] == 2
        assert d["evidence"]["injected"] is True
        assert "host-1" in d["summary"] and "epoch 2" in d["summary"]
        assert "s1" in d["summary"]  # the killed STAGE, by name
        # The same story must be tellable with the cluster GONE:
        # dump_all reads the persisted files directly.
        from ray_tpu.util import flightrec

        offline = doctor.post_mortem(
            flightrec.dump_all(config.flightrec_dir))
        assert any(x["signature"] == "gang-death"
                   and x["source"] == "group:pm-pipe-gang"
                   and x["evidence"]["first_dying"] == "host-1"
                   for x in offline)
    finally:
        plane.stop()


# ----------------------------------------- formation-abort discharge


def test_register_failure_strands_neither_gang_nor_record(pipe_cluster):
    """``pipe_register`` itself failing during formation (injected
    error at the controller's RPC site) must discharge BOTH
    acquisitions: the already-started gang is shut down (sub-slice
    released, group record dropped) and no pipeline record exists.
    Regression: the register call sat outside the cleanup guard, so its
    failure stranded the gang actors and their reserved sub-slice."""
    from ray_tpu.core import multihost
    from ray_tpu.core.placement import cluster_topology
    from ray_tpu.train.pipeline_plane import PipelinePlane

    def reservations():
        out = {}
        for s in cluster_topology()["slices"].values():
            out.update(s["reservations"])
        return out

    assert reservations() == {}  # clean slate from the prior tests
    cfg, params, _steps = _setup(n_steps=1)
    plane = PipelinePlane(cfg, params, n_stages=2, n_microbatches=4,
                          lr=1e-2, name="regfail-pipe")
    with Faults(_FAULTS) as f:
        f.add("rpc.server.*.pipe_register", "error", times=1,
              rule_id="regfail")
        with pytest.raises(Exception) as ei:
            plane.start()
        assert "faultinject" in str(ei.value)
    # Nothing stranded: no reservation, no group record, no pipeline
    # record — and the chips are actually free again (a fresh gang of
    # the same shape forms).
    assert reservations() == {}
    assert multihost.registry_state("regfail-pipe-gang") is None
    assert plane.registry_state() is None
    plane2 = PipelinePlane(cfg, params, n_stages=2, n_microbatches=4,
                           lr=1e-2, name="regfail-pipe").start()
    try:
        assert plane2.stats()["group"]["state"] == "ALIVE"
    finally:
        plane2.stop()
    assert reservations() == {}
