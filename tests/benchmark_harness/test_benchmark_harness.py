"""The benchmark's own arithmetic, on the CPU, in seconds: traffic
generation, the client-side metric rules, whole-steps throughput, the trace
reduction on a small trace recorded on a TPU v5e, the peaks table, and the
runner's refusal to measure without a chip, and the seam through which a
serve cell reaches its family (reference, tolerance, check sizes, deployment
class). The end-to-end rehearsals (every cell at a toy size; a dummy
configuration, traffic mix and metric, and a dummy SERVE family with its own
reference, added as files) are marked slow."""

import ast
import json
import os
import random
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops, peaks, stats, tracered, traffic  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(ROOT, "benchmarks", "run.py")


def _mix(name):
    return traffic.load(name, os.path.join(ROOT, "benchmarks"))


@pytest.mark.parametrize("seed_a,seed_b", [(1, 2), (7, 3000000019)])
def test_open_loop_same_multiset_other_order(seed_a, seed_b):
    mix = _mix("chat_steady")
    a = traffic.open_loop_schedule(mix, seed_a, 51)
    b = traffic.open_loop_schedule(mix, seed_b, 51)
    for phase in ("lead_in", "window", "drain"):
        pa = [(r.prompt_len, r.answer_len) for r in a if r.phase == phase]
        pb = [(r.prompt_len, r.answer_len) for r in b if r.phase == phase]
        assert sorted(pa) == sorted(pb) and pa != pb
    n_win = sum(1 for r in a if r.phase == "window")
    assert n_win == round(mix["rate_rps"] * 51)
    assert a[0].tokens(1000) != b[0].tokens(1000)
    again = traffic.open_loop_schedule(mix, seed_a, 51)
    assert [(r.due_s, r.token_seed) for r in a] == \
        [(r.due_s, r.token_seed) for r in again]


def test_lengths_follow_the_distribution_and_its_clips():
    d = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 32,
         "max": 2048}
    xs = traffic.stratified_lengths(d, 101)
    assert xs == sorted(xs) and xs[50] == 512
    assert min(xs) >= 32 and max(xs) <= 2048
    assert traffic.stratified_lengths({"dist": "fixed", "value": 64}, 3) == \
        [64, 64, 64]
    with pytest.raises(ValueError):
        traffic.stratified_lengths({"dist": "zipf"}, 3)


def test_paced_due_times_one_arrival_per_interval():
    rate = 1.4
    dues = traffic.paced_due_times(200, rate, random.Random(5), 35)
    for i, t in enumerate(dues):
        assert (35 + i) / rate <= t < (36 + i) / rate
    sched = traffic.open_loop_schedule(_mix("chat_steady"), 9, 51)
    window = [r for r in sched if r.phase == "window"]
    lead = _mix("chat_steady")["lead_in_s"]
    assert all(lead <= r.due_s < lead + 51 for r in window)


def test_closed_loop_multiset():
    mix = _mix("docs_batch")
    a = traffic.closed_loop_requests(mix, 1)
    b = traffic.closed_loop_requests(mix, 2)
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert all(1024 <= r.prompt_len <= 3584 and r.answer_len == 64
               for r in a)


def test_warm_lengths_cover_every_prefill_program():
    lengths = [40, 100, 128, 300, 512, 513, 600, 1100, 1536, 2048]
    warm = traffic.warm_lengths(lengths, 512, 64)
    have = set().union(*(traffic.prefill_programs(n, 512, 64)
                         for n in warm))
    for n in lengths:
        assert traffic.prefill_programs(n, 512, 64) <= have
    # A preempted request returns at prompt + answer so far: any bucket at
    # any width the traffic reaches.
    for n in (1536 + 7, 1024 + 33, 512 + 200):
        assert traffic.prefill_programs(n, 512, 64) <= have
    assert traffic.prefill_programs(300, 512, 64) == {("full", 512)}
    assert traffic.prefill_programs(1100, 512, 64) == {
        (512, 8), (512, 16), (128, 32)}
    assert len(warm) < 25


@pytest.mark.parametrize("k_first", [16, 1])
def test_tpot_gives_the_step_time_whatever_the_first_delivery(k_first):
    step, n = 0.080, 128
    # Tokens are made every ``step``; the first ``k_first`` are delivered
    # together when the last of them exists, the rest as they are made.
    made = [i * step for i in range(n)]
    arrivals = [made[k_first - 1]] * k_first + made[k_first:]
    assert stats.tpot_ms(arrivals) == pytest.approx(step * 1e3, rel=1e-9)
    # The naive (t_last - t_first) / (n - 1) is below the step time while
    # sixteen arrive at once: what the definition is there to avoid.
    naive = (arrivals[-1] - arrivals[0]) / (n - 1) * 1e3
    assert (naive < step * 1e3 * 0.9) == (k_first == 16)
    assert stats.tpot_ms([1.0] * k_first) is None


def test_percentile_and_failed_counts_rules():
    assert stats.percentile([5, 1, 4, 2, 3], 50) == 3
    assert stats.percentile(list(range(1, 101)), 90) == 90
    assert stats.percentile([1.0], 90) == 1.0
    with pytest.raises(ValueError):
        stats.percentile([], 90)
    vals = stats.with_failures([100.0, None, 200.0])
    assert vals == [100.0, 2000.0, 200.0]
    # One failure in ten is the 90th percentile's neighbour, two are it.
    ok = [100.0] * 8
    assert stats.percentile(stats.with_failures(ok + [150.0, None]), 90) \
        == 150.0
    assert stats.percentile(stats.with_failures(ok + [None, None]), 90) \
        == 1000.0
    assert stats.ttft_ms(10.0, None) is None
    assert stats.ttft_ms(10.0, 10.5) == pytest.approx(500.0)


def test_tokens_per_s_counts_only_inside_the_window():
    credits = [(0.5, 100), (1.0, 3000), (1.5, 1), (10.9, 1), (11.0, 7)]
    assert stats.tokens_per_s(credits, 1.0, 11.0) == \
        pytest.approx(3002 / 10.0)


def test_whole_steps_rate_ignores_the_edges():
    step = 0.428
    steps = [(i * step, (i + 1) * step) for i in range(-1, 60)]
    r20 = stats.whole_steps_rate(steps, 0.1, 20.1, 256 * 196, 1)
    r21 = stats.whole_steps_rate(steps, 0.1, 20.45, 256 * 196, 1)
    assert r20 == pytest.approx(256 * 196 / step, rel=1e-9)
    assert r21 == pytest.approx(r20, rel=1e-9)
    assert stats.whole_steps_rate(steps, 0.1, 20.1, 256 * 196, 4) == \
        pytest.approx(r20 / 4)
    with pytest.raises(ValueError):
        stats.whole_steps_rate(steps, 0.1, 0.2, 1, 1)


def test_spread_is_the_contracts():
    vals = [100, 101, 102, 103, 104, 105]
    import statistics

    q = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q[2] - q[0]) / 102.5)


def test_interval_arithmetic():
    assert tracered.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]
    assert tracered.total([(0, 2.5), (3, 4)]) == 3.5
    assert tracered.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tracered.subtract([(0, 1), (5, 6)], [(0, 6)]) == []
    assert tracered.subtract([(0, 1)], []) == [(0, 1)]
    assert tracered.op_label(
        "%fusion.173 = bf16[2048,64,8,128]{3,2,1,0:T(8,128)(2,1)} "
        "fusion(%p)") == "fusion.173 bf16[2048,64,8,128]"


def test_trace_reduction_on_a_recorded_v5e_trace():
    """Four steps of four matmul+tanh programs each, ~10 ms of host sleep
    between steps under a ``bench:host_gap`` annotation (recorded on a TPU
    v5e, PR 23)."""
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        trace = json.load(f)
    r = tracered.reduce(trace, min_gap_s=0.0005)
    ops = tracered.device_ops(trace)[0]
    assert r["busy_s"] == pytest.approx(
        tracered.total(tracered.union([(s, e) for _, s, e in ops])))
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_pct"] == pytest.approx(
        100 * (1 - r["busy_s"] / r["window_s"]))
    assert r["idle_pct"] > 90          # the host slept most of the time
    assert r["device_ops"][0][0].startswith("convolution_tanh_fusion")
    assert r["device_ops"][0][1] == pytest.approx(16 * 92e-6, rel=0.05)
    big = [g for g in r["idle_gaps"] if g[1] > 0.005]
    assert len(big) == 3 and all(name == "host_gap" for name, _ in big)
    assert r["collective_exposed_s"] == 0.0
    assert tracered.reduce({"planes": []}) is None


def test_exposed_collective_time():
    ev = [["%while.9 = (s32[]) while(%t), body=%b", 0, 400],
          ["%all-gather.1 = bf16[8]{0} all-gather(%p)", 0, 100],
          ["%fusion.2 = bf16[8]{0} fusion(%p)", 50, 100],
          ["%all-reduce-start.3 = f32[8]{0} all-reduce-start(%p)", 200, 50]]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ev}]}]}
    r = tracered.reduce(trace)
    # all-gather alone on [0, 50), all-reduce-start alone on [200, 250);
    # the loop that spans them all is not compute that hides them.
    assert r["collective_exposed_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(400e-9)
    assert {n for n, _ in r["device_ops"]} == {
        "all-gather.1 bf16[8]", "fusion.2 bf16[8]",
        "all-reduce-start.3 f32[8]"}
    assert all(not n.startswith("while") for n, _ in r["device_ops"])


def test_peaks_table_raises_on_an_unknown_device():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")


def test_flops_formulas_match_the_programs():
    from ray_tpu.models import llama, vit

    from benchmarks.families import llama as fl, vit as fv

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "internlm2-1.8b.json")) as f:
        m = json.load(f)["model"]
    cfg = fl.model_config(m)
    assert flops.llama_params(m) == cfg.num_params()
    assert 1.88e9 < flops.llama_params(m) < 1.90e9
    assert flops.llama_train_flops_per_token(m, 4096) == pytest.approx(
        llama.flops_per_token(cfg, 4096))
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "vit-b16.json")) as f:
        v = json.load(f)["model"]
    assert flops.vit_train_flops_per_token(v) * 196 == pytest.approx(
        vit.flops_per_image(fv.model_config(v)), rel=1e-4)


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "metrics", m["name"] + ".py"))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= 1


# ------------------------------------------------------- the serve seam

TOY = {"family": "llama", "model": {
    "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 64, "vocab_size": 61,
    "max_position_embeddings": 64, "rope_theta": 10000,
    "rms_norm_eps": 1e-5}}


def test_llama_serve_is_the_reference_and_tolerance_of_the_parent():
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.decode import LlamaDecodeDeployment

    from benchmarks import families
    from benchmarks.reference import llama_ref

    fam = families.serve(TOY)
    assert (fam.tolerance, fam.reference) == (0.25, "llama_ref")
    assert fam.vocab == 61 and fam.model_cfg.n_layers == 2
    assert fam.deployment_class() is LlamaDecodeDeployment
    params = llama.init_params(fam.model_cfg, jax.random.key(3))
    prompts, answers = [[5, 9, 2, 40], [7, 7, 1]], [[3, 60], [0, 12]]
    want = llama_ref.served_token_margins(params, fam.model_cfg, prompts,
                                          answers)
    got = families.load("llama").Serve.reference_margins(
        params, fam.model_cfg, prompts, answers)
    assert got == want and len(got) == 4 and min(got) >= 0.0


def test_check_sizes_default_to_the_parents_draws_and_a_file_overrides():
    from benchmarks import families, serve_cell

    assert families.serve(TOY).check == families.SERVE_CHECK == {
        "prompts": 4, "prompt_len": [16, 128], "tokens": 8}
    # What serve_cell._check drew before the seam, draw for draw.
    rng = random.Random(3000000019)
    old = [(rng.randrange(16, 129), 8, rng.getrandbits(48))
           for _ in range(4)]
    new = serve_cell.check_requests(families.SERVE_CHECK, 3000000019)
    assert [(r.prompt_len, r.answer_len, r.token_seed) for r in new] == old
    over = dict(TOY, serve={"check": {"prompts": 2, "prompt_len": [3, 3]}})
    sizes = families.serve(over).check
    assert sizes == {"prompts": 2, "prompt_len": [3, 3], "tokens": 8}
    reqs = serve_cell.check_requests(sizes, 5)
    assert [(r.prompt_len, r.answer_len) for r in reqs] == [(3, 8), (3, 8)]


def test_the_control_of_correct_fails_and_the_reference_itself_passes():
    """The reference in the program's place answers with margin 0; with
    its weights rounded coarsely enough for this toy size (2 bits; the
    chip's control rounds to 8, ``benchmarks/control.py``) the same
    comparison comes out as not correct."""
    import jax
    import numpy as np

    from ray_tpu.models import llama

    from benchmarks import families
    from benchmarks.reference import llama_ref

    fam = families.serve(TOY)
    params = llama.init_params(fam.model_cfg, jax.random.key(7))
    prompts = [[5, 9, 2, 40, 11], [7, 7, 1]]
    answers = llama_ref.greedy_answers(params, fam.model_cfg, prompts, 4)
    assert [len(a) for a in answers] == [4, 4]
    assert max(llama_ref.served_token_margins(
        params, fam.model_cfg, prompts, answers)) == 0.0
    assert max(fam.control_margins(7, prompts, 4, 2)) > fam.tolerance
    w = params["layers"]["w_down"]
    r = llama_ref.rounded_weights(params, 8)["layers"]["w_down"]
    step = np.abs(np.asarray(w)).max(axis=1, keepdims=True) / 127
    assert 0 < np.abs(np.asarray(r - w)).max() <= step.max() / 2 * 1.001
    assert np.array_equal(
        llama_ref.rounded_weights(params)["final_norm"],
        params["final_norm"])


@pytest.mark.parametrize("module", ["serve_cell.py", "serve_replica.py"])
def test_serve_halves_reach_the_architecture_through_the_family(module):
    """No import of a reference, of a family by name, or of a class of
    ``ray_tpu.serve.decode``: those belong to ``families/<family>.py``."""
    with open(os.path.join(ROOT, "benchmarks", module)) as f:
        tree = ast.parse(f.read())
    imported = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported += [(a.name, None) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            imported += [(node.module or "", a.name) for a in node.names]
    for mod, name in imported:
        whole = f"{mod}.{name}" if name else mod
        assert "benchmarks.reference" not in whole, whole
        assert not whole.startswith("benchmarks.families."), whole
        assert not whole.startswith("ray_tpu.serve.decode"), whole
    names = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)} | \
        {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
    assert not {"llama_ref", "vit_ref", "LlamaDecodeDeployment",
                "LOGIT_TOLERANCE"} & names


def test_bench_deployment_wraps_the_familys_class_and_ships_by_value():
    from ray_tpu.core import serialization
    from ray_tpu.serve.decode import LlamaDecodeDeployment

    from benchmarks import serve_replica

    cls = serve_replica.bench_deployment("llama")
    assert cls.__mro__[1:3] == (serve_replica.BenchDecodeDeployment,
                                LlamaDecodeDeployment)
    assert cls.bench_family == "llama"
    back = serialization.loads_function(serialization.dumps_function(cls))
    assert back.bench_family == "llama"
    assert issubclass(back, LlamaDecodeDeployment)
    assert back.bench_reference_margins is not None


def test_resumed_warm_up_runs_the_programs_a_preempted_request_returns_by():
    """``traffic.resumed_prefills`` names (cached prefix, suffix) pairs and
    ``bench_warm_resumed`` sends them; the engine then has run the suffix
    prefill of every (bucket, width) a power-of-two prefix can lead to."""
    pairs = traffic.resumed_prefills(2463, 512, 64, {(512, 16), (16, 32)})
    programs = {(traffic._pow2(s, 16), traffic._pow2(-(-(p + traffic._pow2(
        s, 16)) // 64), 1)) for p, s in pairs}
    assert len(programs) == len(pairs)
    assert {(16, 2), (64, 2), (128, 4), (256, 8), (16, 16), (512, 64),
            (16, 64)} <= programs
    assert not {(512, 16), (16, 32), (512, 8), (128, 2)} & programs
    assert all(p + s <= 2463 for p, s in pairs)

    from benchmarks import families, serve_replica

    fam = families.serve(dict(TOY, model=dict(
        TOY["model"], max_position_embeddings=512)))
    dep = serve_replica.bench_deployment("llama")(
        config=fam.model_cfg, seed=0, slots=4, capacity=256,
        kv_page_tokens=8, kv_pool_pages=96, prefill_chunk_tokens=32)
    try:
        pairs = traffic.resumed_prefills(136, 32, 8, set())
        dep.bench_warm_resumed(pairs, fam.vocab)
        ran = {k[2:] for k in dep.engine._compiled
               if k[:2] == ("paged_suffix", 1)}
    finally:
        dep.engine.shutdown()
    # Prefixes of 32, 64 and 128 tokens in pages of 8 (one of 8 is under
    # the 16 tokens from which the program's index matches).
    assert pairs == [(8, 16), (8, 32), (32, 16), (64, 16), (64, 32),
                     (128, 8)]
    assert {(16, 8), (16, 16), (32, 16), (16, 32)} <= ran


def _copy_of_the_benchmark(tmp_path):
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return root, json.load(f)


def _run(args, env_extra=None, cwd=ROOT, timeout=600, run=RUN):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, run] + args, env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


def test_runner_refuses_to_measure_on_a_cpu_backend():
    p = _run(["--workload", "vit-b16.train", "--seed", "1", "--seconds",
              "1", "--trace", "0"], {"JAX_PLATFORMS": "cpu"}, timeout=120)
    assert p.returncode != 0
    assert "needs the chip" in p.stderr
    assert not p.stdout.strip().startswith("{")


def test_a_serve_cell_on_a_family_without_serve_fails_at_once(tmp_path):
    from benchmarks import families

    with pytest.raises(ValueError, match="family 'vit' has no Serve"):
        families.serve({"family": "vit", "model": {}})
    root, bench = _copy_of_the_benchmark(tmp_path)
    bench["workloads"].append({
        "name": "vit-b16.chat_steady", "config": "vit-b16",
        "traffic": "chat_steady", "chips": 1, "why": "cannot be"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    p = _run(["--workload", "vit-b16.chat_steady", "--seed", "1",
              "--seconds", "1", "--trace", "0", "--rehearse",
              "--bench-root", str(root)], timeout=120)
    assert p.returncode != 0
    assert "family 'vit' has no Serve" in p.stderr
    assert "a serve cell cannot run on it" in p.stderr
    assert "Traceback" not in p.stderr
    assert not p.stdout.strip().startswith("{")


def _last_json(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("workload,metric", [
    ("internlm2-1.8b.chat_steady", "ttft_p90_ms"),
    ("vit-b16.train", "train_tokens_per_s_per_chip"),
    ("internlm2-1.8b.docs_batch", "serve_tokens_per_s"),
    ("internlm2-1.8b.pretrain_fsdp4", "train_tokens_per_s_per_chip"),
])
def test_rehearsal_runs_every_cell(workload, metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        chips = {w["name"]: w["chips"]
                 for w in json.load(f)["workloads"]}[workload]
    for trace in ("0", "1"):
        p = _run(["--workload", workload, "--seed", "3000000019",
                  "--seconds", "4", "--trace", trace, "--rehearse"])
        assert p.returncode == 0, p.stderr[-2000:]
        line = _last_json(p.stdout)
        assert set(line) == {"correct", "attempted", "failed", "metrics",
                             "device"}
        assert line["correct"] and line["failed"] == 0
        assert line["device"]["platform"] == "cpu"
        assert line["device"]["count"] == chips
        assert "busy_s" not in line["device"]
        names = set(line["metrics"])
        assert (metric in names) == (trace == "0")
        assert ("setup_s" in names) == (trace == "0")
        # No device metric may come out of a CPU run.
        assert not any(n.startswith("device_idle_pct") or "mfu" in n
                       or n.startswith("collective_") for n in names)
        if trace == "1":
            assert any(n.startswith("compiles_in_window") for n in names)


@pytest.mark.slow
def test_a_cell_is_added_with_files_and_entries_only(tmp_path):
    """A dummy configuration, traffic mix and per-layer metric, each a new
    file plus a new entry in a temporary copy; no file that was there is
    edited, and the rehearsal runs the new cell and reports the metric."""
    root, bench = _copy_of_the_benchmark(tmp_path)
    with open(root / "benchmarks" / "configs" / "vit-b16.json") as f:
        conf = json.load(f)
    conf["name"] = "vit-dummy"
    conf["rehearse"]["model"]["num_hidden_layers"] = 1
    (root / "benchmarks" / "configs" / "vit-dummy.json").write_text(
        json.dumps(conf))
    with open(root / "benchmarks" / "traffic" / "train.json") as f:
        mix = json.load(f)
    mix["rehearse"]["microbatch"] = 4
    (root / "benchmarks" / "traffic" / "train_small.json").write_text(
        json.dumps(mix))
    (root / "benchmarks" / "metrics" / "steps_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx['step_ms']))\n")
    bench["configs"].append({
        "name": "vit-dummy", "source": conf["source"],
        "file": "benchmarks/configs/vit-dummy.json", "reduced": [],
        "why": "dummy"})
    bench["workloads"].append({
        "name": "vit-dummy.train_small", "config": "vit-dummy",
        "traffic": "train_small", "chips": 1, "why": "dummy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "vit-b16.train" in m.get("workloads", []):
            m["workloads"].append("vit-dummy.train_small")
    bench["per_layer"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "train step",
        "moves": "train_tokens_per_s_per_chip",
        "workloads": ["vit-dummy.train_small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    p = _run(["--workload", "vit-dummy.train_small", "--seed", "5",
              "--seconds", "2", "--trace", "1", "--rehearse",
              "--bench-root", str(root)])
    assert p.returncode == 0, p.stderr[-2000:]
    line = _last_json(p.stdout)
    assert line["correct"]
    assert line["metrics"]["steps_in_window"]["value"] > 0
    assert "train_step_ms_p50" in line["metrics"]


DUMMY_FAMILY = '''"""A second decoder family: the program's deployment,
a reference and a tolerance of its own."""
from benchmarks.families import llama
from benchmarks.reference import dummy_ref


class Serve(llama.Serve):
    reference = "dummy_ref"
    tolerance = dummy_ref.TOLERANCE
    reference_margins = staticmethod(dummy_ref.served_token_margins)
'''

DUMMY_REFERENCE = '''"""The dummy family's reference: the decoder's, and
a refusal of any check that is not of the sizes its configuration states."""
from benchmarks.reference import llama_ref

TOLERANCE = 0.125


def served_token_margins(params, cfg, prompts, answers):
    sizes = [(len(p), len(a)) for p, a in zip(prompts, answers)]
    if len(sizes) != 2 or any(not 8 <= p <= 24 or a != 3 for p, a in sizes):
        raise ValueError(f"not the check of serve.check: {sizes}")
    return llama_ref.served_token_margins(params, cfg, prompts, answers)
'''


@pytest.mark.slow
def test_a_serve_family_is_added_with_files_and_entries_only(tmp_path):
    """The twin of the test above for serving: a dummy SERVE family
    (``families/dummy.py``, ``reference/dummy_ref.py`` with its own
    tolerance), a configuration of it with a ``serve.check`` override, a
    traffic mix and a metric, all new files plus new entries; the rehearsal
    is correct and its ``[bench]`` line names the dummy's reference and
    tolerance."""
    root, bench = _copy_of_the_benchmark(tmp_path)
    b = root / "benchmarks"
    before = {str(p): p.read_bytes() for p in b.rglob("*") if p.is_file()}
    (b / "families" / "dummy.py").write_text(DUMMY_FAMILY)
    (b / "reference" / "dummy_ref.py").write_text(DUMMY_REFERENCE)
    with open(b / "configs" / "internlm2-1.8b.json") as f:
        conf = json.load(f)
    conf.update(name="dummy-1.8b", family="dummy")
    conf["serve"]["check"] = {"prompts": 2, "prompt_len": [8, 24],
                              "tokens": 3}
    (b / "configs" / "dummy-1.8b.json").write_text(json.dumps(conf))
    with open(b / "traffic" / "chat_steady.json") as f:
        mix = json.load(f)
    mix["rehearse"]["rate_rps"] = 3
    (b / "traffic" / "chat_slow.json").write_text(json.dumps(mix))
    (b / "metrics" / "requests_in_window.py").write_text(
        "from benchmarks.metrics import _common\n\n\n"
        "def read(ctx):\n    return float(len(_common.measured(ctx)))\n")
    cell = "dummy-1.8b.chat_slow"
    bench["configs"].append({
        "name": "dummy-1.8b", "source": conf["source"],
        "file": "benchmarks/configs/dummy-1.8b.json", "reduced": [],
        "why": "dummy"})
    bench["workloads"].append({
        "name": cell, "config": "dummy-1.8b", "traffic": "chat_slow",
        "chips": 1, "why": "dummy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "internlm2-1.8b.chat_steady" in m.get("workloads", []):
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "requests_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "load generator (benchmark)",
        "moves": "ttft_p90_ms", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert {str(p): p.read_bytes() for p in b.rglob("*")
            if p.is_file() and str(p) in before} == before
    # Run the COPY's run.py as a whole checkout (the program linked in):
    # the new family and its reference exist only there, and the replica
    # has to import them too.
    os.symlink(os.path.join(ROOT, "ray_tpu"), root / "ray_tpu")
    p = _run(["--workload", cell, "--seed", "5", "--seconds", "3",
              "--trace", "1", "--rehearse"], run=str(b / "run.py"),
             cwd=str(root), env_extra={"JAX_COMPILATION_CACHE_DIR":
                                       os.path.join(ROOT, ".jax_cache")})
    assert p.returncode == 0, p.stderr[-2000:]
    line = _last_json(p.stdout)
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] == 3 * 3 + 2      # the window's, the check's
    assert line["metrics"]["requests_in_window"]["value"] == 9
    assert "decode_step_ms_p50" in line["metrics"]
    said = [ln for ln in p.stdout.splitlines()
            if ln.startswith("[bench]") and "served-token margin" in ln]
    assert len(said) == 1 and said[0].rstrip().endswith(
        "<= 0.125 (dummy_ref)"), said
