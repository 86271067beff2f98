"""The cell ``nemotron-3-super.agent_turns_batch`` (PR 57): its rehearsal
(the cell's whole control flow on the CPU at the toy size of its files'
``rehearse`` blocks: Mamba-2, attention and expert layers, an engine with
one page kind beside slot state; marked slow as its twins are), its entries
in ``BENCHMARK.json`` (membership and relative order only), its traffic's
fixed multiset, the arithmetic of ``benchmarks/nemotron_h_counts.py`` on
rows and shapes made by hand, and what PR 55's
``test_setup_metrics.py::test_the_benchmark_lists_the_eleven_under_setup_s``
meant, computed from ``BENCHMARK.json`` as it now is (that test pins nine
cells, and only a ``benchmark`` PR may edit its file)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
METRICS = os.path.join(ROOT, "benchmarks", "metrics")
CELL = "nemotron-3-super.agent_turns_batch"
# PR 57's per-layer metrics, in the order BENCHMARK.json has them.
NEW_IN_ORDER = ["serve_mfu_pct.nemotronh.batch",
                "ssd_chunk_roofline_pct.nemotronh.batch",
                "ssd_step_roofline_pct.nemotronh.batch",
                "moe_experts_roofline_pct.nemotronh.batch",
                "moe_tokens_per_expert_mean.nemotronh.batch",
                "cache_bytes_per_ctx_token.nemotronh.batch"]
# The accepted metrics whose readers read the cell unchanged.
JOINED = ["active_slots_mean.batch", "preempted.batch",
          "chunk_step_ms_p50.batch", "compiles_in_window.batch",
          "device_idle_pct.batch", "step_host_ms_p50.batch",
          "pages_ms_per_step.batch", "prefill_useful_ratio.batch",
          "chunk_device_ms_p50.batch", "device_idle_unattributed_pct.batch",
          "token_delivery_ms_p50.batch", "stream_items_per_pull_mean.batch",
          "chunk_ahead_share.batch", "decode_device_ms_p50.phi4flash.batch"]
# What the cell stays off: no prefix index, and the lists an accepted test
# pins to equality (``test_deepseek_cell.py``: deepseek's five).
NOT_JOINED = ["pages_pinned_prefix_mean.batch", "serve_mfu_pct.batch",
              "moe_experts_roofline_pct.batch",
              "moe_tokens_per_expert_mean.batch",
              "kv_bytes_per_ctx_token.batch",
              "latent_attn_roofline_pct.batch",
              "state_bytes_per_ctx_token.brumby.batch"]
# The eleven set-up entries (PR 55): seven tile ``setup_s``, four are views.
SETUP = ["setup_runtime_start_s", "setup_probe_wait_s", "setup_placement_s",
         "setup_device_init_s", "setup_weights_s", "setup_engine_build_s",
         "setup_first_dispatch_s", "setup_compile_s",
         "setup_cache_hit_share", "setup_before_window_s",
         "setup_unattributed_s"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron-3-super.json")) as f:
        return json.load(f)


@pytest.mark.slow
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_nemotron_cell(trace):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "3000000019",
         "--seconds", "4", "--trace", trace, "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    names = set(line["metrics"])
    assert ("serve_tokens_per_s" in names) == (trace == "0")
    assert ("setup_s" in names) == (trace == "0")
    # No share of a peak or of a roofline may come out of a CPU run.
    assert not [n for n in names if "mfu" in n or "roofline" in n]
    if trace == "1":
        assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
        assert line["metrics"]["preempted.batch"]["value"] == 0
        # What the program counts is read on the CPU too.
        assert names & set(NEW_IN_ORDER) == {
            "moe_tokens_per_expert_mean.nemotronh.batch",
            "cache_bytes_per_ctx_token.nemotronh.batch"}
        assert line["metrics"][
            "cache_bytes_per_ctx_token.nemotronh.batch"]["value"] > 0
        assert not names & set(NOT_JOINED)
        assert set(SETUP) - {"setup_probe_wait_s"} <= names


def test_the_cells_entries_name_their_files():
    """Membership only, found by name: a later PR appends a cell to any
    of these lists, or an entry behind these, without an edit here."""
    bench = _bench()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_IN_ORDER + JOINED:
        m = by_name[name]
        assert CELL in m["workloads"], name
        assert m["moves"] == "serve_tokens_per_s", name
        assert os.path.isfile(os.path.join(METRICS, name + ".py")), name
    for name in NEW_IN_ORDER:
        assert by_name[name]["workloads"] == [CELL] or \
            by_name[name]["workloads"][0] == CELL, name
    for name in NOT_JOINED:
        assert CELL not in by_name[name]["workloads"], name
    # ONE of the three readers of the decode's device time, not a fourth.
    assert sum(CELL in m["workloads"] for n, m in by_name.items()
               if n.startswith("decode_device_ms_p50")) == 1
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    config = next(c for c in bench["configs"]
                  if c["name"] == "nemotron-3-super")
    assert os.path.isfile(os.path.join(ROOT, config["file"]))
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["source"] == _config()["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("nemotron-3-super", "agent_turns_batch", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "traffic", cell["traffic"] + ".json"))
    # One cell in four may take four chips, and one always may.
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert len(bench["workloads"]) <= 24


def test_the_new_metrics_stand_behind_the_earlier_ones_in_their_order():
    """Relative order only, without pinning the list's tail."""
    names = [m["name"] for m in _bench()["per_layer"]]
    assert len(names) == len(set(names))
    mine = [names.index(n) for n in NEW_IN_ORDER]
    assert mine == sorted(mine)
    assert mine[0] > max(names.index(n) for n in SETUP)
    cells = [w["name"] for w in _bench()["workloads"]]
    assert cells.index(CELL) > cells.index("brumby-14b.long_context_batch")
    configs = [c["name"] for c in _bench()["configs"]]
    assert configs.index("nemotron-3-super") > configs.index("brumby-14b")


def test_the_eleven_set_up_entries_their_readers_and_their_lists():
    """What ``test_the_benchmark_lists_the_eleven_under_setup_s`` meant,
    without its count of nine cells: the eleven entries stand together in
    their order, each with its reader, its unit and its source, moving
    ``setup_s`` in the layer ``set-up``; the seven that a serve replica's
    start alone has list the serve cells, the others all cells, AS
    ``BENCHMARK.json`` NOW HAS THEM; and ``chunk_ahead_share.batch`` stands
    right before them over the served batch cells."""
    bench = _bench()
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("setup_runtime_start_s")
    block = bench["per_layer"][at:at + 11]
    assert [m["name"] for m in block] == SETUP
    cells = [w["name"] for w in bench["workloads"]]
    serve = [w["name"] for w in bench["workloads"]
             if w["traffic"] not in ("train", "pretrain_fsdp4")]
    assert CELL in serve and len(cells) - len(serve) == 2
    all_cells = {m["name"] for m in block if m["workloads"] == cells}
    serve_only = {m["name"] for m in block if m["workloads"] == serve}
    assert all_cells | serve_only == set(SETUP)
    assert serve_only == {"setup_engine_build_s", "setup_first_dispatch_s",
                          "setup_before_window_s"}
    for m in block:
        assert os.path.exists(os.path.join(METRICS, m["name"] + ".py"))
        assert m["moves"] == "setup_s" and m["layer"] == "set-up"
        if m["name"] == "setup_cache_hit_share":
            assert (m["unit"], m["better"], m["source"]) == (
                "share", "higher", "program_counter")
        else:
            assert (m["unit"], m["better"], m["source"]) == (
                "s", "lower", "program_span")
    batch = [c for c in serve if c != "internlm2-1.8b.chat_steady"]
    ahead = bench["per_layer"][at - 1]
    assert ahead["name"] == "chunk_ahead_share.batch"
    assert ahead["workloads"] == batch and CELL in batch
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "workloads" not in e2e["setup_s"]       # every cell reports it
    assert e2e["serve_tokens_per_s"]["workloads"] == batch


def test_the_file_holds_the_catalogs_keys_and_states_the_cut():
    from benchmarks import families, run

    config = _config()
    share = config["share"]
    assert share["published"] == {"num_hidden_layers": 88,
                                  "n_routed_experts": 512,
                                  "vocab_size": 131072}
    assert (share["chips_per_layer"], share["pipeline_stages"],
            share["first_expert"]) == (4, 8, 0)
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (11, 128, 32768)
    assert len(config["hybrid_override_pattern"]) == 88
    assert config["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    for word in ("32 chips", "131,072", "512", "LAST stage"):
        assert word in config["deployment"], word
    assert "LEFT OUT" in config["assumed"]["about"]["mtp"]
    fam = families.serve(config)
    cfg = fam.model_cfg
    assert (cfg.n_layers, cfg.dim, cfg.vocab_size, cfg.n_heads,
            cfg.n_kv_heads, cfg.head_dim) == (11, 4096, 32768, 32, 2, 128)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups,
            cfg.ssm_state, cfg.d_conv, cfg.chunk_size) == \
        (128, 64, 8, 128, 4, 128)
    assert (cfg.latent, cfg.expert_dim, cfg.shared_dim, cfg.top_k,
            cfg.n_routed_experts, cfg.held) == \
        (1024, 2688, 5376, 22, 512, (0, 128))
    assert cfg.routed_scale == 5.0 and cfg.norm_eps == 1e-5
    assert [s.kind[0] for s in cfg.segments()] == list("memememfeme")
    assert fam.deployment_class().__name__ == "NemotronHDecodeDeployment"
    assert not hasattr(families.load("nemotron_h"), "Train")
    assert fam.check["prompt_len"] == [1024, 6144]
    assert fam.check["tokens"] == 64 and fam.check["prompts"] == 4
    toy = families.serve(run.merge(config, config["rehearse"])).model_cfg
    assert (toy.n_layers, toy.pattern, toy.held, toy.n_routed_experts) == \
        (9, "MEMM*EM*E", (0, 8), 16)
    for key, other in (("model_type", "mamba2"), ("mlp_hidden_act", "silu"),
                       ("tie_word_embeddings", True), ("n_group", 2),
                       ("use_conv_bias", False)):
        with pytest.raises(ValueError, match="not implemented"):
            families.serve({**config, key: other})
    with pytest.raises(ValueError, match="expand"):
        families.serve({**config, "expand": 4})


def test_the_traffic_is_the_multiset_the_cell_was_sized_for():
    from benchmarks import traffic

    mix = traffic.load("agent_turns_batch")
    prompts = traffic.stratified_lengths(mix["prompt"], mix["requests"])
    answers = traffic.stratified_lengths(mix["answer"], mix["requests"])
    layout = _config()["serve"]["layouts"][mix["layout"]]
    assert (layout["slots"], layout["capacity"], layout["kv_page_tokens"],
            layout["kv_pool_pages"], layout["prefill_chunk_tokens"]) == \
        (96, 20480, 64, 24576, 2048)
    assert max(prompts) + max(answers) <= layout["capacity"]
    assert min(prompts) >= 1024 and max(prompts) <= 16384
    assert min(answers) >= 128 and max(answers) <= 1536
    n = mix["requests"]
    assert 4700 < sum(prompts) / n < 5500
    assert 540 < sum(answers) / n < 620
    # The slots bind, not the pool: 96 mean contexts are ~0.55 M tokens of
    # the pool's 1.57 M (only 96 of the very longest would run it dry).
    pool = layout["kv_pool_pages"] * layout["kv_page_tokens"]
    live = layout["slots"] * (sum(prompts) + sum(answers) / 2) / n
    assert pool == 1_572_864 and 0.3 * pool < live < 0.4 * pool
    # Prompts that fit a chunk go as whole-prefill waves, which are warmed
    # up to the wave cap of 4,096 tokens.
    assert any(p <= layout["prefill_chunk_tokens"] for p in prompts)
    assert mix["warm_waves"] == [2, 4] and "warm_resumed" not in mix
    # ISSUE 57's multiset, letter for letter.
    assert (mix["clients"], n, mix["loop"]) == (144, 256, "closed")
    assert (mix["prompt"]["median"], mix["prompt"]["sigma"],
            mix["prompt"]["min"], mix["prompt"]["max"]) == \
        (4096, 0.7, 1024, 16384)
    assert (mix["answer"]["median"], mix["answer"]["sigma"],
            mix["answer"]["min"], mix["answer"]["max"]) == \
        (512, 0.5, 128, 1536)
    assert mix["clients"] == 1.5 * layout["slots"]
    assert mix["lead_in_s"] == 45 and mix["drain_s"] == 0


def test_the_counts_follow_the_shapes():
    from benchmarks import nemotron_h_counts as nc

    m = _config()
    # ISSUE 57's table: the matrices of an M layer 109.58 M (109.64 with
    # conv, scalars and norms), a * layer 35.65 M, an E layer outside its
    # experts 54.53 M, an expert 5.505 M.
    assert nc.mamba_params(m) == 4096 * 18560 + 8192 * 4096
    assert nc.attn_params(m) == 2 * 4096 * 4096 + 4096 * 512
    assert nc.experts_rest_params(m) == 4096 * 512 + 2 * 4096 * 1024 \
        + 2 * 4096 * 5376
    assert nc.expert_params(m) == 5_505_024
    assert (nc.layers_of(m, "M"), nc.layers_of(m, "E"),
            nc.layers_of(m, "*")) == (5, 5, 1)
    assert nc.held_pairs_per_token(m) == 5.5
    assert nc.ssd_token_flops(m) == 4.0 * 128 * 64 * 128
    assert nc.ssd_chunk_token_flops(m) == 128 * (2.0 * 128 * 64
                                                 + 4.0 * 64 * 128) \
        + 8 * 2.0 * 128 * 128 == 6_553_600
    # A chunk of 2,048 tokens is ~4.2 TFLOP of matrices.
    assert 4.0e12 < 2048 * nc.token_matmul_flops(m) < 4.4e12
    assert nc.head_flops(m) == 2.0 * 4096 * 32768
    assert nc.state_slot_bytes(m) == 4_194_304
    assert nc.kv_token_bytes(m) == 1024
    # A decode step of 96 slots: 4.0 GB of state beside 1.41 GB of experts
    # a layer.
    assert round(96 * 5 * 2 * nc.state_slot_bytes(m) / 1e9, 1) == 4.0
    assert round(128 * nc.expert_params(m) * 2 / 1e9, 2) == 1.41
    assert nc.request_flops(m, 4096, [0, 1, 2]) == \
        4098 * nc.token_matmul_flops(m) + 3 * nc.head_flops(m) \
        + nc.attention_flops(m, 0, 4096) + nc.attention_flops(m, 4096, 1) \
        + nc.attention_flops(m, 4097, 1)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # 528 pairs over 120 hit experts: the weights bind.
    assert nc.experts_least_s(m, 528, 120, peak) == \
        120 * 5_505_024 * 2 / 819e9


def _ctx(rows):
    return {"rows": rows, "wall_window": (0.0, 100.0)}


def test_cache_bytes_per_ctx_token_reads_the_rows(monkeypatch):
    from benchmarks import nemotron_h_counts as nc

    monkeypatch.setattr(nc, "model", _config)
    slot = 5 * (4_194_304 + 61_440)
    rows = [{"t0": 1.0, "t1": 2.0, "state_bytes": 96 * slot,
             "pages_full": 8000, "kv_tokens": 500_000, "pages_free": 0},
            {"t0": 2.0, "t1": 3.0, "state_bytes": 0, "pages_full": 0,
             "kv_tokens": 0},
            {"t0": 200.0, "t1": 201.0, "state_bytes": slot,
             "pages_full": 1, "kv_tokens": 1}]     # outside the window
    got = nc.cache_bytes_per_ctx_token(_ctx(rows))
    assert got == (96 * slot + 8000 * 64 * 1024) / 500_000
    assert 5000 < got < 6144       # under five attention layers' cost
    # A program's rows without the keys: nothing to read.
    assert nc.cache_bytes_per_ctx_token(_ctx(
        [{"t0": 1.0, "t1": 2.0, "pages_free": 3, "kv_tokens": 5}])) is None


def test_the_trace_readers_return_nothing_without_a_trace(monkeypatch):
    from benchmarks import nemotron_h_counts as nc

    monkeypatch.setattr(nc, "model", _config)
    ctx = {"trace": None, "trace_dir": None,
           "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert nc.serve_mfu_pct(ctx) is None
    assert nc.ssd_step_roofline_pct(ctx) is None
    assert nc.ssd_chunk_roofline_pct(ctx) is None
    assert nc.moe_experts_roofline_pct(ctx) is None


def test_an_operation_is_put_down_to_its_scope():
    from benchmarks import nemotron_h_counts as nc

    def op(hlo, path):
        return [hlo, 0, 1, path]

    assert nc.scope_of(op(
        "%ssd_step.3 = custom-call(...)",
        "jit(engine_decode)/while/body/ssd_step/ssd_step")) == "ssd_step"
    assert nc.scope_of(op(
        "%fusion.1 = ...",
        "jit(engine_paged_suffix)/while/body/ssd_chunk/while/body/"
        "dot_general")) == "ssd_chunk"
    assert nc.scope_of(op(
        "%fusion.2 = ...",
        "jit(engine_decode)/while/body/ssd_proj/dot")) == "ssd_proj"
    assert nc.scope_of(op(
        "%fusion.3 = ...",
        "jit(engine_decode)/while/body/moe_experts/mul")) == "moe_experts"
    assert nc.scope_of(op("%ragged-dot.5 = ...", "ragged-dot.5")) \
        == "moe_experts"
    assert nc.scope_of(op("%chunk_attn_full.1 = ...", "")) \
        == "chunk_attn_full"
    assert nc.scope_of(op("%fusion.4 = ...",
                          "jit(engine_decode)/head/dot")) == "head"
    assert nc.scope_of(op("%fusion.9 = ...", "jit(x)/mul")) is None


def test_the_family_fails_at_once_where_the_model_is_absent(monkeypatch):
    """On a checkout from before the model (the parent commit) the cell
    fails cleanly before the runtime starts."""
    import builtins

    from benchmarks import families

    real = builtins.__import__

    def absent(name, *a, **kw):
        if name == "ray_tpu.models.nemotron_h":
            raise ImportError("No module named 'ray_tpu.models.nemotron_h'")
        return real(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", absent)
    monkeypatch.delitem(sys.modules, "ray_tpu.models.nemotron_h",
                        raising=False)
    with pytest.raises(ValueError, match="this checkout has none"):
        families.serve(_config())
