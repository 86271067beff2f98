"""The cell ``command-a-plus.grounded_docs_batch`` (PR 49): its rehearsal
(the cell's whole control flow on the CPU at the toy size of its files'
``rehearse`` blocks: one period ``[window, window, window, full]``, 8 of 16
experts, two averaged shared ones, a window of 12 over pages of 8; marked
slow as its twins are), its entries in ``BENCHMARK.json`` (membership and
relative order only), its traffic's fixed multiset, and the arithmetic of
``benchmarks/cohere2_moe_counts.py`` on rows and shapes made by hand."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
CELL = "command-a-plus.grounded_docs_batch"
# PR 49's per-layer metrics, in the order BENCHMARK.json has them.
NEW_IN_ORDER = ["serve_mfu_pct.cohere2.batch",
                "decode_device_ms_p50.cohere2.batch",
                "chunk_attn_roofline_pct.cohere2.batch",
                "full_attn_roofline_pct.cohere2.batch",
                "window_attn_roofline_pct.cohere2.batch",
                "moe_experts_roofline_pct.cohere2.batch",
                "moe_tokens_per_expert_mean.cohere2.batch",
                "kv_bytes_per_ctx_token.cohere2.batch"]
# The accepted metrics whose readers read the cell unchanged.
JOINED = ["active_slots_mean.batch", "preempted.batch",
          "chunk_step_ms_p50.batch", "compiles_in_window.batch",
          "device_idle_pct.batch", "step_host_ms_p50.batch",
          "pages_ms_per_step.batch", "prefill_useful_ratio.batch",
          "chunk_device_ms_p50.batch",
          "device_idle_unattributed_pct.batch",
          "token_delivery_ms_p50.batch", "stream_items_per_pull_mean.batch"]
# What the cell does not report: no prefix index, and deepseek's five.
NOT_JOINED = ["pages_pinned_prefix_mean.batch", "serve_mfu_pct.batch",
              "decode_device_ms_p50.batch", "latent_attn_roofline_pct.batch",
              "moe_experts_roofline_pct.batch",
              "moe_tokens_per_expert_mean.batch"]
# The last metric of PR 45's block, which PR 49's eight stand behind.
BEFORE = "kv_bytes_per_ctx_token.phi4flash.batch"


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "command-a-plus.json")) as f:
        return json.load(f)


@pytest.mark.slow
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_command_a_cell(trace):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "3000000019",
         "--seconds", "4", "--trace", trace, "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
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
        # What the program counts is read on the CPU too.
        assert 0 < line["metrics"]["kv_bytes_per_ctx_token.cohere2.batch"][
            "value"] < 2000
        assert line["metrics"]["moe_tokens_per_expert_mean.cohere2.batch"][
            "value"] >= 1
        assert "pages_pinned_prefix_mean.batch" not in names
        assert names & set(NEW_IN_ORDER) == {
            "kv_bytes_per_ctx_token.cohere2.batch",
            "moe_tokens_per_expert_mean.cohere2.batch"}


def test_the_cells_entries_name_their_files():
    """Membership only, found by name: a later PR appends a cell to any
    of these lists, or an entry behind these, without an edit here."""
    bench = _bench()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_IN_ORDER + JOINED:
        m = by_name[name]
        assert CELL in m["workloads"], name
        assert m["moves"] == "serve_tokens_per_s", name
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "metrics", name + ".py")), name
    for name in NOT_JOINED:
        assert CELL not in by_name[name]["workloads"], name
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    config = next(c for c in bench["configs"]
                  if c["name"] == "command-a-plus")
    assert os.path.isfile(os.path.join(ROOT, config["file"]))
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("command-a-plus", "grounded_docs_batch", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "traffic", cell["traffic"] + ".json"))
    # One cell in four may take four chips, and one always may.
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_the_new_metrics_stand_behind_the_earlier_ones_in_their_order():
    """Relative order only, without pinning the list's tail."""
    names = [m["name"] for m in _bench()["per_layer"]]
    assert len(names) == len(set(names))
    mine = [names.index(n) for n in NEW_IN_ORDER]
    assert mine == sorted(mine) and mine[0] > names.index(BEFORE)
    cells = [w["name"] for w in _bench()["workloads"]]
    assert cells.index(CELL) > cells.index(
        "phi-4-mini-flash.reasoning_batch")


def test_the_family_reads_the_published_keys_up_to_the_cut():
    from benchmarks import families, run

    config = _config()
    assert len(config["layer_types"]) == 32
    assert config["share"]["published"] == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144}
    assert config["share"]["chips_per_layer"] == 8
    cfg = families.serve(config).model_cfg
    assert cfg.layer_types == ("sliding_attention",) * 3 \
        + ("full_attention",)
    assert (cfg.n_routed_experts, cfg.held, cfg.vocab_size, cfg.n_layers) \
        == (128, (0, 16), 32768, 4)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.window,
            cfg.mlp_dim, cfg.n_shared_experts, cfg.top_k) == \
        (128, 8, 128, 4096, 4096, 4, 8)
    assert cfg.router().score == "sigmoid" and cfg.router().renormalise
    assert cfg.rope_theta == 50000.0 and cfg.logit_scale == 1.0
    toy = families.serve(run.merge(config, config["rehearse"])).model_cfg
    assert (toy.window, toy.held, toy.n_layers, toy.n_shared_experts) == \
        (12, (0, 8), 4, 2)
    for key, other in (("expert_selection_fn", "softmax"),
                       ("use_parallel_block", False),
                       ("shared_expert_combination_strategy", "sum"),
                       ("first_k_dense_replace", 1)):
        with pytest.raises(ValueError, match="not implemented"):
            families.serve({**config, key: other})


def test_the_traffic_is_the_multiset_the_cell_was_sized_for():
    from benchmarks import traffic

    mix = traffic.load("grounded_docs_batch")
    prompts = traffic.stratified_lengths(mix["prompt"], mix["requests"])
    answers = traffic.stratified_lengths(mix["answer"], mix["requests"])
    layout = _config()["serve"]["layouts"][mix["layout"]]
    assert (layout["slots"], layout["capacity"],
            layout["prefill_chunk_tokens"]) == (24, 32768, 2048)
    assert max(prompts) + max(answers) <= layout["capacity"]
    assert min(prompts) >= 4096 and max(prompts) <= 32256
    assert min(answers) >= 64 and max(answers) <= 512
    # Every prompt is past the window and longer than a chunk: no whole
    # prefill, no admission wave.
    assert min(prompts) > layout["prefill_chunk_tokens"]
    assert mix["warm_waves"] == [] and "warm_resumed" not in mix
    total = sum(prompts)
    assert 16000 < total / 192 < 19000 and 240 < sum(answers) / 192 < 300
    # 24 slots of mean traffic fit the full kind's 589,824 tokens.
    live = 24 * (total + sum(answers)) / 192
    assert live < 0.8 * layout["kv_pool_pages"] * layout["kv_page_tokens"]
    assert (mix["clients"], mix["requests"], mix["loop"]) == \
        (36, 192, "closed")
    assert mix["lead_in_s"] == 45 and mix["drain_s"] == 0


def test_the_counts_follow_the_shapes():
    from benchmarks import cohere2_moe_counts as cc

    m = _config()
    assert cc.windows_of(m) == [True, True, True, False]
    # ISSUE 49's arithmetic: 142.6 M a layer's attention, 50.33 M an
    # expert, 4,096 B a token a layer, 4,096 + 12,288 over the cut.
    assert round(cc.attn_params(m) / 1e6, 1) == 142.6
    assert round(cc.expert_params(m) / 1e6, 2) == 50.33
    assert cc.kv_token_bytes(m, False) == 4096
    assert cc.kv_token_bytes(m, True) == 12288
    assert cc.held_pairs_per_token(m) == 1.0
    # A token's matmuls: attention, router, four shared and one held pair.
    per_layer = 142606336 + 4096 * 128 + 5 * 50331648
    assert cc.token_matmul_flops(m, 1.0) == 2.0 * 4 * per_layer
    # A prompt of 16,384: 134 M live pairs in the full layer, 59 M in each
    # window layer.
    full = 16384 * 16385 / 2
    window = sum(min(i + 1, 4096) for i in range(16384))
    assert round(full / 1e6) == 134 and round(window / 1e6) == 59
    assert cc.attention_flops(m, 0, 16384) == \
        2.0 * 128 * 256 * (full + 3 * window)
    # A decode token at 16k of context: the full layer's keys dominate.
    assert cc.attention_flops(m, 16383, 1) == \
        2.0 * 128 * 256 * (16384 + 3 * 4096)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # 24 pairs over 14 hit experts: the weights' bytes bound it.
    assert cc.experts_least_s(m, 24, 14, peak) == \
        14 * 50331648 * 2 / 819e9


def _ctx(rows):
    return {"rows": rows, "wall_window": (0.0, 100.0)}


def test_kv_bytes_per_ctx_token_reads_the_rows(monkeypatch):
    from benchmarks import cohere2_moe_counts as cc

    monkeypatch.setattr(cc, "model", _config)
    rows = [{"t0": 1.0, "t1": 2.0, "pages_full": 300, "pages_window": 65,
             "kv_tokens": 19000},
            {"t0": 2.0, "t1": 3.0, "pages_full": 0, "pages_window": 0,
             "kv_tokens": 0},            # an empty engine: left out
            {"t0": 200.0, "t1": 201.0, "pages_full": 1, "pages_window": 1,
             "kv_tokens": 1}]            # outside the window
    got = cc.kv_bytes_per_ctx_token(_ctx(rows))
    assert got == (300 * 64 * 4096 + 65 * 64 * 12288) / 19000
    assert got < 16384 / 2
    # A program of one kind of page has no such keys: nothing to read.
    assert cc.kv_bytes_per_ctx_token(_ctx(
        [{"t0": 1.0, "t1": 2.0, "pages_free": 3}])) is None


def test_the_trace_readers_return_nothing_without_a_trace(monkeypatch):
    from benchmarks import cohere2_moe_counts as cc

    monkeypatch.setattr(cc, "model", _config)
    ctx = {"trace": None, "trace_dir": None,
           "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert cc.serve_mfu_pct(ctx) is None
    assert cc.decode_attn_roofline_pct(ctx, window=True) is None
    assert cc.decode_attn_roofline_pct(ctx, window=False) is None
    assert cc.chunk_attn_roofline_pct(ctx) is None
    assert cc.moe_experts_roofline_pct(ctx) is None


def test_an_operation_is_put_down_to_its_scope_or_its_kernel():
    from benchmarks import cohere2_moe_counts as cc

    def op(hlo, path):
        return [hlo, 0, 1, path]

    assert cc.scope_of(op(
        "%paged_decode_attn.3 = custom-call(...)",
        "jit(engine_decode)/while/body/window_attn/paged_decode_attn")) \
        == "window_attn"
    assert cc.scope_of(op("%fusion.1 = ...",
                          "jit(engine_decode)/while/body/attn_proj/dot")) \
        == "attn_proj"
    assert cc.scope_of(op("%fusion.2 = ...",
                          "jit(engine_decode)/while/body/moe_shared/dot")) \
        == "moe_shared"
    assert cc.scope_of(op("%chunk_attn_window.3 = custom-call(...)",
                          "jit(engine_paged_suffix)/chunk_attn_window")) \
        == "chunk_attn_window"
    assert cc.scope_of(op("%ragged-dot-none.2 = ...", "ragged-dot-none")) \
        == "moe_experts"
    assert cc.scope_of(op("%fusion.9 = ...", "jit(x)/mul")) is None
