"""The cell ``mimo-v2.5.mixed_lengths_batch`` (PR 43): its rehearsal (the
cell's whole control flow on the CPU at the toy size of its files'
``rehearse`` blocks: the seven layers ``[0,1,1,1,1,0,1]``, 8 of 16 experts,
a window of 12 over pages of 8; marked slow as its twins are), its entries
in ``BENCHMARK.json``, its traffic's fixed multiset, and the arithmetic of
``benchmarks/mimo_counts.py`` on rows and shapes made by hand."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
CELL = "mimo-v2.5.mixed_lengths_batch"
# PR 43's per-layer metrics, in the order BENCHMARK.json has them.
NEW_IN_ORDER = ["serve_mfu_pct.mimo.batch", "full_attn_roofline_pct.batch",
                "window_attn_roofline_pct.batch",
                "chunk_attn_roofline_pct.batch",
                "kv_bytes_per_ctx_token.batch"]
# The accepted metrics whose readers read the cell unchanged.
JOINED = ["active_slots_mean.batch", "preempted.batch",
          "chunk_step_ms_p50.batch", "compiles_in_window.batch",
          "device_idle_pct.batch", "step_host_ms_p50.batch",
          "pages_ms_per_step.batch", "prefill_useful_ratio.batch",
          "chunk_device_ms_p50.batch",
          "device_idle_unattributed_pct.batch",
          "token_delivery_ms_p50.batch", "stream_items_per_pull_mean.batch"]
# PR 39's seven, the block that PR 43's five stand behind.
PR39_IN_ORDER = [
    "ingress_ms_p50.chat", "first_token_delivery_ms_p50.chat",
    "ttft_inside_ms_p90.chat", "token_delivery_ms_p50.chat",
    "stream_items_per_pull_mean.chat", "token_delivery_ms_p50.batch",
    "stream_items_per_pull_mean.batch"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mimo-v2.5.json")) as f:
        return json.load(f)


@pytest.mark.slow
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_mimo_cell(trace):
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
        # What the program counts is read on the CPU too: the mechanism's
        # own number (one kind of page would read 7 layers x ... more),
        # and deepseek's counter names.
        assert 0 < line["metrics"]["kv_bytes_per_ctx_token.batch"][
            "value"] < 2000
        # The engine runs without a prefix index: nothing to read there.
        assert "pages_pinned_prefix_mean.batch" not in names
        assert names & set(NEW_IN_ORDER) == {"kv_bytes_per_ctx_token.batch"}


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
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    config = next(c for c in bench["configs"] if c["name"] == "mimo-v2.5")
    assert os.path.isfile(os.path.join(ROOT, config["file"]))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("mimo-v2.5", "mixed_lengths_batch", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "traffic", cell["traffic"] + ".json"))


def test_earlier_metrics_keep_their_place():
    """Relative order only, without pinning the list's tail: PR 39's seven
    are still one block, and PR 43's five stand behind it in their order.
    Later PRs append behind them."""
    names = [m["name"] for m in _bench()["per_layer"]]
    assert len(names) == len(set(names))
    at = [names.index(n) for n in PR39_IN_ORDER]
    assert at == list(range(at[0], at[0] + len(at)))
    mine = [names.index(n) for n in NEW_IN_ORDER]
    assert mine == sorted(mine) and mine[0] > at[-1]


def test_the_family_reads_the_published_lists_up_to_the_cut():
    from benchmarks import families, run

    config = _config()
    assert len(config["hybrid_layer_pattern"]) == 48
    assert len(config["moe_layer_freq"]) == 48
    cfg = families.serve(config).model_cfg
    assert cfg.layer_pattern == (0, 1, 1, 1, 1, 0, 1)
    assert cfg.moe_pattern == (0, 1, 1, 1, 1, 1, 1)
    assert (cfg.n_routed_experts, cfg.held, cfg.vocab_size) == \
        (256, (0, 16), 19072)
    assert (cfg.rotary_dim, cfg.window, cfg.swa_sink, cfg.full_sink) == \
        (64, 128, True, False)
    assert cfg.router().score == "sigmoid" and cfg.router().renormalise
    toy = families.serve(run.merge(config, config["rehearse"])).model_cfg
    assert (toy.window, toy.held, toy.n_layers) == (12, (0, 8), 7)
    with pytest.raises(ValueError, match="not implemented"):
        families.serve({**config, "scoring_func": "softmax"})


def test_the_traffic_is_the_multiset_the_cell_was_sized_for():
    from benchmarks import traffic

    mix = traffic.load("mixed_lengths_batch")
    prompts = traffic.stratified_lengths(mix["prompt"], mix["requests"])
    answers = traffic.stratified_lengths(mix["answer"], mix["requests"])
    layout = _config()["serve"]["layouts"][mix["layout"]]
    assert max(prompts) + max(answers) <= layout["capacity"]
    assert (min(prompts), max(prompts)) == (256, 30720)
    assert min(answers) >= 32 and max(answers) == 1024
    total = sum(prompts)
    assert 7000 < total / 256 < 7400 and 300 < sum(answers) / 256 < 340
    assert 0.09 < sum(p < 900 for p in prompts) / 256 < 0.11
    assert sum(p > 16384 for p in prompts) / 256 == 0.125
    assert 0.64 < sum(p for p in prompts if p > 8192) / total < 0.72
    # 32 slots of mean traffic are ~240k tokens: 3,750 of the 12,288
    # full pages; one kind of page would need 7.4 GB for them.
    live = 32 * (total + sum(answers)) / 256
    assert 230_000 < live < 250_000
    assert mix["clients"] == 48 and mix["warm_waves"] == [2]
    assert "warm_resumed" not in mix


def test_the_counts_follow_the_shapes():
    from benchmarks import mimo_counts as mc

    m = _config()
    assert [l["window"] for l in mc.layers_of(m)] == \
        [False, True, True, True, True, False, True]
    # ISSUE 43's arithmetic: 89.1 M and 94.4 M a layer's attention,
    # 2,560 B and 5,120 B a token a layer, 5,120 + 25,600 over the cut.
    assert round(mc.attn_params(m, False) / 1e6, 1) == 89.1
    assert round(mc.attn_params(m, True) / 1e6, 1) == 94.4
    assert mc.kv_token_bytes(m, False) == 2 * 2560
    assert mc.kv_token_bytes(m, True) == 5 * 5120
    assert mc.held_pairs_per_token(m) == 0.5
    # Live pairs: causal, and a window's band with its short start.
    assert mc.live_pairs(0, 4, None) == 10
    assert mc.live_pairs(10, 2, None) == 23
    assert mc.live_pairs(0, 200, 128) == sum(
        min(i + 1, 128) for i in range(200))
    assert mc.live_pairs(100, 50, 128) == sum(
        min(i + 1, 128) for i in range(100, 150))
    assert mc.live_pairs(4096, 2048, 128) == 2048 * 128
    # A decode token at 8k of context: the full layers' keys dominate.
    one = mc.attention_flops(m, 8191, 1)
    assert one == 2.0 * 64 * 320 * (2 * 8192 + 5 * 128)


def _ctx(rows):
    return {"rows": rows, "wall_window": (0.0, 100.0)}


def test_kv_bytes_per_ctx_token_reads_the_rows(monkeypatch):
    from benchmarks import mimo_counts as mc

    monkeypatch.setattr(mc, "model", _config)
    rows = [{"t0": 1.0, "t1": 2.0, "pages_full": 100, "pages_window": 6,
             "kv_tokens": 6000},
            {"t0": 2.0, "t1": 3.0, "pages_full": 0, "pages_window": 0,
             "kv_tokens": 0},            # an empty engine: left out
            {"t0": 200.0, "t1": 201.0, "pages_full": 1, "pages_window": 1,
             "kv_tokens": 1}]            # outside the window
    got = mc.kv_bytes_per_ctx_token(_ctx(rows))
    assert got == (100 * 64 * 5120 + 6 * 64 * 25600) / 6000
    # A program of one kind of page has no such keys: nothing to read.
    assert mc.kv_bytes_per_ctx_token(_ctx(
        [{"t0": 1.0, "t1": 2.0, "pages_free": 3}])) is None


def test_the_trace_readers_return_nothing_without_a_trace(monkeypatch):
    from benchmarks import mimo_counts as mc

    monkeypatch.setattr(mc, "model", _config)
    ctx = {"trace": None, "trace_dir": None,
           "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert mc.serve_mfu_pct(ctx) is None
    assert mc.decode_attn_roofline_pct(ctx, window=True) is None
    assert mc.decode_attn_roofline_pct(ctx, window=False) is None
    assert mc.chunk_attn_roofline_pct(ctx) is None


def test_an_operation_is_put_down_to_its_scope_or_its_kernel():
    from benchmarks import mimo_counts as mc

    def op(hlo, path):
        return [hlo, 0, 1, path]

    assert mc.scope_of(op("%fusion.1 = ...",
                          "jit(engine_decode)/while/body/full_attn/dot")) \
        == "full_attn"
    assert mc.scope_of(op("%chunk_attn_window.3 = custom-call(...)",
                          "jit(engine_paged_suffix)/chunk_attn_window")) \
        == "chunk_attn_window"
    assert mc.scope_of(op("%chunk_attn_full.1 = custom-call(...)", "")) \
        == "chunk_attn_full"
    assert mc.scope_of(op("%ragged-dot-none.2 = ...", "ragged-dot-none")) \
        == "moe_experts"
    assert mc.scope_of(op("%fusion.9 = ...", "jit(x)/mul")) is None
