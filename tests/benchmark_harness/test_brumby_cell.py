"""The cell ``brumby-14b.long_context_batch`` (PR 52): its rehearsal (the
cell's whole control flow on the CPU at the toy size of its files'
``rehearse`` blocks: three power-retention layers, a head of 16 in blocks
of 4, an engine with no page kind; marked slow as its twins are), its
entries in ``BENCHMARK.json`` (membership and relative order only), its
traffic's fixed multiset, and the arithmetic of
``benchmarks/brumby_counts.py`` on rows and shapes made by hand."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
CELL = "brumby-14b.long_context_batch"
# PR 52's per-layer metrics, in the order BENCHMARK.json has them.
NEW_IN_ORDER = ["serve_mfu_pct.brumby.batch",
                "retention_step_roofline_pct.brumby.batch",
                "retention_chunk_roofline_pct.brumby.batch",
                "state_bytes_per_ctx_token.brumby.batch"]
# The accepted metrics whose readers read the cell unchanged.
JOINED = ["active_slots_mean.batch", "preempted.batch",
          "chunk_step_ms_p50.batch", "chunk_device_ms_p50.batch",
          "compiles_in_window.batch", "device_idle_pct.batch",
          "device_idle_unattributed_pct.batch", "step_host_ms_p50.batch",
          "prefill_useful_ratio.batch", "token_delivery_ms_p50.batch",
          "stream_items_per_pull_mean.batch",
          "decode_device_ms_p50.phi4flash.batch"]
# What the cell does not report: it has no pages and no prefix index.
NOT_JOINED = ["pages_ms_per_step.batch", "pages_pinned_prefix_mean.batch",
              "kv_bytes_per_ctx_token.phi4flash.batch",
              "ssm_step_roofline_pct.batch"]
# The last metric of PR 49's block, which PR 52's four stand behind.
BEFORE = "kv_bytes_per_ctx_token.cohere2.batch"


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "brumby-14b.json")) as f:
        return json.load(f)


@pytest.mark.slow
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_brumby_cell(trace):
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
        assert line["metrics"]["preempted.batch"]["value"] == 0
        # What the program counts is read on the CPU too: 3 layers x 2
        # heads x 17 x 160 x 4 B a slot over the tokens it holds.
        assert 0 < line["metrics"]["state_bytes_per_ctx_token.brumby.batch"][
            "value"] < 65280
        assert names & set(NEW_IN_ORDER) == {
            "state_bytes_per_ctx_token.brumby.batch"}
        assert not names & set(NOT_JOINED)


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
    # ONE of the three readers of the decode's device time, not a fourth.
    assert sum(CELL in m["workloads"] for n, m in by_name.items()
               if n.startswith("decode_device_ms_p50")) == 1
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    config = next(c for c in bench["configs"] if c["name"] == "brumby-14b")
    assert os.path.isfile(os.path.join(ROOT, config["file"]))
    assert config["reduced"] == ["num_hidden_layers"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("brumby-14b", "long_context_batch", 1)
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
        "command-a-plus.grounded_docs_batch")


def test_the_family_reads_the_published_keys_up_to_the_cut():
    from benchmarks import families, run

    config = _config()
    assert config["share"]["published"] == {"num_hidden_layers": 40}
    assert config["share"]["chips_per_layer"] == 1
    assert config["share"]["pipeline_stages"] == 5
    assert (config["assumed"]["state_rows_published"],
            config["assumed"]["state_rows_held"]) == (8256, 9216)
    fam = families.serve(config)
    cfg = fam.model_cfg
    assert (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.mlp_dim, cfg.vocab_size, cfg.max_seq_len) == \
        (8, 5120, 40, 8, 128, 17408, 151936, 32768)
    assert (cfg.degree, cfg.phi_block, cfg.state_rows, cfg.group) == \
        (2, 16, 9216, 5)
    assert cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-6
    assert fam.deployment_class().__name__ == "BrumbyDecodeDeployment"
    assert not hasattr(families.load("brumby"), "Train")
    toy = families.serve(run.merge(config, config["rehearse"])).model_cfg
    assert (toy.n_layers, toy.head_dim, toy.phi_block, toy.state_rows) == \
        (3, 16, 4, 160)
    for key, other in (("model_type", "qwen3"),
                       ("tie_word_embeddings", True),
                       ("attention_bias", True),
                       ("use_sliding_window", True)):
        with pytest.raises(ValueError, match="not implemented"):
            families.serve({**config, key: other})
    with pytest.raises(ValueError, match="rows of state"):
        families.serve({**config, "assumed": {
            **config["assumed"], "state_rows_held": 16384}})
    with pytest.raises(ValueError, match="degree"):
        families.serve({**config, "assumed": {**config["assumed"],
                                              "degree": 3}})


def test_the_traffic_is_the_multiset_the_cell_was_sized_for():
    from benchmarks import traffic

    mix = traffic.load("long_context_batch")
    prompts = traffic.stratified_lengths(mix["prompt"], mix["requests"])
    answers = traffic.stratified_lengths(mix["answer"], mix["requests"])
    layout = _config()["serve"]["layouts"][mix["layout"]]
    assert (layout["slots"], layout["capacity"],
            layout["prefill_chunk_tokens"]) == (16, 32768, 2048)
    assert "kv_pool_pages" not in layout      # there is no pool of pages
    assert max(prompts) + max(answers) <= layout["capacity"]
    assert min(prompts) >= 2048 and max(prompts) <= 16384
    assert min(answers) >= 128 and max(answers) <= 1024
    # The shortest prompt is a whole chunk and the only one that is not
    # longer: it prefills whole and alone (the set-up's single prompt of
    # that length warms its program), so there is no wave to warm.
    assert sum(p <= layout["prefill_chunk_tokens"] for p in prompts) == 1
    assert mix["warm_waves"] == [] and "warm_resumed" not in mix
    n = mix["requests"]
    assert 8500 < sum(prompts) / n < 9300
    assert 520 < sum(answers) / n < 600
    # Every mean context is past the 8,320 tokens at which a state as
    # published is smaller than the keys and values it replaces.
    assert (sum(prompts) + sum(answers) / 2) / n > 8320
    # ISSUE 52's multiset, letter for letter.
    assert (mix["clients"], n, mix["loop"]) == (24, 192, "closed")
    assert (mix["prompt"]["median"], mix["prompt"]["sigma"]) == (8192, 0.5)
    assert (mix["answer"]["median"], mix["answer"]["sigma"]) == (512, 0.5)
    assert mix["clients"] == 1.5 * layout["slots"]
    assert mix["lead_in_s"] == 45 and mix["drain_s"] == 0


def test_the_counts_follow_the_shapes():
    from benchmarks import brumby_counts as bc

    m = _config()
    # ISSUE 52's arithmetic: 330.35 M a layer, 101 MFLOP of retention a
    # token a layer beside 661 of matrices, 34.08 MB of published state a
    # slot a layer and 38.04 as held.
    assert bc.layer_params(m) == 330_342_400
    assert round(2 * bc.layer_params(m) / 1e6) == 661
    assert bc.retention_flops(m) == 2.0 * 8256 * 128 * 48
    assert round(bc.retention_flops(m) / 1e6) == 101
    assert bc.state_slot_bytes(m) == 8 * 129 * 9216 * 4 == 38_043_648
    assert 8 * 129 * 8256 * 4 == 34_080_768
    assert bc.token_flops(m) == 8 * (2.0 * 330_342_400 + 2.0 * 8256 * 128
                                     * 48)
    assert bc.head_flops(m) == 2.0 * 5120 * 151936
    # A prompt of 8,192 answered with its first token and two more.
    assert bc.request_flops(m, 8192, [0, 1, 2]) == \
        8194 * bc.token_flops(m) + 3 * bc.head_flops(m)
    assert bc.request_flops(m, 8192, [5]) == \
        bc.token_flops(m) + bc.head_flops(m)
    # The crossover: keys and values of these heads are 4,096 B a token.
    assert 34_080_768 / 4096 == 8320.5


def _ctx(rows):
    return {"rows": rows, "wall_window": (0.0, 100.0)}


def test_state_bytes_per_ctx_token_reads_the_rows():
    from benchmarks import brumby_counts as bc

    slot = 8 * 38_043_648
    rows = [{"t0": 1.0, "t1": 2.0, "state_bytes": 16 * slot,
             "kv_tokens": 16 * 9000, "pages_free": 0},
            {"t0": 2.0, "t1": 3.0, "state_bytes": 0, "kv_tokens": 0},
            {"t0": 200.0, "t1": 201.0, "state_bytes": slot,
             "kv_tokens": 1}]            # outside the window
    got = bc.state_bytes_per_ctx_token(_ctx(rows))
    assert got == slot / 9000
    assert 32768 < got < 34000       # past the crossover, as held
    # A paged program's rows carry no such key: nothing to read.
    assert bc.state_bytes_per_ctx_token(_ctx(
        [{"t0": 1.0, "t1": 2.0, "pages_free": 3, "kv_tokens": 5}])) is None


def test_the_trace_readers_return_nothing_without_a_trace(monkeypatch):
    from benchmarks import brumby_counts as bc

    monkeypatch.setattr(bc, "model", _config)
    ctx = {"trace": None, "trace_dir": None,
           "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert bc.serve_mfu_pct(ctx) is None
    assert bc.retention_step_roofline_pct(ctx) is None
    assert bc.retention_chunk_roofline_pct(ctx) is None


def test_an_operation_is_put_down_to_its_scope():
    from benchmarks import brumby_counts as bc

    def op(hlo, path):
        return [hlo, 0, 1, path]

    assert bc.scope_of(op(
        "%retention_step.3 = custom-call(...)",
        "jit(engine_decode)/while/body/retention_step/retention_step")) \
        == "retention_step"
    assert bc.scope_of(op(
        "%fusion.1 = ...",
        "jit(engine_paged_suffix)/while/body/retention_chunk/while/body/"
        "dot_general")) == "retention_chunk"
    assert bc.scope_of(op("%fusion.2 = ...",
                          "jit(engine_decode)/while/body/mlp/dot")) == "mlp"
    assert bc.scope_of(op("%fusion.4 = ...",
                          "jit(engine_decode)/head/dot")) == "head"
    assert bc.scope_of(op("%fusion.9 = ...", "jit(x)/mul")) is None
