"""The cell ``phi-4-mini-flash.reasoning_batch`` (PR 45): its rehearsal (the
cell's whole control flow on the CPU at the toy size of its files'
``rehearse`` blocks: eight layers with every kind of layer, a window of 12
over pages of 8, a state a slot; marked slow as its twins are), its entries
in ``BENCHMARK.json`` (membership, never equality), its traffic's fixed
multiset, and the arithmetic of ``benchmarks/phi4flash_counts.py`` against
brute force on rows and shapes made by hand."""

import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
CELL = "phi-4-mini-flash.reasoning_batch"
# PR 45's per-layer metrics, in the order BENCHMARK.json has them.
NEW_IN_ORDER = ["serve_mfu_pct.phi4flash.batch",
                "decode_device_ms_p50.phi4flash.batch",
                "shared_kv_roofline_pct.batch", "swa_roofline_pct.batch",
                "ssm_step_roofline_pct.batch", "ssm_scan_roofline_pct.batch",
                "kv_bytes_per_ctx_token.phi4flash.batch"]
# The accepted metrics whose readers read the cell unchanged
# (``chunk_device_ms_p50.batch`` raises on a trace without a chunk run, and
# five seconds of this traffic may hold none: left out).
JOINED = ["active_slots_mean.batch", "preempted.batch",
          "chunk_step_ms_p50.batch", "compiles_in_window.batch",
          "device_idle_pct.batch", "step_host_ms_p50.batch",
          "pages_ms_per_step.batch", "prefill_useful_ratio.batch",
          "device_idle_unattributed_pct.batch",
          "token_delivery_ms_p50.batch", "stream_items_per_pull_mean.batch"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "phi-4-mini-flash.json")) as f:
        return json.load(f)


@pytest.mark.slow
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_phi4flash_cell(trace):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "4500000019",
         "--seconds", "4", "--trace", trace, "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    names = set(line["metrics"])
    assert ("serve_tokens_per_s" in names) == (trace == "0")
    # No share of a peak or of a roofline may come out of a CPU run.
    assert not [n for n in names if "mfu" in n or "roofline" in n]
    if trace == "1":
        # What the program counts is read on the CPU too.
        assert 0 < line["metrics"]["kv_bytes_per_ctx_token.phi4flash.batch"][
            "value"]
        assert names & set(NEW_IN_ORDER) == {
            "kv_bytes_per_ctx_token.phi4flash.batch"}


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
    names = [m["name"] for m in bench["per_layer"]]
    mine = [names.index(n) for n in NEW_IN_ORDER]
    assert mine == sorted(mine) and len(names) == len(set(names))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    config = next(c for c in bench["configs"]
                  if c["name"] == "phi-4-mini-flash")
    assert config["reduced"] == [] == _config()["reduced"]
    assert os.path.isfile(os.path.join(ROOT, config["file"]))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("phi-4-mini-flash", "reasoning_batch", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "traffic", cell["traffic"] + ".json"))
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "reference", "phi4flash_ref.py"))


def test_the_family_reads_the_published_keys_and_the_assumed_sizes():
    from benchmarks import families, run

    config = _config()
    cfg = families.serve(config).model_cfg
    assert (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
            cfg.mlp_dim, cfg.window, cfg.vocab_size) == (
        32, 2560, 40, 20, 10240, 512, 200064)
    assert (cfg.d_state, cfg.d_conv, cfg.d_inner, cfg.dt_rank) == (
        16, 4, 5120, 160)
    toy = families.serve(run.merge(config, config["rehearse"])).model_cfg
    assert (toy.n_layers, toy.window, toy.d_state, toy.dim) == (8, 12, 4, 64)
    assert set(toy.kinds()) == {"mamba", "window", "full", "gmu", "cross"}
    with pytest.raises(ValueError, match="not implemented"):
        families.serve({**config, "tie_word_embeddings": False})
    with pytest.raises(ValueError, match="dt_rank"):
        families.serve({**config, "assumed": {**config["assumed"],
                                              "dt_rank": 128}})


def test_the_traffic_is_the_multiset_the_cell_was_sized_for():
    from benchmarks import traffic

    mix = traffic.load("reasoning_batch")
    prompts = traffic.stratified_lengths(mix["prompt"], mix["requests"])
    answers = traffic.stratified_lengths(mix["answer"], mix["requests"])
    layout = _config()["serve"]["layouts"][mix["layout"]]
    assert max(prompts) + max(answers) <= layout["capacity"]
    assert (min(prompts), max(prompts)) == (64, 2048)
    assert (min(answers), max(answers)) == (256, 6144)
    assert 500 < sum(prompts) / 256 < 560
    assert 1900 < sum(answers) / 256 < 2020
    # A third of the prompts cross a chunk's edge and carry their state.
    chunk = layout["prefill_chunk_tokens"]
    assert 0.30 < sum(p > chunk for p in prompts) / 256 < 0.40
    assert (mix["clients"], layout["slots"], mix["lead_in_s"],
            mix["loop"], mix["requests"]) == (96, 64, 60, "closed", 256)
    assert "warm_resumed" not in mix and mix["drain_s"] == 0
    # The full kind holds every slot at its capacity: the slots bind.
    assert layout["kv_pool_pages"] * layout["kv_page_tokens"] == \
        layout["slots"] * layout["capacity"]


def test_the_counts_follow_the_shapes():
    from benchmarks import phi4flash_counts as pc

    m = _config()
    z = pc.sizes(m)
    assert (z["mamba"], z["window"], z["back"], z["di"], z["kv"],
            z["d"]) == (9, 8, 7, 5120, 1280, 64)
    # ISSUE 45's arithmetic, by brute force: a Mamba layer 119.9 M, an
    # attention layer 98.3 M, a GMU layer 104.9 M, a cross layer 91.8 M,
    # the embedding 512.2 M.
    e, f, di = 2560, 10240, 5120
    mlp = e * 2 * f + f * e
    mamba = e * 2 * di + di * (160 + 32) + 160 * di + di * e + mlp
    attn = e * (e + 2 * 1280) + e * e + mlp
    gmu = 2 * e * di + mlp
    cross = 2 * e * e + mlp
    vectors = 4 * di + di + di + 16 * di + di + 4 * e   # conv, dt, A, D, LN
    assert [round(x / 1e6, 1) for x in (mamba + vectors, attn, gmu,
                                        cross)] == [119.9, 98.3, 104.9, 91.8]
    assert pc.self_decoder_flops(m) == 2.0 * (
        9 * mamba + 8 * attn + e * 2 * 1280)
    assert pc.cross_decoder_flops(m) == 2.0 * (
        (attn - e * 2 * 1280) + 7 * gmu + 7 * cross + e * 200064)
    # Both halves together are 2 x the matrices a decode token meets: the
    # model's 3.853 B less the vectors (norms, biases, conv, A, D, lam).
    whole = (pc.self_decoder_flops(m) + pc.cross_decoder_flops(m)) / 2
    assert 3.848e9 < whole < 3.853e9
    assert pc.kv_token_bytes(m) == 5120
    assert pc.state_slot_bytes(m) == 327680 + 30720 == 358400
    # Live pairs against brute force.
    for first, n, window in [(0, 4, None), (10, 2, None), (0, 700, 512),
                             (300, 400, 512), (4096, 512, 512)]:
        brute = sum(min(i + 1, window or i + 1)
                    for i in range(first, first + n))
        assert pc.live_pairs(first, n, window) == brute
    # A decode token at 2,000 of context: 8 layers read the whole cache, 8
    # their window; every head scores 64 numbers and weighs 128.
    one = pc.request_flops(m, 1000, [1001])
    assert one == pc.self_decoder_flops(m) + pc.cross_decoder_flops(m) \
        + 2.0 * 40 * 192 * (8 * 512 + 8 * 2001)
    # A prompt: the cross-decoder once, at its last position.
    first = pc.request_flops(m, 1000, [0])
    assert first == 1000 * pc.self_decoder_flops(m) \
        + pc.cross_decoder_flops(m) + 2.0 * 40 * 192 * (
            8 * sum(min(i + 1, 512) for i in range(1000)) + 8 * 1000)
    # The scans of a 512-token chunk: 9 layers x (x, dt, y, B, C and a
    # state in and out).
    assert pc.scan_bytes(m, 512, 1) == 9 * 4.0 * (
        512 * (3 * 5120 + 32) + 2 * 16 * 5120)


def _ctx(rows):
    return {"rows": rows, "wall_window": (0.0, 100.0)}


def test_kv_bytes_per_ctx_token_reads_the_rows(monkeypatch):
    from benchmarks import phi4flash_counts as pc

    monkeypatch.setattr(pc, "model", _config)
    rows = [{"t0": 1.0, "t1": 2.0, "pages_full": 100, "pages_window": 18,
             "state_bytes": 2 * 9 * 358400, "kv_tokens": 6000},
            {"t0": 2.0, "t1": 3.0, "pages_full": 0, "pages_window": 0,
             "state_bytes": 0, "kv_tokens": 0},     # empty: left out
            {"t0": 200.0, "t1": 201.0, "pages_full": 1, "pages_window": 1,
             "state_bytes": 1, "kv_tokens": 1}]     # outside the window
    got = pc.kv_bytes_per_ctx_token(_ctx(rows))
    assert got == (100 * 64 * 5120 + 18 * 64 * 40960
                   + 2 * 9 * 358400) / 6000
    # A program without slot state has no such key: nothing to read.
    assert pc.kv_bytes_per_ctx_token(_ctx(
        [{"t0": 1.0, "t1": 2.0, "pages_full": 3, "pages_window": 1,
          "kv_tokens": 9}])) is None


def test_the_trace_readers_return_nothing_without_a_trace(monkeypatch):
    from benchmarks import phi4flash_counts as pc

    monkeypatch.setattr(pc, "model", _config)
    ctx = {"trace": None, "trace_dir": None,
           "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert pc.serve_mfu_pct(ctx) is None
    for read in (pc.shared_kv_roofline_pct, pc.swa_roofline_pct,
                 pc.ssm_step_roofline_pct, pc.ssm_scan_roofline_pct):
        assert read(ctx) is None


def test_an_operation_is_put_down_to_its_scope_or_its_kernel():
    from benchmarks import phi4flash_counts as pc

    def op(hlo, path):
        return [hlo, 0, 1, path]

    assert pc.scope_of(op("%fusion.1 = ...",
                          "jit(engine_decode)/while/body/cross_attn/dot")) \
        == "cross_attn"
    # A layer that gathers for itself: the gather inside its attention.
    assert pc.scope_of(op("%gather.2 = ...",
                          "jit(engine_decode)/full_attn/full_gather/g")) \
        == "full_gather"
    assert pc.scope_of(op(
        "%chunk_attn_window.3 = custom-call(...)",
        "jit(engine_paged_suffix)/window_attn/chunk_attn_window")) \
        == "window_attn"
    assert pc.scope_of(op("%chunk_attn_window.1 = custom-call(...)", "")) \
        == "chunk_attn_window"
    assert pc.scope_of(op("%fusion.9 = ...", "jit(x)/ssm_step/mul")) \
        == "ssm_step"
    assert pc.scope_of(op("%fusion.9 = ...", "jit(x)/mul")) is None


def test_the_limits_of_correct_stand_in_the_family():
    from benchmarks.families import phi4flash as fam

    assert fam.readings([0.0, 0.3, 0.01, 0.02, 0.0, 0.2, 0.1]) == (0.3, 0.01)
    assert fam.shares_of_limits([0.0] * 10) == [0.0, 0.0]
    assert fam.Serve.tolerance == 1.0 and fam.RANKED_LIMIT < fam.LARGEST_LIMIT
    assert math.isfinite(fam.LARGEST_LIMIT)
    # Between the readings in the file's comment: sound 0.1464 and 0.2755 at
    # most, the int8 control 0.6700 and 0.8637 at least.
    assert 0.1464 < fam.RANKED_LIMIT < 0.6700
    assert 0.2755 < fam.LARGEST_LIMIT < 0.8637
