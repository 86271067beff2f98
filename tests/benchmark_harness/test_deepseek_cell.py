"""The rehearsal of ``deepseek-v2.longdocs_batch``: the cell's whole control
flow on the CPU at the toy size of its files' ``rehearse`` blocks (1 dense +
2 expert layers, 8 of 16 experts in 4 groups, so tokens route to absent
experts too), untraced and traced. Marked slow, as its llama twin is
(``test_benchmark_harness.py::test_rehearsal_runs_every_cell``)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
CELL = "deepseek-v2.longdocs_batch"
# PR 36's per-layer metrics, in the order BENCHMARK.json has them.
NEW_IN_ORDER = ["serve_mfu_pct.batch", "decode_device_ms_p50.batch",
                "latent_attn_roofline_pct.batch",
                "moe_experts_roofline_pct.batch",
                "moe_tokens_per_expert_mean.batch"]
NEW = set(NEW_IN_ORDER)


@pytest.mark.slow
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_deepseek_cell(trace):
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
        assert "compiles_in_window.batch" in names
        # What the program counts is read on the CPU too.
        assert line["metrics"]["moe_tokens_per_expert_mean.batch"][
            "value"] >= 1.0
        assert names & NEW == {"moe_tokens_per_expert_mean.batch"}


def test_the_cells_entries_name_their_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert {m["name"] for m in mine} == NEW
    for m in mine:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "metrics", m["name"] + ".py"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    config = next(c for c in bench["configs"] if c["name"] == "deepseek-v2")
    assert os.path.isfile(os.path.join(ROOT, config["file"]))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["config"] == "deepseek-v2" and cell["chips"] == 1
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "traffic", cell["traffic"] + ".json"))


def test_earlier_metrics_keep_their_place():
    """What ``test_progtrace.py``'s last assertion meant to hold, without
    pinning the list's tail: PR 24's sixteen per-layer metrics are still
    one block, and PR 36's five stand after it in their order. Later PRs
    append behind them."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_progtrace_tests", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "test_progtrace.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    pr24 = set().union(*mod.ROW_BASED.values()) | mod.DEVICE_BASED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert len(names) == len(set(names))
    at = sorted(names.index(n) for n in pr24)
    assert at == list(range(at[0], at[0] + 16))
    mine = [names.index(n) for n in NEW_IN_ORDER]
    assert mine == sorted(mine) and mine[0] > at[-1]
