"""The seven readers of the stream records (PR 39), on rows made by hand and
in a CPU rehearsal. A record is the ``stream-end`` event the program enters
in its step log once a streamed request (``ray_tpu/serve/replica.py``); the
readers join it to the client's outcomes by ``request_id``. They read rows,
so a rehearsal has them, and a program without the event gives them
nothing."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402

METRICS = os.path.join(ROOT, "benchmarks", "metrics")
CHAT = ["ingress_ms_p50.chat", "first_token_delivery_ms_p50.chat",
        "ttft_inside_ms_p90.chat", "token_delivery_ms_p50.chat",
        "stream_items_per_pull_mean.chat"]
BATCH = ["token_delivery_ms_p50.batch", "stream_items_per_pull_mean.batch"]
T0 = 1_790_000_000.0   # a wall clock


def _ms(value):
    """Stamps near 1.8e9 s hold a quarter of a microsecond."""
    return pytest.approx(value, abs=1e-3)


def _read(name, ctx):
    return bench_run.read_metric(METRICS, name, ctx)


def _outcome(rid, phase="window", ok=True, ended=5.0):
    return SimpleNamespace(request=SimpleNamespace(phase=phase),
                           request_id=rid, ok=ok, ended=ended)


def _record(rid, received=0.0, submitted=0.003, first_put=0.040,
            deliveries=(0.002, 0.001, 0.001), items=None, spans=True):
    """A record whose acknowledged deliveries took ``deliveries`` seconds
    each (the first is the first token's), plus the one never
    acknowledged: the last."""
    acked = len(deliveries)
    return {
        "kind": "stream-end", "ts": T0 + 9.0, "request": rid,
        "outcome": "completed",
        "received": T0 + received if spans else None,
        "started": T0 + received + 0.002, "submitted": T0 + submitted,
        "admitted": T0 + submitted + 0.001, "first_put": T0 + first_put,
        "last_put": T0 + first_put + 0.1,
        "first_ack": T0 + first_put + deliveries[0] if acked else None,
        "last_ack": T0 + first_put + 0.09 if acked else None,
        "items": acked + 1 if items is None else items, "pulls": acked + 1,
        "acked": acked, "dwell_s_sum": 0.0004 * (acked + 1),
        "dwell_s_max": 0.0005, "deliver_s_sum": sum(deliveries),
        "deliver_s_max": max(deliveries, default=0.0),
        "first_deliver_s": deliveries[0] if acked else None,
        "blocked_s_sum": 0.08}


def _ctx(outcomes, records, window=(0.0, 10.0)):
    """Records ride on step-log rows, some beside other events."""
    rows = [{"t0": T0 + i, "t1": T0 + i + 0.01, "phases": [], "active": 1,
             "events": [{"kind": "page-free", "ts": T0 + i, "n": 1}, rec]}
            for i, rec in enumerate(records)]
    rows.append({"t0": T0 + 50, "t1": T0 + 50.01, "phases": [], "active": 0})
    return {"outcomes": outcomes, "rows": rows, "window": window}


def test_the_chat_readers_on_three_requests():
    ctx = _ctx(
        [_outcome("a"), _outcome("b"), _outcome("c"),
         _outcome("lead", phase="lead_in")],
        [_record("a", submitted=0.002, deliveries=(0.002, 0.001, 0.001)),
         _record("b", submitted=0.003, deliveries=(0.004, 0.003, 0.001)),
         _record("c", submitted=0.007, first_put=0.100,
                 deliveries=(0.003, 0.002, 0.002), items=7),
         _record("lead", submitted=1.0, deliveries=(0.5, 0.5))])
    assert _read("ingress_ms_p50.chat", ctx) == _ms(3.0)
    assert _read("first_token_delivery_ms_p50.chat", ctx) == _ms(3.0)
    # first_ack - received: 42, 44 and 103 ms; nearest rank of three.
    assert _read("ttft_inside_ms_p90.chat", ctx) == _ms(103.0)
    # (sum - first) / (acked - 1): 1, 2 and 2 ms.
    assert _read("token_delivery_ms_p50.chat", ctx) == _ms(2.0)
    # 4 + 4 + 7 items in 4 + 4 + 4 pulls.
    assert _read("stream_items_per_pull_mean.chat", ctx) == \
        pytest.approx(15 / 12)


@pytest.mark.parametrize("name", CHAT + BATCH)
def test_a_program_without_the_record_gives_nothing(name):
    """The parent of PR 39: rows and outcomes, no ``stream-end`` event."""
    ctx = _ctx([_outcome("a"), _outcome("b", phase="closed")], [])
    assert _read(name, ctx) is None
    assert _read(name, {"outcomes": [], "rows": [], "window": (0, 1)}) \
        is None


def test_a_request_without_a_record_and_a_failed_one_are_left_out():
    ctx = _ctx(
        [_outcome("a"), _outcome("no-record"),
         _outcome("failed", ok=False)],
        [_record("a", deliveries=(0.002, 0.001)),
         _record("failed", submitted=0.9, deliveries=(0.9, 0.9))])
    assert _read("ingress_ms_p50.chat", ctx) == _ms(3.0)
    assert _read("ttft_inside_ms_p90.chat", ctx) == _ms(42.0)
    assert _read("token_delivery_ms_p50.chat", ctx) == _ms(1.0)


def test_one_acknowledged_delivery_has_a_first_token_and_no_rest():
    ctx = _ctx([_outcome("a")], [_record("a", deliveries=(0.002,))])
    assert _read("first_token_delivery_ms_p50.chat", ctx) == _ms(2.0)
    assert _read("ttft_inside_ms_p90.chat", ctx) == _ms(42.0)
    assert _read("token_delivery_ms_p50.chat", ctx) is None
    assert _read("stream_items_per_pull_mean.chat", ctx) == 1.0
    # ... and a stream that was one delivery has no acknowledgement at all.
    ctx = _ctx([_outcome("a")], [_record("a", deliveries=())])
    assert _read("first_token_delivery_ms_p50.chat", ctx) is None
    assert _read("ttft_inside_ms_p90.chat", ctx) is None
    assert _read("ingress_ms_p50.chat", ctx) == _ms(3.0)


def test_spans_off_leaves_out_what_counts_from_the_receive():
    ctx = _ctx([_outcome("a")], [_record("a", spans=False)])
    assert _read("ingress_ms_p50.chat", ctx) is None
    assert _read("ttft_inside_ms_p90.chat", ctx) is None
    assert _read("first_token_delivery_ms_p50.chat", ctx) == _ms(2.0)
    assert _read("token_delivery_ms_p50.chat", ctx) == _ms(1.0)


def test_a_closed_loop_counts_the_requests_that_ended_in_its_window():
    ctx = _ctx(
        [_outcome("in", phase="closed", ended=5.0),
         _outcome("before", phase="closed", ended=0.5),
         _outcome("after", phase="closed", ended=12.0),
         _outcome("cut", phase="closed", ok=False, ended=None)],
        [_record("in", deliveries=(0.004, 0.002, 0.002), items=6),
         _record("before", deliveries=(0.5, 0.5, 0.5)),
         _record("after", deliveries=(0.5, 0.5, 0.5))],
        window=(1.0, 11.0))
    assert _read("token_delivery_ms_p50.batch", ctx) == _ms(2.0)
    assert _read("stream_items_per_pull_mean.batch", ctx) == \
        pytest.approx(6 / 4)


def test_the_seven_entries_stand_behind_the_earlier_ones():
    """One block in their order, behind everything PR 38 had (later PRs
    append behind them: the list's tail is not pinned here)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(CHAT[0])
    assert names[at:at + 7] == CHAT + BATCH
    assert at > names.index("moe_tokens_per_expert_mean.batch")
    tail = bench["per_layer"][at:at + 7]
    e2e = {m["name"]: set(m.get("workloads", [])) for m in bench["end_to_end"]}
    for m in tail:
        assert os.path.isfile(os.path.join(METRICS, m["name"] + ".py"))
        assert m["layer"] == "proxy, router, replica stream"
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert set(m["workloads"]) <= e2e[m["moves"]]
        assert all(w.endswith("chat_steady") for w in m["workloads"]) == \
            m["name"].endswith(".chat")


@pytest.mark.parametrize("workload,names", [
    ("internlm2-1.8b.chat_steady", CHAT),
    ("internlm2-1.8b.docs_batch", BATCH)])
def test_a_rehearsal_prints_them(workload, names):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", "3900000019", "--seconds", "4",
         "--trace", "1", "--rehearse"], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["device"]["platform"] == "cpu"
    got = line["metrics"]
    assert set(names) <= set(got), sorted(got)
    for name in names:
        if name.startswith("stream_items_per_pull_mean"):
            # The last pull of a stream may bring only its end.
            assert 0.5 < got[name]["value"] <= 16.0
        else:
            assert 0.0 < got[name]["value"] < 1000.0
    if workload.endswith("chat_steady"):
        # The segments lie inside what the client saw of the same run.
        assert (got["ingress_ms_p50.chat"]["value"]
                < got["ttft_inside_ms_p90.chat"]["value"])
