"""The readers PR 24 added to the benchmark, on the CPU and without JAX: the
xplane decoder on a file encoded here, and the arithmetic of
``benchmarks/progtrace.py`` and ``benchmarks/kernel_counts.py`` on a trace
recorded on a TPU v5e (``data/progtrace_small.json``: three engine steps and
one ``train_step``). The rehearsals at the end run every new reader through
``run.py``: the row-based ones give a number, the device ones nothing."""

import copy
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import kernel_counts, progtrace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
V5E = {"device": {"kind": "TPU v5 lite"}}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "progtrace_small.json")) as f:
        rec = json.load(f)
    meta = rec["op_meta"]
    return {"host": rec["host"], "programs": rec["programs"],
            "ops": [[meta[i][0], t, d, meta[i][1]]
                    for i, t, d in rec["ops"]]}


def _ctx(trace):
    """A traced run's ctx with the trace already read."""
    runs = progtrace.program_runs(trace)
    pairs = progtrace.pair(runs, progtrace.launches(trace))
    a = {"trace": trace, "runs": runs, "pairs": pairs,
         "instrumented": bool(trace["host"])}
    if a["instrumented"]:
        a["offset"] = progtrace.clock_offset_ns(runs, pairs)
        a["idle"] = progtrace.idle_by_slice(trace, a["offset"][2])
    return dict(V5E, trace={}, trace_dir="unused", _progtrace=a)


# ------------------------------------------------------ the wire format


def _varint(n):
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _f(field, value):
    """One field: ints as varints, bytes/str/nested as length-delimited."""
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _stat(mid, **kw):
    (kind, v), = kw.items()
    num = {"u64": 3, "i64": 4, "s": 5, "ref": 7}[kind]
    return _f(1, mid) + _f(num, v)


def _plane(name, lines, event_meta, stat_meta):
    out = _f(2, name)
    for lname, t0_ns, events in lines:
        body = _f(2, lname) + _f(3, t0_ns)
        for mid, off_ps, dur_ps, stats in events:
            ev = _f(1, mid) + _f(2, off_ps) + _f(3, dur_ps)
            for st in stats:
                ev += _f(4, st)
            body += _f(4, ev)
        out += _f(3, body)
    for mid, (mname, stats) in event_meta.items():
        em = _f(1, mid) + _f(2, mname)
        for st in stats:
            em += _f(5, st)
        out += _f(4, _f(1, mid) + _f(2, em))
    for sid, sname in stat_meta.items():
        out += _f(5, _f(1, sid) + _f(2, _f(1, sid) + _f(2, sname)))
    return out


def test_xplane_decoder_reads_names_stats_and_the_scope(tmp_path):
    scope = "jit(engine_decode)/while/body/closed_call/paged_attn/dot:"
    dev = _plane(
        "/device:TPU:0",
        [("XLA Modules", 1000, [(1, 5_000_000, 900_000_000, [])]),
         ("XLA Ops", 1000, [(2, 6_000_000, 100_000_000, []),
                            (3, 200_000_000, 50_000_000, [])]),
         ("Steps", 1000, [(1, 0, 1, [])])],
        {1: ("jit_engine_decode(123456)", []),
         2: ("%fusion.7 = bf16[32,8,2,4096]{3,2,1,0} fusion(...)",
             [_stat(10, s=scope), _stat(11, u64=77)]),
         # A stat's value may be a reference to another stat's name.
         3: ("%convert.1 = bf16[8]{0} convert(...)", [_stat(10, ref=12)])},
        {10: "tf_op", 11: "flops", 12: "jit(engine_decode)/weight_cast/c:"})
    other = _plane("/device:TPU:1", [("XLA Ops", 0, [(2, 0, 5, [])])],
                   {2: ("%never.1 = f32[] add()", [])}, {})
    host = _plane(
        "/host:CPU",
        [("python3", 2000, [
            (1, 1_000_000, 2_000_000, [_stat(20, s="decode"),
                                       _stat(21, i64=13),
                                       _stat(22, i64=-1)]),
            (2, 0, 5, [])])],
        {1: ("engine:launch", []), 2: ("PjitFunction(engine_decode)", [])},
        {20: "program", 21: "batch", 22: "neg"})
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(
        _f(1, dev) + _f(1, other) + _f(1, host) + _f(4, "hostname"))
    t = progtrace.load(str(tmp_path))
    assert t["programs"] == [["jit_engine_decode", 6000.0, 900000.0]]
    assert [(o[0][:10], o[1], o[2], o[3]) for o in t["ops"]] == [
        ("%fusion.7 ", 7000.0, 100000.0, scope),
        ("%convert.1", 201000.0, 50000.0,
         "jit(engine_decode)/weight_cast/c:")]
    assert t["host"] == [["engine:launch", 3000.0, 2000.0,
                          {"program": "decode", "batch": 13, "neg": -1}]]
    assert progtrace.scopes_of(t["ops"][0][3]) == ["paged_attn"]
    with pytest.raises(FileNotFoundError):
        progtrace.load(str(tmp_path / "nothing"))


# -------------------------------------------- the recorded TPU v5e trace


def test_operations_are_grouped_by_the_programs_names(recorded):
    runs = progtrace.program_runs(recorded)
    by = {}
    for r in runs:
        by.setdefault(r["program"], []).append(r)
    assert len(by["jit_engine_decode"]) == 3
    assert len(by["jit_engine_paged_suffix"]) == 3
    assert len(by["jit_train_step"]) == 1
    assert sum(len(r["ops"]) for r in runs) <= len(recorded["ops"])
    for r in by["jit_engine_decode"]:
        assert all(r["t0"] <= o[1] and o[1] + o[2] <= r["t1"] + 1
                   for o in r["ops"])
        scopes = {s for o in r["ops"] for s in progtrace.scopes_of(o[3])}
        assert scopes == {"paged_gather", "paged_attn", "weight_cast"}
        # Four layers, K and V: eight page gathers of [pages, 64, 4, 128].
        gathers = [progtrace.result_shape(o[0]) for o in r["ops"]
                   if "paged_gather" in progtrace.scopes_of(o[3])
                   and len(progtrace.result_shape(o[0])[1]) == 4]
        assert len(gathers) == 8
        assert {g for g in gathers} == {("bf16", (256, 64, 4, 128))}
    kernels = [progtrace.kernel_of(o[0]) for o in by["jit_train_step"][0][
        "ops"] if progtrace.kernel_of(o[0])]
    assert sorted(kernels) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


def test_runs_pair_with_their_launch_by_program_and_role(recorded):
    runs = progtrace.program_runs(recorded)
    pairs = progtrace.pair(runs, progtrace.launches(recorded))
    roles = [(r["program"], ln and ln["role"]) for r, ln in zip(runs, pairs)
             if r["program"].startswith("jit_engine_")]
    assert roles == [("jit_engine_paged_suffix", "prefill_chunk"),
                     ("jit_engine_decode", "decode")] * 3
    # A chunk that is not the prompt's last has no fetch: its bracket ends
    # with the decode's fetch of the same step.
    chunk, decode = pairs[[r["program"] for r in runs].index(
        "jit_engine_paged_suffix")], next(
        ln for r, ln in zip(runs, pairs)
        if r["program"] == "jit_engine_decode")
    assert chunk["t1"] == decode["t1"] and chunk["t0"] < decode["t0"]
    assert decode["stats"]["ctx_tokens"] == 101
    assert [ln for r, ln in zip(runs, pairs)
            if r["program"] == "jit_train_step"] == [None]


def test_clock_offset_from_the_launch_fetch_brackets(recorded):
    runs = progtrace.program_runs(recorded)
    pairs = progtrace.pair(runs, progtrace.launches(recorded))
    lo, hi, est = progtrace.clock_offset_ns(runs, pairs)
    # My chip run, PR 24: the device's clock is 0.5-2.2 ms behind.
    assert 0.2e6 < lo < est < hi < 2.5e6 and est == (lo + hi) / 2
    # Move the device's clock: the estimate follows to the nanosecond.
    moved = copy.deepcopy(recorded)
    for key in ("programs", "ops"):
        for e in moved[key]:
            e[1] -= 3e6
    runs2 = progtrace.program_runs(moved)
    pairs2 = progtrace.pair(runs2, progtrace.launches(moved))
    assert [p and p["role"] for p in pairs2] == [p and p["role"]
                                                for p in pairs]
    lo2, hi2, est2 = progtrace.clock_offset_ns(runs2, pairs2)
    assert est2 == pytest.approx(est + 3e6, abs=1)
    assert progtrace.clock_offset_ns(runs, [None] * len(runs)) is None


def test_idle_is_attributed_by_overlap_not_by_midpoint():
    ms = 1e6
    trace = {"programs": [], "host": [
        ["engine:sample_emit", 9 * ms, 7 * ms, {}],
        ["engine:park", 16 * ms, 3 * ms, {"active": 4}],
        ["engine:launch", 19 * ms, 6 * ms, {"program": "decode"}],
        ["engine:fetch", 25 * ms, 30 * ms, {"program": "decode"}]],
        "ops": [["%a.1 = f32[] add()", 0, 10 * ms, ""],
                ["%b.1 = f32[] add()", 20 * ms, 10 * ms, ""],
                ["%c.1 = f32[] add()", 30.2 * ms, 1 * ms, ""],
                ["%d.1 = f32[] add()", 60 * ms, 1 * ms, ""]]}
    r = progtrace.idle_by_slice(trace, 0.0)
    # The gap [10, 20): sample_emit covers 6 ms of it, park 3, launch 1;
    # the midpoint (15) would have given all ten to sample_emit.
    # The gap [31.2, 60): fetch covers it up to 55, nothing after.
    assert r["by_slice_ns"] == pytest.approx({
        "sample_emit": 6 * ms, "park": 3 * ms, "launch": 1 * ms,
        "fetch": 23.8 * ms})
    assert r["unattributed_ns"] == pytest.approx(5 * ms)
    assert r["short_ns"] == pytest.approx(0.2 * ms)     # [30, 30.2)
    assert r["idle_ns"] == pytest.approx(38.8 * ms)
    assert r["window_ns"] == pytest.approx(61 * ms)
    assert r["longest_gap_ns"] == pytest.approx(28.8 * ms)
    assert r["longest_gap_by_ns"] == pytest.approx({"fetch": 23.8 * ms})
    # With the device's clock 2 ms behind the host's, the same gaps lie
    # 2 ms later under the slices.
    r2 = progtrace.idle_by_slice(trace, 2 * ms)
    assert r2["by_slice_ns"]["sample_emit"] == pytest.approx(4 * ms)
    assert r2["by_slice_ns"]["launch"] == pytest.approx(3 * ms)


def test_recorded_trace_reads_every_device_metric(recorded):
    ctx = _ctx(recorded)
    # The engine's part of the trace (the train step came 300 ms later):
    # every idle interval of the device lies under the step's slices.
    t_train = next(p[1] for p in recorded["programs"]
                   if p[0] == "jit_train_step")
    idle = _ctx(dict(
        recorded, ops=[o for o in recorded["ops"] if o[1] < t_train],
        programs=[p for p in recorded["programs"] if p[1] < t_train])
    )["_progtrace"]["idle"]
    assert idle["unattributed_ns"] < 0.005 * idle["window_ns"]
    assert idle["idle_ns"] > 0.5 * idle["window_ns"]      # a toy engine
    assert set(idle["by_slice_ns"]) == {
        "park", "reap", "admit", "pages", "launch", "fetch", "sample_emit"}
    assert idle["longest_gap_ns"] == pytest.approx(7.04e6, rel=0.01)
    assert max(idle["longest_gap_by_ns"],
               key=idle["longest_gap_by_ns"].get) == "park"
    assert progtrace.device_ms_p50(ctx, "jit_engine_decode") == \
        pytest.approx(1.096, abs=0.01)
    assert progtrace.device_ms_p50(
        ctx, "jit_engine_paged_suffix", role="prefill_chunk") == \
        pytest.approx(1.004, abs=0.03)
    # One slot of ~100 tokens against a view of 8 x 2048: almost all of
    # what the gather moves is waste.
    assert 0.0 < kernel_counts.paged_attn_roofline_pct(ctx) < 2.0
    fwd = kernel_counts.flash_roofline_pct(ctx, ("flash_fwd",), False)
    bwd = kernel_counts.flash_roofline_pct(
        ctx, ("flash_bwd_dkv", "flash_bwd_dq"), True)
    assert 5.0 < fwd < 40.0 and 5.0 < bwd < 60.0
    # By hand: [1, 8, 2048, 128] causal is 2 * 2048^2 * 128 * 8 FLOPs.
    t_fwd = sum(o[2] for o in recorded["ops"]
                if progtrace.kernel_of(o[0]) == "flash_fwd") / 1e9
    assert fwd == pytest.approx(
        100 * 2 * 2048 ** 2 * 128 * 8 / (197e12 * t_fwd))


def test_a_missing_name_is_an_error_and_an_old_program_is_none(recorded):
    # The program before PR 24: no engine: slice, the step is ``jit_step``.
    old = {"host": [], "ops": recorded["ops"], "programs": [
        [n.replace("jit_engine_", "jit__").replace("train_step", "step"),
         s, d] for n, s, d in recorded["programs"]]}
    ctx = _ctx(old)
    assert progtrace.device_ms_p50(ctx, "jit_engine_decode") is None
    assert kernel_counts.paged_attn_roofline_pct(ctx) is None
    assert kernel_counts.flash_roofline_pct(ctx, ("flash_fwd",), False) \
        is None
    # Slices are there, but a name the reader expects is not.
    bare = dict(recorded, ops=[[o[0].replace("flash_fwd", "shard_map"), o[1],
                                o[2], o[3].replace("paged_gather", "gather")]
                               for o in recorded["ops"]])
    ctx = _ctx(bare)
    with pytest.raises(progtrace.MissingName, match="paged_gather"):
        kernel_counts.paged_attn_roofline_pct(ctx)
    with pytest.raises(progtrace.MissingName, match="flash_fwd"):
        kernel_counts.flash_roofline_pct(ctx, ("flash_fwd",), False)
    with pytest.raises(progtrace.MissingName, match="jit_engine_adopt"):
        progtrace.device_ms_p50(ctx, "jit_engine_adopt_pages")
    with pytest.raises(progtrace.MissingName, match="launched as paged_suf"):
        progtrace.device_ms_p50(ctx, "jit_engine_paged_suffix",
                                role="paged_suffix")
    # No CPU run and no untraced run reads a device metric.
    assert progtrace.analysis({"trace": None, "trace_dir": "x"}) is None
    assert progtrace.analysis({"trace": {}, "trace_dir": None}) is None


def test_a_share_over_100_is_an_error_never_a_clamp(recorded):
    assert progtrace.share_pct(98.5e12, 197e12, 1.0, "x") == 50.0
    with pytest.raises(ValueError, match="the count is wrong"):
        progtrace.share_pct(2.0e14, 197e12, 1.0, "flash_fwd")
    # A kernel ten times as fast as the chip can be: the reader raises.
    fast = dict(recorded, ops=[
        [o[0], o[1], o[2] / 10 if progtrace.kernel_of(o[0]) else o[2], o[3]]
        for o in recorded["ops"]])
    with pytest.raises(ValueError, match="flash_fwd"):
        kernel_counts.flash_roofline_pct(_ctx(fast), ("flash_fwd",), False)


def test_useful_work_of_the_kernels():
    # pretrain_fsdp4: one call is [1, 16, 4096, 128]; 24 layers x 4
    # microbatches a chip and step, forward once and backward 2.5 times.
    fwd = kernel_counts.flash_fwd_flops(1, 16, 4096, 128)
    assert fwd == 2 * 4096 ** 2 * 128 * 16 == 68719476736
    assert kernel_counts.flash_bwd_flops(1, 16, 4096, 128) == 2.5 * fwd
    assert kernel_counts.flash_fwd_flops(1, 16, 4096, 128, causal=False) \
        == 2 * fwd
    assert 96 * 3.5 * fwd == pytest.approx(2.3e13, rel=0.01)
    # chat_steady: 13 slots of ~330 tokens, 8 KV heads of 128 in bf16,
    # 24 layers: 0.42 GB a step, half a millisecond at 819 GB/s.
    assert kernel_counts.paged_attn_bytes(4290, 8, 128, 2, 24) == \
        4290 * 2 * 8 * 128 * 2 * 24
    assert kernel_counts.paged_attn_bytes(1, 8, 128) == 4096


def test_slice_readers_on_hand_made_rows():
    def row(t0, t1, slices, phases=("decode",), events=()):
        r = {"t0": t0, "t1": t1, "phases": [{"phase": p} for p in phases],
             "slices": [dict(name=n, t0=a, t1=b, **kw)
                        for n, a, b, kw in slices]}
        if events:
            r["events"] = list(events)
        return r

    rows = [
        row(1.000, 1.100, [("park", 0.990, 1.000, {"active": 3}),
                           ("reap", 1.000, 1.001, {}),
                           ("admit", 1.001, 1.004, {}),
                           ("launch", 1.004, 1.010, {
                               "program": "prefill_chunk", "tokens": 512}),
                           ("admit", 1.010, 1.011, {}),
                           ("pages", 1.011, 1.013, {}),
                           ("launch", 1.013, 1.015, {"program": "decode"}),
                           ("fetch", 1.015, 1.090, {"program": "decode"}),
                           ("sample_emit", 1.090, 1.097, {}),
                           ("finish", 1.097, 1.099, {}),
                           ("sample_emit", 1.099, 1.100, {})],
            phases=("prefill_chunk", "decode"),
            events=[{"kind": "preempt", "prefilled": 128, "pages": 2}]),
        row(2.000, 2.010, [("reap", 2.000, 2.010, {})], phases=()),
    ]
    r = rows[0]
    assert progtrace.decodes(r) and not progtrace.decodes(rows[1])
    assert progtrace.host_ms(r) == pytest.approx(25.0)
    assert progtrace.slice_ms(r, ("sample_emit", "finish")) == \
        pytest.approx(10.0)
    assert progtrace.slice_ms(r, ("reap", "admit", "pages")) == \
        pytest.approx(7.0)
    assert progtrace.prefill_useful_ratio(rows) == pytest.approx(0.75)
    assert progtrace.prefill_useful_ratio(rows[1:]) is None
    assert progtrace.median_ms([]) is None


# --------------------------------------------------------- the rehearsal

ROW_BASED = {
    "internlm2-1.8b.chat_steady": {
        "step_host_ms_p50.chat", "sample_emit_ms_p50.chat",
        "admit_host_ms_p50.chat", "between_steps_ms_p50.chat"},
    "internlm2-1.8b.docs_batch": {
        "step_host_ms_p50.batch", "pages_ms_per_step.batch",
        "prefill_useful_ratio.batch", "pages_pinned_prefix_mean.batch"},
    "internlm2-1.8b.pretrain_fsdp4": set(),
}
DEVICE_BASED = {
    "decode_device_ms_p50", "chunk_device_ms_p50.chat",
    "chunk_device_ms_p50.batch", "paged_attn_roofline_pct.chat",
    "device_idle_unattributed_pct.chat",
    "device_idle_unattributed_pct.batch", "flash_fwd_roofline_pct",
    "flash_bwd_roofline_pct"}


def test_every_new_metric_has_its_reader_and_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = set().union(*ROW_BASED.values()) | DEVICE_BASED
    entries = {m["name"]: m for m in bench["per_layer"] if m["name"] in new}
    assert set(entries) == new and len(new) == 16
    e2e = {m["name"]: set(m.get("workloads", [])) for m in bench["end_to_end"]}
    for name, m in entries.items():
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "metrics", name + ".py"))
        # Reported only where the end-to-end metric it moves is.
        assert set(m["workloads"]) <= e2e[m["moves"]]
        assert ("roofline" in name) == (m["layer"] == "kernels")
    # New entries stand at the end of the list.
    assert {m["name"] for m in bench["per_layer"][-16:]} == new


@pytest.mark.parametrize("workload", sorted(ROW_BASED))
def test_rehearsal_reads_the_new_metrics(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", "3000000019", "--seconds", "4",
         "--trace", "1", "--rehearse"], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["device"]["platform"] == "cpu"
    got = set(line["metrics"])
    assert ROW_BASED[workload] <= got
    for name in ROW_BASED[workload]:
        assert line["metrics"][name]["value"] >= 0.0
    # No device metric may come out of a CPU run.
    assert not (DEVICE_BASED & got)
