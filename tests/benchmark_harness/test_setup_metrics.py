"""The eleven readers of the set-up record (PR 55) on records made by hand:
``setup.phase`` events as the program's flight recorder keeps them
(``ray_tpu/util/flightrec.py``; sites in ``docs/OBSERVABILITY.md`` "Set-up
phases"), one timeline a run, every second of ``setup_s`` in exactly one
phase. ``tests/test_setup_record.py`` holds the program to the sites; a
rehearsal (``test_a_rehearsal_tiles_its_set_up``) holds both ends."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import progtrace  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402

METRICS = os.path.join(ROOT, "benchmarks", "metrics")
T0 = 1_790_000_000.0          # the benchmark process starts
DRIVER, REPLICA, OTHER_RUN = 100, 200, 300
CLUSTER = "127.0.0.1:7001"
TILING = ["setup_runtime_start_s", "setup_placement_s", "setup_device_init_s",
          "setup_weights_s", "setup_engine_build_s", "setup_first_dispatch_s",
          "setup_before_window_s", "setup_unattributed_s"]
VIEWS = ["setup_probe_wait_s", "setup_compile_s", "setup_cache_hit_share"]
SERVE_ONLY = {"setup_engine_build_s", "setup_first_dispatch_s",
              "setup_before_window_s"}


def _read(name, ctx):
    return bench_run.read_metric(METRICS, name, ctx)


def _s(value):
    """Stamps near 1.8e9 s hold a quarter of a microsecond."""
    return pytest.approx(value, abs=1e-5)


def ev(pid, phase, t0, t1=None, **attrs):
    t1 = t0 if t1 is None else t1
    return {"ev": "setup.phase", "ts": T0 + t1, "pid": pid, "phase": phase,
            "t0": T0 + t0, "t1": T0 + t1, **attrs}


def placement(pid, edge, at, name="llm", cluster=CLUSTER):
    return ev(pid, f"placement.{edge}", at, name=name, cluster=cluster)


def dispatch(pid, key, t0, t1, compile_s, compiles=1, cache_hits=1):
    return ev(pid, "first_dispatch", t0, t1, key=key, compiles=compiles,
              compile_s=compile_s, cache_hits=cache_hits)


def serve_events():
    """A replica's start, seconds after ``T0``. Gaps nobody stamped: 14-15,
    27-27.5, 60-60.5."""
    return [
        ev(DRIVER, "runtime_start", 2.0, 14.0, chips=1),
        ev(DRIVER, "probe", 2.1, 13.9, tries=2, busy_wait_s=2.0),
        placement(DRIVER, "begin", 15.0),
        placement(REPLICA, "end", 27.0),
        ev(REPLICA, "device_init", 27.5, 34.5, import_s=3.0, platform="tpu",
           device_count=1),
        ev(REPLICA, "weights", 34.5, 43.0, bytes=10 ** 9),
        ev(REPLICA, "engine_build", 43.0, 45.0, slots=32, pool_bytes=10 ** 9),
        ev(REPLICA, "warm_decode", 45.0, 60.0, rungs=2),
        dispatch(REPLICA, "decode/64", 45.5, 50.0, 1.0),
        dispatch(REPLICA, "decode/128", 50.0, 59.0, 1.5),
        ev(REPLICA, "ready", 60.5, compiles=10, compile_s=5.0, cache_hits=9),
        dispatch(REPLICA, "paged_prefill/1/128", 70.0, 90.0, 8.0, compiles=2,
                 cache_hits=2),
        # Begun in set-up, ended in the window: clipped at ``open_wall``,
        # and no compile of set-up's.
        dispatch(REPLICA, "paged_suffix/1/2048", 115.0, 125.0, 4.0),
        # Other times, and a run beside this one (the tests run six at
        # once into one recorder directory).
        ev(REPLICA, "weights", -100.0, -90.0, bytes=1),
        dispatch(REPLICA, "decode_k/4/64", 130.0, 140.0, 1.0),
        ev(OTHER_RUN, "runtime_start", 1.0, 9.0, chips=1),
        placement(OTHER_RUN, "begin", 10.0, cluster="127.0.0.1:7002"),
        placement(OTHER_RUN + 1, "end", 20.0, cluster="127.0.0.1:7002"),
        ev(OTHER_RUN + 1, "weights", 21.0, 99.0, bytes=1),
    ]


def serve_ctx(events, setup_s=120.0):
    return {"kind": "serve", "setup_events": events, "setup_pid": DRIVER,
            "marks": {"open_wall": T0 + setup_s},
            "end_to_end": {"setup_s": setup_s}}


WANT_SERVE = {
    "setup_runtime_start_s": 14.0,        # from the process's start
    "setup_probe_wait_s": 11.8,
    "setup_placement_s": 12.0,
    "setup_device_init_s": 7.0,
    "setup_weights_s": 8.5,
    "setup_engine_build_s": 2.0 + (15.0 - 4.5 - 9.0),
    "setup_first_dispatch_s": 4.5 + 9.0 + 20.0 + 5.0,
    "setup_compile_s": 5.0 + 8.0,         # ``ready``'s, and the one after
    "setup_cache_hit_share": (9 + 2) / (10 + 2),
    "setup_before_window_s": (120.0 - 60.5) - 20.0 - 5.0,
    "setup_unattributed_s": 1.0 + 0.5 + 0.5,
}


@pytest.mark.parametrize("name", sorted(WANT_SERVE))
def test_a_replicas_start_phase_by_phase(name):
    assert _read(name, serve_ctx(serve_events())) == _s(WANT_SERVE[name])


def test_the_tiling_metrics_sum_to_setup_s_and_the_views_are_beside_them():
    ctx = serve_ctx(serve_events())
    assert sum(_read(n, ctx) for n in TILING) == _s(120.0)
    assert sum(WANT_SERVE[n] for n in TILING) == _s(120.0)
    assert sum(WANT_SERVE[n] for n in VIEWS) > 0


def test_nested_phases_are_subtracted_once():
    """``first_dispatch`` inside ``warm_decode`` inside a (mis-stamped)
    ``engine_build``: each second still has one owner."""
    events = [e for e in serve_events() if e["phase"] != "engine_build"]
    events.append(ev(REPLICA, "engine_build", 43.0, 60.0, slots=32,
                     pool_bytes=1))
    ctx = serve_ctx(events)
    assert _read("setup_engine_build_s", ctx) == _s(17.0 - 13.5)
    assert _read("setup_first_dispatch_s", ctx) == _s(38.5)
    assert sum(_read(n, ctx) for n in TILING) == _s(120.0)


def test_a_window_that_opens_earlier_clips_what_straddles_it():
    ctx = serve_ctx(serve_events(), setup_s=80.0)
    assert _read("setup_first_dispatch_s", ctx) == _s(4.5 + 9.0 + 10.0)
    assert _read("setup_before_window_s", ctx) == _s(80.0 - 60.5 - 10.0)
    assert _read("setup_compile_s", ctx) == _s(5.0)
    assert sum(_read(n, ctx) for n in TILING) == _s(80.0)


def test_an_asking_nobody_answered_raises():
    events = [e for e in serve_events()
              if not (e["phase"] == "placement.end" and e["pid"] == REPLICA)]
    with pytest.raises(progtrace.MissingName, match="no placement.end"):
        _read("setup_placement_s", serve_ctx(events))


@pytest.mark.parametrize("name", sorted(WANT_SERVE))
def test_no_record_gives_nothing(name):
    """The parent of PR 55 writes no ``setup.phase`` event; neither does
    another run's record make one for this run."""
    assert _read(name, serve_ctx([])) is None
    theirs = [e for e in serve_events() if e["pid"] >= OTHER_RUN]
    assert _read(name, serve_ctx(theirs)) is None


def test_a_run_without_a_probe_leaves_the_probe_out():
    events = [e for e in serve_events() if e["phase"] != "probe"]
    ctx = serve_ctx(events)
    assert _read("setup_probe_wait_s", ctx) is None
    assert _read("setup_runtime_start_s", ctx) == _s(14.0)


def train_ctx():
    worker = 400
    events = [
        ev(DRIVER, "runtime_start", 1.0, 13.0, chips=4),
        ev(DRIVER, "probe", 1.2, 12.9, tries=1, busy_wait_s=0.0),
        placement(DRIVER, "begin", 13.5, name="train_ab12"),
        placement(worker, "end", 16.0, name="train_ab12"),
        ev(worker, "device_init", 16.0, 24.0, import_s=4.0, platform="tpu",
           device_count=4),
        ev(worker, "weights", 25.0, 27.0, bytes=10 ** 9),
        ev(worker, "weights", 27.0, 28.0, bytes=2 * 10 ** 9),
    ]
    return {"kind": "train", "setup_events": events, "setup_pid": DRIVER,
            "end_to_end": {"setup_s": 70.0},
            "final": {"open_wall": T0 + 70.0,
                      "compiles": {"compiles": 9, "compile_s": 30.5,
                                   "cache_hits": 6}}}


WANT_TRAIN = {
    "setup_runtime_start_s": 13.0, "setup_probe_wait_s": 11.7,
    "setup_placement_s": 2.5, "setup_device_init_s": 8.0,
    "setup_weights_s": 3.0, "setup_compile_s": 30.5,
    "setup_cache_hit_share": 6 / 9,
    # 13-13.5, 24-25 and the benchmark's own loop, 28-70.
    "setup_unattributed_s": 0.5 + 1.0 + 42.0,
    "setup_engine_build_s": None, "setup_first_dispatch_s": None,
    "setup_before_window_s": None,
}


@pytest.mark.parametrize("name", sorted(WANT_TRAIN))
def test_a_trainers_start_phase_by_phase(name):
    got = _read(name, train_ctx())
    assert got is None if WANT_TRAIN[name] is None \
        else got == _s(WANT_TRAIN[name])


def test_the_benchmark_lists_the_eleven_under_setup_s():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("setup_runtime_start_s")
    block = bench["per_layer"][at:at + 11]
    assert {m["name"] for m in block} == set(TILING + VIEWS)
    cells = [w["name"] for w in bench["workloads"]]
    serve = [w["name"] for w in bench["workloads"]
             if w["traffic"] not in ("train", "pretrain_fsdp4")]
    assert len(cells) == 9 and len(serve) == 7
    for m in block:
        assert os.path.exists(os.path.join(METRICS, m["name"] + ".py"))
        assert m["moves"] == "setup_s" and m["layer"] == "set-up"
        assert m["workloads"] == (serve if m["name"] in SERVE_ONLY
                                  else cells)
        if m["name"] == "setup_cache_hit_share":
            assert (m["unit"], m["better"], m["source"]) == (
                "share", "higher", "program_counter")
        else:
            assert (m["unit"], m["better"], m["source"]) == (
                "s", "lower", "program_span")
    # What PR 53's ``test_chunk_ahead_share.py::test_the_benchmark_lists_it
    # _for_the_six_batch_cells`` holds beside "it is the LAST entry" (which
    # an appended entry ends, and only a ``benchmark`` PR may edit that
    # file: ROADMAP B17): its entry as it stood, where it stood, the eleven
    # behind it, its cells the benchmark's and its reader there.
    batch = [c for c in serve if c != "internlm2-1.8b.chat_steady"]
    assert bench["per_layer"][at - 1] == {
        "name": "chunk_ahead_share.batch", "unit": "share",
        "better": "higher", "source": "program_span",
        "layer": "admission, batching, KV pages",
        "moves": "serve_tokens_per_s", "workloads": batch}
    assert len(batch) == 6 and set(batch) <= set(cells)
    assert os.path.exists(os.path.join(METRICS, "chunk_ahead_share.batch.py"))
    assert names[at + 11:] == []


@pytest.mark.parametrize("workload,absent", [
    ("internlm2-1.8b.chat_steady", set()),
    ("vit-b16.train", SERVE_ONLY),
])
def test_a_rehearsal_tiles_its_set_up(workload, absent):
    """The whole path on the CPU: the program's records, through the
    recorder's files, to the line. A rehearsal is given its chips, so it
    probes nothing. Untraced, the line is the end-to-end metrics alone."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    lines = {}
    for trace in ("1", "0"):
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
             "--workload", workload, "--seed", "3100000055", "--seconds",
             "4", "--trace", trace, "--rehearse"], env=env, cwd=ROOT,
            capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        lines[trace] = json.loads(p.stdout.strip().splitlines()[-1])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    end_to_end = {m["name"] for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])}
    assert set(lines["0"]["metrics"]) == end_to_end
    got = {k: v["value"] for k, v in lines["1"]["metrics"].items()
           if k.startswith("setup_")}
    assert set(got) == set(TILING + VIEWS) - absent - {"setup_probe_wait_s"}
    assert all(v >= 0 for v in got.values())
    assert got["setup_cache_hit_share"] <= 1.0
    tiled = sum(got[n] for n in TILING if n not in absent)
    # ``setup_s`` itself is not on a traced line; the tiling is its split,
    # so it is no shorter than the worker's own phases and no longer than
    # the run.
    assert tiled > got["setup_device_init_s"] + got["setup_weights_s"]
    if not absent:
        assert got["setup_unattributed_s"] < 5.0
