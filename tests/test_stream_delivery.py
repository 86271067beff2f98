"""A streamed item leaves the replica when it exists (ISSUE 26).

The stream path — ``StreamQueue`` / ``ReplicaActor.next_chunks``, the
engine's end-of-request hook, the proxy's chunk writes — on the CPU, by
events rather than clocks wherever possible: a delivery never waits for a
second item, a fast producer still ships sixteen at a time, and a stream
ends on every way its request can end without waiting out the backstop.
"""

import io
import json
import os
import socket
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core import serialization
from ray_tpu.core.errors import (DeadlineExceededError, OverloadedError,
                                 RequestCancelledError)
from ray_tpu.serve.decode import LlamaDecodeDeployment
from ray_tpu.serve.replica import ReplicaActor, StreamQueue
from ray_tpu.util import metrics as um

# Every wait in this file that should end at once is bounded by this, and
# the backstop is pushed far past it: a stream that needs the backstop to
# end fails its test instead of passing half a second late.
PROMPT = 5.0


@pytest.fixture(autouse=True)
def far_backstop(monkeypatch):
    monkeypatch.setattr(StreamQueue, "BACKSTOP_S", 60.0)


def _replica(cls, *args, **kwargs) -> ReplicaActor:
    """A replica in THIS process: its streams, its counters and the hosted
    instance can be looked at directly."""
    return ReplicaActor(serialization.dumps_function(cls), args, kwargs)


def _pull_items_histogram() -> dict:
    for m in um._Registry.get().snapshot(run_collectors=False):
        if m["name"] == "serve_stream_pull_items":
            return m
    return {"count": 0, "sum": 0.0, "counts": [0] * 7}


def _in_thread(fn, *args):
    """Run ``fn`` beside the test; ``box`` gets ("ok", value) or
    ("raised", exception), ``box['done']`` is set when it has."""
    box = {"done": threading.Event()}

    def run():
        try:
            box["result"] = ("ok", fn(*args))
        except BaseException as e:  # noqa: BLE001 — the test looks at it
            box["result"] = ("raised", e)
        box["done"].set()

    threading.Thread(target=run, daemon=True).start()
    return box


# ------------------------------------------------------ (i) the first item


class _Handshake:
    """Yields item 0, then refuses to go on until the consumer HAS it."""

    def __init__(self):
        self.received = threading.Event()
        self.gave_up = False

    def __call__(self, n):
        yield 0
        if not self.received.wait(30):
            self.gave_up = True
        for i in range(1, n):
            yield i


def test_first_item_is_not_held_for_the_second():
    rep = _replica(_Handshake)
    sid = rep.start_stream("__call__", (3,), {})
    pull = _in_thread(rep.next_chunks, sid)
    # On the parent this pull sat in next() for item 1, which the producer
    # withholds until item 0 is received: it returned only after 30 s.
    assert pull["done"].wait(PROMPT), "the pull waits for a second item"
    assert pull["result"] == ("ok", ([0], False))
    rep._instance.received.set()
    got = []
    done = False
    while not done:
        items, done = rep.next_chunks(sid)
        got.extend(items)
    assert got == [1, 2] and not rep._instance.gave_up
    assert rep._ongoing == 0 and not rep._streams


# ------------------------------------------------- (ii) a producer ahead


class _Fast:
    def __call__(self, n):
        yield from range(n)


def test_fast_producer_ships_sixteen_at_a_time():
    before = _pull_items_histogram()
    rep = _replica(_Fast)
    sid = rep.start_stream("__call__", (1000,), {})
    got, pulls = [], []
    done = False
    while not done:
        items, done = rep.next_chunks(sid)
        assert 0 <= len(items) <= 16
        got.extend(items)
        pulls.append(len(items))
        if len(pulls) < 3:
            # Let the producer get one delivery ahead, as it is whenever
            # a pull takes an RPC's time to come back round.
            time.sleep(0.05)
    assert got == list(range(1000))  # each once, in order
    after = _pull_items_histogram()
    replies = after["count"] - before["count"]
    assert replies == len(pulls)
    # Fed from the stream's record: each reply at the stream's mean.
    assert after["sum"] - before["sum"] == pytest.approx(1000)
    assert replies <= 1000 // 16 + 8, pulls
    # Nearly every reply is full: the bucket of exactly sixteen items.
    buckets = after["buckets"]
    full = (after["counts"][buckets.index(16)]
            - before["counts"][buckets.index(16)])
    assert full >= 55, (full, pulls)
    assert rep._ongoing == 0


class _Counting:
    def __init__(self):
        self.made = []

    def __call__(self, n):
        for i in range(n):
            self.made.append(i)
            yield i


def test_pump_stays_one_delivery_ahead_of_its_consumer():
    """Backpressure survives: with nothing pulled after the first
    delivery, the generator is not run to its end."""
    rep = _replica(_Counting)
    made = rep._instance.made
    sid = rep.start_stream("__call__", (10_000,), {})
    assert made == []  # nothing runs before the first pull
    items, done = rep.next_chunks(sid, 4)
    assert items and not done
    time.sleep(0.2)
    assert len(made) <= len(items) + 4 + 1, len(made)
    rep.cancel_stream(sid)
    deadline = time.monotonic() + PROMPT
    n = len(made)
    while time.monotonic() < deadline:  # the pump stops, the generator too
        time.sleep(0.05)
        if len(made) == n:
            break
        n = len(made)
    assert len(made) < 100 and rep._ongoing == 0


def test_stream_queue_delivers_items_put_before_an_error():
    q = StreamQueue()
    q.put(1)
    q.put(2)
    q.end(ValueError("late"))
    q.end(KeyError("the first end wins"))
    assert q.take(16) == ([1, 2], False)
    with pytest.raises(ValueError):
        q.take(16)
    done = StreamQueue()
    done.put("x")
    done.end()
    assert list(done) == ["x"]
    assert done.take(16) == ([], True)


def test_stream_queue_under_a_racing_producer():
    """Put and take from two threads that are switched every few
    bytecodes: every item once, in order, never more than asked for."""
    import random
    import sys

    q = StreamQueue()
    n = 20_000

    def produce():
        for i in range(n):
            q.put(i)
        q.end()

    rng = random.Random(0)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        producer = threading.Thread(target=produce, daemon=True)
        producer.start()
        got = []
        done = False
        deadline = time.monotonic() + 60
        while not done:
            assert time.monotonic() < deadline
            cap = rng.randint(1, 16)
            items, done = q.take(cap)
            assert len(items) <= cap and (items or done)
            got.extend(items)
        producer.join(timeout=30)
        assert not producer.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert got == list(range(n))
    q.close()
    assert q.record["items"] == n


# ------------------------------------------------ (iii) every way to end


def _tiny_cfg():
    from ray_tpu.models import llama

    return llama.LlamaConfig(vocab_size=61, dim=32, n_layers=2, n_heads=4,
                             n_kv_heads=2, mlp_dim=64, max_seq_len=128)


STEP_S = 0.02


class _SlowDecode(LlamaDecodeDeployment):
    """The tiny engine with a step of ~20 ms, so that an ending lands
    while the stream is open; ``break_after`` in a request makes its
    ``on_token`` raise after that many tokens."""

    def __init__(self, step_s=STEP_S, **kwargs):
        super().__init__(config=_tiny_cfg(), capacity=64,
                         prefix_pool_entries=0, kv_page_tokens=8,
                         **kwargs)
        self.emitted = {}  # request_id -> wall time of each token
        inner = self.engine._decode

        def slow(*a, **k):
            time.sleep(step_s)
            return inner(*a, **k)

        self.engine._decode = slow

    def _submit(self, request, on_token=None, **kwargs):
        limit = request.get("break_after")
        stamps = self.emitted.setdefault(request.get("request_id"), [])
        deliver = on_token

        def stamped(tok):
            stamps.append(time.time())
            if limit is not None and len(stamps) > limit:
                raise RuntimeError("consumer wedged")
            if deliver is not None:
                deliver(tok)

        return super()._submit(request, on_token=stamped, **kwargs)

    def emit_times(self, request_id):
        return list(self.emitted.get(request_id, ()))


def _quiet(engine, timeout=PROMPT):
    """The engine with nothing seated and nothing queued, or the stats
    that say otherwise."""
    deadline = time.monotonic() + timeout
    while True:
        s = engine.stats()
        if (s["active"] == 0 and s["prefilling"] == 0 and s["queued"] == 0
                and s["free_slots"] == s["slots"]
                and s["pages_free"] == s["pages_total"]
                and not engine._requests):  # popped last, by the loop
            return s
        assert time.monotonic() < deadline, (s, engine._requests)
        time.sleep(0.01)


def _request(n, **extra):
    return dict({"tokens": [5, 9, 2], "max_new_tokens": n}, **extra)


def _drain(rep, sid):
    got = []
    done = False
    while not done:
        items, done = rep.next_chunks(sid)
        got.extend(items)
    return got


def _end_completed(rep):
    sid = rep.start_stream("stream", (_request(6),), {})
    return "ok", len(_drain(rep, sid))


def _end_hang_up(rep):
    sid = rep.start_stream("stream", (_request(50),), {})
    items, done = rep.next_chunks(sid)
    assert items and not done
    rep.cancel_stream(sid)  # what the router's finally sends
    return "ok", None


def _end_deadline(rep):
    sid = rep.start_stream("stream", (_request(50),), {}, "", 0.3)
    return "raised", pytest.raises(DeadlineExceededError, _drain, rep, sid)


def _end_shed(rep):
    hog = rep.start_stream("stream", (_request(50),), {})
    rep.next_chunks(hog)  # seated: the one slot is taken
    queued = rep.start_stream("stream", (_request(4),), {})
    assert rep._ongoing == 2
    with pytest.raises(OverloadedError):  # queue_max=1: before any stream
        rep.start_stream("stream", (_request(4),), {})
    assert rep._ongoing == 2
    rep.cancel_stream(hog)
    rep.cancel_stream(queued)
    return "raised", None


def _end_on_token_raises(rep):
    sid = rep.start_stream("stream", (_request(50, break_after=2),), {})
    with pytest.raises(RuntimeError, match="consumer wedged") as err:
        got = _drain(rep, sid)
        pytest.fail(f"the stream ended quietly after {got}")
    return "raised", err


def _end_engine_shutdown(rep):
    sids = [rep.start_stream("stream", (_request(50),), {})]
    rep.next_chunks(sids[0])  # seated; the next one waits in the queue
    sids.append(rep.start_stream("stream", (_request(50),), {}))
    pulls = [_in_thread(_drain, rep, sid) for sid in sids]
    time.sleep(3 * STEP_S)
    rep._instance.engine.shutdown()
    for pull in pulls:
        assert pull["done"].wait(PROMPT), "a stream outlived its engine"
        kind, err = pull["result"]
        assert kind == "raised" and isinstance(err, RequestCancelledError)
    return "raised", None


def _end_cancel_while_blocked(rep):
    hog = rep.start_stream("stream", (_request(50),), {})
    rep.next_chunks(hog)
    sid = rep.start_stream("stream", (_request(4),), {})  # queued: no token
    pull = _in_thread(rep.next_chunks, sid)
    assert not pull["done"].wait(5 * STEP_S)  # blocked for its first item
    rep.cancel_stream(sid)
    assert pull["done"].wait(PROMPT), "cancel left the pull blocked"
    kind, err = pull["result"]
    assert kind == "raised" and isinstance(err, RequestCancelledError)
    rep.cancel_stream(hog)
    return "raised", None


def _end_loop_dies(rep):
    sid = rep.start_stream("stream", (_request(50),), {})
    rep.next_chunks(sid)
    pull = _in_thread(_drain, rep, sid)

    def broken(*a, **k):
        raise MemoryError("the device is out of memory")

    rep._instance.engine._decode = broken  # the next step kills the loop
    assert pull["done"].wait(PROMPT), "a stream outlived its engine's loop"
    kind, err = pull["result"]
    assert kind == "raised" and isinstance(err, RequestCancelledError)
    return "raised", None


@pytest.mark.filterwarnings(  # loop_dies: the loop's thread does die
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
@pytest.mark.parametrize("ending", [
    _end_completed, _end_hang_up, _end_deadline, _end_shed,
    _end_on_token_raises, _end_engine_shutdown, _end_cancel_while_blocked,
    _end_loop_dies,
], ids=lambda f: f.__name__[len("_end_"):])
def test_stream_ends_with_its_request(ending):
    """Each ending inside PROMPT seconds with the backstop 60 s away: the
    stream stops or raises its typed error, the replica counts nothing in
    flight, and the engine's slot and pages are free."""
    rep = _replica(_SlowDecode, slots=1, queue_max=1)
    engine = rep._instance.engine
    t0 = time.monotonic()
    try:
        run = _in_thread(ending, rep)
        assert run["done"].wait(4 * PROMPT), "the ending hangs"
        kind, value = run["result"]
        if kind == "raised":
            raise value
        assert time.monotonic() - t0 < 4 * PROMPT
        assert rep._ongoing == 0 and not rep._streams
        stats = _quiet(engine)
        if ending is _end_completed:
            assert value == ("ok", 6)
        if ending is _end_shed:
            assert stats["shed"] == 1
    finally:
        engine.shutdown()


def test_in_process_stream_iterates_and_closes_like_a_generator():
    dep = _SlowDecode(slots=1)
    try:
        toks = list(dep.stream(_request(5)))
        assert len(toks) == 5 and all(isinstance(t, int) for t in toks)
        stream = dep.stream(_request(50))
        assert isinstance(next(stream), int)
        stream.close()
        stream.close()  # idempotent
        assert _quiet(dep.engine)["cancelled"] == 1
    finally:
        dep.engine.shutdown()


def test_unary_wait_wakes_when_the_engine_shuts_down():
    """``_wait_done`` has no deadline to wake it: the shutdown must."""
    dep = _SlowDecode(slots=1)
    call = _in_thread(dep, _request(50))
    time.sleep(3 * STEP_S)
    dep.engine.shutdown()
    assert call["done"].wait(PROMPT)
    kind, err = call["result"]
    assert kind == "raised" and isinstance(err, RequestCancelledError)
    _quiet(dep.engine)


# ----------------------------------------------- (iv) through the proxy


@pytest.fixture
def serve_cluster(ray_start_regular):
    yield
    try:
        serve.shutdown()
    except Exception:
        pass


def _read_stream_lines(host, port, route, payload):
    """[(arrival wall time, parsed line)] of one streamed response, read
    from the raw socket so that a line's time is when its bytes came."""
    body = json.dumps(payload).encode()
    sock = socket.create_connection((host, port), timeout=60)
    sock.sendall(
        f"POST /{route} HTTP/1.1\r\nHost: x\r\nX-Serve-Stream: 1\r\n"
        f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    buf = b""
    out = []
    seen = 0
    while True:
        data = sock.recv(65536)
        now = time.time()
        if not data:
            break
        buf += data
        head, sep, rest = buf.partition(b"\r\n\r\n")
        if not sep:
            continue
        lines = [ln for ln in rest.split(b"\r\n")
                 if ln.endswith(b"\n")]  # chunk payloads are whole lines
        for ln in lines[seen:]:
            out.append((now, json.loads(ln)))
        seen = len(lines)
        if rest.endswith(b"0\r\n\r\n"):
            break
    sock.close()
    return out


@pytest.mark.timeout_s(240)
def test_first_token_reaches_the_client_before_the_third_exists(
        serve_cluster):
    step_s = 0.15
    serve.run(serve.deployment(_SlowDecode).bind(step_s=step_s, slots=2),
              name="slow")
    host, port = serve.start_http()
    handle = serve.get_deployment_handle("slow")
    # Warm the path (compiles, route table) outside the measured request.
    _read_stream_lines(host, port, "slow",
                       _request(3, stream=True, request_id="warm"))
    lines = _read_stream_lines(host, port, "slow",
                               _request(8, stream=True, request_id="r1"))
    emitted = handle.emit_times.remote("r1").result(timeout=30)
    assert len(lines) == 8 and len(emitted) == 8
    assert all(isinstance(tok, int) for _, tok in lines)
    # Same host, one wall clock: the first line is at the client before
    # the engine has made its third token (the parent held it for all 8).
    assert lines[0][0] < emitted[2], (lines[0][0] - emitted[0],
                                      emitted[2] - emitted[0])
    arrivals = [t for t, _ in lines[:5]]
    gaps = [b - a for a, b in zip(arrivals, arrivals[1:])]
    assert min(gaps) > step_s / 5, gaps  # no two of them together
    slo = None
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        slo = serve.status()["slow"].get("slo", {}).get("stream_pull_items")
        if slo and slo["count"] >= 8:
            break
        time.sleep(0.5)
    assert slo and slo["mean"] < 2.0, slo  # ~1 item a reply


# ----------------------------------------------- (v) the proxy's writes


class _CountingWriter(io.BytesIO):
    def __init__(self):
        super().__init__()
        self.writes = []

    def write(self, data):
        self.writes.append(bytes(data))
        return super().write(data)


def test_proxy_sends_each_chunk_as_one_write():
    from ray_tpu.serve import proxy as proxy_mod

    items = [{"token": 1}, {"token": 22}, {"token": 333}]

    class FakeHandle:
        def stream(self, payload):
            yield from items

    handler_cls = proxy_mod.make_handler(proxy_mod._InFlight(),
                                         proxy_mod._RouteTable())
    handler = handler_cls.__new__(handler_cls)
    handler.wfile = _CountingWriter()
    handler.request_version = "HTTP/1.1"
    handler.requestline = "POST /x HTTP/1.1"
    handler.client_address = ("127.0.0.1", 0)
    handler._stream_response(FakeHandle(), None, "x")
    # send_response/end_headers flush the header block as one write; then
    # one write a chunk, and the terminator.
    chunks = [w for w in handler.wfile.writes
              if not w.startswith(b"HTTP/1.1")]
    assert len(chunks) == len(items) + 1, chunks
    for item, data in zip(items, chunks):
        line = json.dumps(item).encode() + b"\n"
        assert data == b"%x\r\n%s\r\n" % (len(line), line)
    assert chunks[-1] == b"0\r\n\r\n"
    assert handler.close_connection


def test_proxy_socket_has_tcp_nodelay():
    from http.server import ThreadingHTTPServer

    from ray_tpu.serve import proxy as proxy_mod

    seen = {}
    base = proxy_mod.make_handler(proxy_mod._InFlight(),
                                  proxy_mod._RouteTable())

    class Probe(base):
        def do_GET(self):  # noqa: N802
            seen["nodelay"] = self.connection.getsockopt(
                socket.IPPROTO_TCP, socket.TCP_NODELAY)
            super().do_GET()

    server = ThreadingHTTPServer(("127.0.0.1", 0), Probe)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        import urllib.request

        host, port = server.server_address
        with urllib.request.urlopen(f"http://{host}:{port}/-/healthz",
                                    timeout=30) as resp:
            assert resp.read() == b"ok"
    finally:
        server.shutdown()
        server.server_close()
    assert seen["nodelay"] != 0


# ------------------------------------- (vi) shutdown with streams open


@pytest.mark.timeout_s(300)
def test_shutdown_with_64_streams_open_leaves_no_process():
    # The check the benchmark makes after every cell, itself.
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.run import leftover_processes as _processes_of

    core = ray_tpu.init(num_cpus=4)
    node_hex = core.node_id.hex()
    try:
        serve.run(serve.deployment(_SlowDecode, max_ongoing_requests=80)
                  .bind(step_s=0.05, slots=4, queue_max=128), name="many")
        handle = serve.get_deployment_handle("many")
        assert _processes_of(node_hex)
        firsts = []

        def client(i):
            stream = handle.stream(_request(60, request_id=f"c{i}"))
            try:
                firsts.append(next(stream))
                for _ in stream:
                    pass
            except Exception:  # noqa: BLE001 — the shutdown cuts it off
                pass

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(64)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 120
        while len(firsts) < 4:  # the four slots stream, sixty wait
            assert time.monotonic() < deadline, len(firsts)
            time.sleep(0.05)
        # Sixty-four threads take a moment to open their streams on a
        # busy host: shut down once half of them are seen ongoing.
        while True:
            status = serve.status()["many"]
            if status["ongoing"] >= 32:
                break
            assert time.monotonic() < deadline, status
            time.sleep(0.05)
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    deadline = time.monotonic() + 30
    while True:
        left = _processes_of(node_hex)
        if not left:
            break
        assert time.monotonic() < deadline, left
        time.sleep(0.5)
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
