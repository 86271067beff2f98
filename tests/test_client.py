"""Thin-client protocol tests (reference: Ray Client, util/client/).

The client process owns nothing: a ClientServer inside the cluster hosts
the real refs/actors. Covered: put/get, tasks with (nested) ref args,
multiple returns, actors incl. named lookup + kill, wait, disconnect
cleanup semantics, and a REAL separate client process driving the cluster
over one TCP connection.
"""

import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest


@pytest.fixture
def client_pair(ray_start_regular):
    from ray_tpu import client as client_mod

    server = client_mod.ClientServer(host="127.0.0.1")
    client = client_mod.connect(f"ray-tpu://{server.address[0]}:"
                                f"{server.address[1]}")
    yield server, client
    client.disconnect()
    server.stop()


def test_put_get_task_actor_roundtrip(client_pair):
    import ray_tpu

    _server, client = client_pair

    # put/get with numpy payload
    arr = np.arange(1000, dtype=np.float32)
    ref = ray_tpu.put(arr)
    np.testing.assert_array_equal(ray_tpu.get(ref), arr)

    # top-level ref arg: resolved to its value before execution
    @ray_tpu.remote
    def add(a, b):
        return a + b

    out = ray_tpu.get(add.remote(ref, np.ones(1000, np.float32)))
    np.testing.assert_array_equal(out, arr + 1.0)

    # NESTED ref (reference semantics: stays a ref; the task gets it)
    @ray_tpu.remote
    def nested_sum(d):
        return float(ray_tpu.get(d["r"]).sum()) + d["c"]

    assert ray_tpu.get(nested_sum.remote({"r": ref, "c": 0.5})) == \
        float(arr.sum()) + 0.5

    # multiple returns
    @ray_tpu.remote(num_returns=2)
    def two():
        return 1, 2

    r1, r2 = two.remote()
    assert ray_tpu.get(r1) == 1 and ray_tpu.get(r2) == 2

    # wait
    ready, pending = ray_tpu.wait([r1, r2], num_returns=2, timeout=30)
    assert len(ready) == 2 and not pending

    # actor create/call/kill
    @ray_tpu.remote
    class Counter:
        def __init__(self, start):
            self.n = start

        def inc(self, k=1):
            self.n += k
            return self.n

    c = Counter.remote(10)
    assert ray_tpu.get(c.inc.remote()) == 11
    assert ray_tpu.get(c.inc.remote(5)) == 16
    ray_tpu.kill(c)

    # task error propagates to the client
    @ray_tpu.remote(max_retries=0)
    def boom():
        raise ValueError("boom-from-task")

    with pytest.raises(Exception, match="boom"):
        ray_tpu.get(boom.remote(), timeout=60)


def test_named_actor_survives_disconnect(client_pair):
    import ray_tpu
    from ray_tpu import client as client_mod

    server, client = client_pair

    @ray_tpu.remote
    class Holder:
        def __init__(self):
            self.v = 7

        def v_(self):
            return self.v

    named = Holder.options(name="keeper").remote()
    unnamed = Holder.remote()
    assert ray_tpu.get(named.v_.remote()) == 7
    unnamed_key = unnamed._key
    client.disconnect()

    # Reconnect: the named actor is still there, the unnamed one is gone.
    client2 = client_mod.connect(
        f"ray-tpu://{server.address[0]}:{server.address[1]}")
    try:
        again = ray_tpu.get_actor("keeper")
        assert ray_tpu.get(again.v_.remote()) == 7
        with pytest.raises(Exception):
            h = client_mod.ClientActorHandle(unnamed_key, client2)
            ray_tpu.get(h.v_.remote(), timeout=15)
    finally:
        client2.disconnect()


@pytest.mark.timeout_s(120)
def test_stale_session_reaped(ray_start_regular):
    """A crashed client (keepalive stops, no disconnect) gets its session
    reaped server-side: refs released, unnamed actors killed."""
    from ray_tpu import client as client_mod
    from ray_tpu.core.config import config

    config.update({"client_session_timeout_s": 3.0})
    server = client_mod.ClientServer(host="127.0.0.1")
    client = client_mod.connect(
        f"ray-tpu://{server.address[0]}:{server.address[1]}")
    try:
        import ray_tpu

        @ray_tpu.remote
        class Doomed:
            def alive(self):
                return True

        d = Doomed.remote()
        assert ray_tpu.get(d.alive.remote(), timeout=60)
        assert len(server._sessions) == 1
        # Simulate a crash: keepalive stops, no disconnect ever arrives.
        client._stop_ping.set()
        deadline = time.monotonic() + 30
        while server._sessions and time.monotonic() < deadline:
            time.sleep(0.2)
        assert not server._sessions, "stale session was not reaped"
    finally:
        client.disconnect()
        server.stop()
        config.update({"client_session_timeout_s": 60.0})


@pytest.mark.timeout_s(150)
def test_separate_client_process(ray_start_regular):
    """A genuinely separate OS process drives the cluster as a thin client
    over one outbound TCP connection."""
    from ray_tpu import client as client_mod

    server = client_mod.ClientServer(host="127.0.0.1")
    script = textwrap.dedent(f"""
        import numpy as np
        import ray_tpu

        ray_tpu.init(address="ray-tpu://{server.address[0]}:{server.address[1]}")

        @ray_tpu.remote
        def square(x):
            return x * x

        refs = [square.remote(i) for i in range(8)]
        assert ray_tpu.get(refs, timeout=90) == [i * i for i in range(8)]

        @ray_tpu.remote
        class Acc:
            def __init__(self):
                self.total = 0
            def add(self, v):
                self.total += v
                return self.total

        acc = Acc.remote()
        for i in range(5):
            last = acc.add.remote(i)
        assert ray_tpu.get(last, timeout=60) == 10
        big = ray_tpu.put(np.ones((256, 256)))
        assert float(ray_tpu.get(big).sum()) == 256 * 256
        ray_tpu.shutdown()
        print("CLIENT-OK")
    """)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            timeout=120, env={**__import__("os").environ,
                              "PYTHONPATH": "/root/repo",
                              "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "CLIENT-OK" in proc.stdout
    finally:
        server.stop()
