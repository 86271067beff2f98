"""The load generator: one thread, one asyncio loop, one TCP connection per
request (the proxy closes a stream's connection), every token line stamped
with the host's monotonic clock as it arrives.

Open loop: request ``i`` is sent when its due time comes whether or not
earlier ones have ended, and its latency counts from the DUE time. Closed
loop: a fixed number of clients each send their next request when the last
one ends."""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from benchmarks.traffic import Request

REQUEST_TIMEOUT_S = 300


@dataclass
class Outcome:
    request: Request
    request_id: str
    due: float = 0.0                 # monotonic
    sent: float = 0.0
    arrivals: List[float] = field(default_factory=list)  # one per token
    tokens: List[int] = field(default_factory=list)
    ended: Optional[float] = None    # stream closed cleanly
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return (self.error is None and self.ended is not None
                and len(self.arrivals) == self.request.answer_len)


async def _post_stream(addr: Tuple[str, int], route: str, payload: Dict,
                       out: Outcome) -> None:
    """POST ``payload`` and read the chunked JSON-lines stream to its end,
    stamping every token line."""
    body = json.dumps(dict(payload, stream=True)).encode()
    head = (f"POST {route} HTTP/1.1\r\nHost: {addr[0]}\r\n"
            f"Content-Type: application/json\r\nX-Serve-Stream: 1\r\n"
            f"X-Request-Timeout-S: {REQUEST_TIMEOUT_S}\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n")
    reader, writer = await asyncio.open_connection(*addr)
    try:
        out.sent = time.monotonic()
        writer.write(head.encode() + body)
        await writer.drain()
        status = await reader.readline()
        code = int(status.split()[1])
        chunked = False
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            if line.lower().startswith(b"transfer-encoding") and \
                    b"chunked" in line.lower():
                chunked = True
        if code != 200 or not chunked:
            rest = await reader.read(300)
            out.error = f"HTTP {code}: {rest[:200]!r}"
            return
        while True:
            size = int((await reader.readline()).split(b";")[0].strip()
                       or b"0", 16)
            if size == 0:
                break
            data = await reader.readexactly(size + 2)
            now = time.monotonic()
            for item in data[:-2].splitlines():
                tok = json.loads(item)
                if isinstance(tok, dict):
                    out.error = f"stream error record: {tok}"
                    return
                out.arrivals.append(now)
                out.tokens.append(tok)
        out.ended = time.monotonic()
    finally:
        writer.close()


async def send(addr, route, vocab, out: Outcome, sink: List[Outcome]):
    r = out.request
    payload = {"tokens": r.tokens(vocab), "max_new_tokens": r.answer_len,
               "request_id": out.request_id}
    sink.append(out)
    try:
        await asyncio.wait_for(_post_stream(addr, route, payload, out),
                               REQUEST_TIMEOUT_S)
    except asyncio.CancelledError:
        out.error = out.error or "cancelled at the drain limit"
        raise
    except Exception as e:  # noqa: BLE001 — a failed request is a result
        out.error = f"{type(e).__name__}: {e}"


async def open_loop(addr, route: str, vocab: int, schedule: List[Request],
                    t_start: float, stop_when: Callable[[], bool],
                    hard_stop: float, rid_prefix: str,
                    sink: List[Outcome]) -> None:
    """Send ``schedule`` at its due times from ``t_start`` (monotonic) until
    ``stop_when()`` holds or ``hard_stop`` passes; requests still open then
    are cancelled, and count as failed if measured. Outcomes are appended
    to ``sink`` as requests are sent, so ``stop_when`` can read them."""
    tasks = []
    for r in schedule:
        due = t_start + r.due_s
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        if time.monotonic() > hard_stop or stop_when():
            break
        out = Outcome(r, f"{rid_prefix}-{r.index}", due=due)
        tasks.append(asyncio.ensure_future(send(addr, route, vocab, out,
                                                sink)))
    while tasks and time.monotonic() < hard_stop and not stop_when():
        await asyncio.wait(tasks, timeout=0.05)
        if all(t.done() for t in tasks):
            break
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def closed_loop(addr, route: str, vocab: int, requests: List[Request],
                      clients: int, t_stop: float, rid_prefix: str,
                      sink: List[Outcome]) -> None:
    """``clients`` clients take requests off one list (wrapping round) and
    each sends its next when its last one ends, until ``t_stop``; requests
    open then are cancelled. Outcomes are appended to ``sink``."""
    nxt = [0]

    async def client():
        while time.monotonic() < t_stop:
            i = nxt[0]
            nxt[0] += 1
            out = Outcome(requests[i % len(requests)], f"{rid_prefix}-{i}",
                          due=time.monotonic())
            await send(addr, route, vocab, out, sink)
            if out.error:
                await asyncio.sleep(0.2)  # do not spin on a refusing server

    tasks = [asyncio.ensure_future(client()) for _ in range(clients)]
    await asyncio.sleep(max(0.0, t_stop - time.monotonic()))
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
