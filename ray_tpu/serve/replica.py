"""ReplicaActor: hosts the user callable + multiplexed model cache.

Analogue of the reference's ``ReplicaActor`` + ``UserCallableWrapper``
(``serve/_private/replica.py:231,750``) and the replica half of model
multiplexing (``serve/multiplex.py`` — per-replica LRU of loaded models,
residency reported to the controller for model-aware routing).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu.core import runtime
from ray_tpu.core.errors import RequestCancelledError
from ray_tpu.util import flightrec

logger = logging.getLogger(__name__)

_current_model_id = threading.local()
_current_deadline = threading.local()

# Which deployment/replica THIS worker process hosts — set by
# ReplicaActor.__init__ before the user class is constructed, so a
# DecodeEngine built inside it labels its SLO metrics by deployment
# without the engine ever knowing the serve plane exists. A process
# hosts at most one replica (replicas are dedicated actors).
_replica_ident: Dict[str, str] = {"deployment": "", "replica_id": ""}


def replica_ident() -> Dict[str, str]:
    """{'deployment', 'replica_id'} of the replica hosted by this
    process (empty strings outside a replica worker)."""
    return dict(_replica_ident)


def get_multiplexed_model_id() -> str:
    """Inside a replica: the model id of the in-flight request (reference:
    ``serve.get_multiplexed_model_id``)."""
    return getattr(_current_model_id, "value", "")


def request_deadline_s() -> Optional[float]:
    """Inside a replica: seconds remaining on the in-flight request's
    deadline, or None when the caller set none. The deadline is
    propagated as a RELATIVE duration at every hop (proxy -> handle ->
    replica) so it never depends on cross-process clock agreement; here
    it is re-anchored to this process's monotonic clock on arrival."""
    deadline = getattr(_current_deadline, "value", None)
    if deadline is None:
        return None
    return deadline - time.monotonic()


class _MultiplexCache:
    """Per-replica LRU of loaded models (multiplex.py's model cache)."""

    def __init__(self, loader, capacity: int):
        self._loader = loader
        self._capacity = max(1, capacity)
        self._models: "OrderedDict[str, Any]" = OrderedDict()
        self._lock = threading.Lock()

    def get(self, instance, model_id: str):
        with self._lock:
            if model_id in self._models:
                self._models.move_to_end(model_id)
                return self._models[model_id]
        model = self._loader(instance, model_id)
        with self._lock:
            self._models[model_id] = model
            self._models.move_to_end(model_id)
            while len(self._models) > self._capacity:
                old_id, old = self._models.popitem(last=False)
                del old
        return model

    def loaded(self) -> List[str]:
        with self._lock:
            return list(self._models)


def multiplexed(max_num_models_per_replica: int = 3):
    """``@serve.multiplexed`` — wraps a ``get_model(self, model_id)`` loader
    with a per-replica LRU cache (reference: ``serve/multiplex.py``). The
    cache is created lazily on the instance (decoration-time state would
    make the user class unpicklable — it ships to replicas by value)."""

    def wrap(loader):
        attr = f"__mux_cache_{loader.__name__}"

        def cached(self, model_id: Optional[str] = None):
            cache = getattr(self, attr, None)
            if cache is None:
                cache = _MultiplexCache(loader, max_num_models_per_replica)
                setattr(self, attr, cache)
            if model_id is None:
                model_id = get_multiplexed_model_id()
            return cache.get(self, model_id)

        cached._is_multiplexed = True
        return cached

    return wrap


def loaded_model_ids(instance) -> List[str]:
    """All model ids resident in ``instance``'s multiplex caches."""
    out: List[str] = []
    for name, value in vars(instance).items():
        if name.startswith("__mux_cache_") and isinstance(
                value, _MultiplexCache):
            out.extend(value.loaded())
    return out


class StreamQueue:
    """What one stream's producer has made and its consumer has not taken
    yet. The producer ``put``s items and ``end``s the stream; the
    consumer ``take``s: it blocks until ONE item exists (or the stream
    has ended) and leaves with that item and whatever else is already
    here, never waiting for an item that does not exist yet. ``close``
    is the consumer going away.

    Two producers fill it. A deployment that makes its items on a thread
    of its own returns one from its streaming method and fills it
    directly (``LlamaDecodeDeployment.stream``: the engine's ``on_token``
    is ``put``, its end-of-request hook is ``end``). Any other iterable
    gets one from ``pumping``: a thread, started at the first ``take``,
    runs the iterator and stays at most one delivery ahead of the
    consumer, so a fast producer ships ``max_items`` at a time and a
    slow one ships each item as it appears.

    It iterates (``next`` takes one item, ``close`` as on a generator),
    so code that held a generator still works.

    It also keeps the stream's one RECORD (``docs/OBSERVABILITY.md``,
    "Stream record"): every item is stamped as it is put (one
    ``time.time()``, kept beside the item), a ``take`` adds how long its
    oldest item lay here (``dwell``) and how long it waited with nothing
    here (``blocked``), and a consumer that says when it was done with
    the delivery before (``acked_at`` on its next ``take``) gives that
    delivery's ``put`` -> acknowledged time. Sums and extremes only:
    nothing grows with the stream. ``close`` closes the record, once,
    and hands it to ``on_record``."""

    # A producer that forgets to ``end`` is caught by asking ``backstop``
    # this often; no ending the code knows of waits for it.
    BACKSTOP_S = 0.5

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._items: deque = deque()
        self._ended = False      # producer: nothing more will be put
        self._error: Optional[BaseException] = None
        self._closed = False     # consumer: nothing more will be taken
        self._source: Optional[tuple] = None  # pumping(): not started yet
        self._room = 0           # pumped: items the pump may run ahead
        self.backstop: Optional[Callable[[], None]] = None
        self.on_close: Optional[Callable[[], None]] = None
        # The record (wall clock), whole from the start: ``stamp`` adds
        # the replica's stamps, ``take`` the rest. ``close`` publishes it
        # as ``record`` after ``on_record`` had it, which may add to it
        # (the engine's clocks).
        self._rec: Dict[str, Any] = dict(
            received=None, started=None, first_put=None, last_put=None,
            first_ack=None, last_ack=None, items=0, pulls=0, acked=0,
            dwell_s_sum=0.0, dwell_s_max=0.0, deliver_s_sum=0.0,
            deliver_s_max=0.0, first_deliver_s=None, blocked_s_sum=0.0)
        self._unacked: Optional[float] = None  # oldest put of the
        #   delivery that left last, until its acknowledgement comes
        self.on_record: Optional[Callable[[Dict[str, Any]], None]] = None
        self.record: Optional[Dict[str, Any]] = None  # set by close()

    @classmethod
    def pumping(cls, iterator, model_id: str = "",
                deadline: Optional[float] = None) -> "StreamQueue":
        """A queue that a thread fills from ``iterator`` once the first
        ``take`` says how many items one delivery holds; the iterator's
        body sees ``model_id`` and ``deadline`` as its request's."""
        out = cls()
        out._source = (iterator, model_id, deadline)
        return out

    # ------------------------------------------------------------ producer

    def put(self, item: Any) -> None:
        now = time.time()
        with self._cond:
            self._items.append((item, now))
            self._cond.notify_all()

    def end(self, error: Optional[BaseException] = None) -> None:
        """No further item follows. With ``error`` the consumer is
        raised it once it has taken what was put before. The first call
        wins."""
        with self._cond:
            if not self._ended:
                self._ended, self._error = True, error
                self._cond.notify_all()

    def _pump(self, iterator, model_id: str,
              deadline: Optional[float]) -> None:
        _current_model_id.value = model_id  # the iterator's body runs here
        _current_deadline.value = deadline
        error: Optional[BaseException] = None
        try:
            while True:
                with self._cond:
                    while (len(self._items) >= self._room
                           and not self._closed):
                        self._cond.wait(self.BACKSTOP_S)
                    if self._closed:
                        break
                try:
                    item = next(iterator)
                except StopIteration:
                    break
                self.put(item)
        except BaseException as e:  # noqa: BLE001 — the consumer's to see
            error = e
        finally:
            _close_iterator(iterator)
        self.end(error)

    # ------------------------------------------------------------ consumer

    def take(self, max_items: int = 1, acked_at: Optional[float] = None
             ) -> Tuple[List[Any], bool]:
        """(items, done): block for the first item, then leave with up to
        ``max_items`` of what is here. ``done`` says nothing follows.
        Raises the producer's error once the items before it are taken,
        ``RequestCancelledError`` if the stream is closed meanwhile.
        ``acked_at`` is when the consumer was done with the delivery
        before this one (``time.time()`` on this host)."""
        with self._cond:
            if acked_at is not None and self._unacked is not None:
                self._acknowledge(acked_at)
            if self._source is not None:
                source, self._source = self._source, None
                self._room = max(1, max_items)
                threading.Thread(target=self._pump, args=source,
                                 name="stream-pump", daemon=True).start()
            waited_from = None
            while not (self._items or self._ended or self._closed):
                if waited_from is None:
                    waited_from = time.time()
                if (not self._cond.wait(self.BACKSTOP_S)
                        and self.backstop is not None):
                    self.backstop()
            if self._closed:
                raise RequestCancelledError("stream closed by its consumer")
            stamped = [self._items.popleft()
                       for _ in range(min(max_items, len(self._items)))]
            if self._room:
                self._cond.notify_all()  # room again: wake the pump
            done = self._ended and not self._items
            if done and self._error is not None:
                if not stamped:
                    raise self._error
                done = False  # the next take raises it
            now = time.time()
            rec = self._rec
            if waited_from is not None:
                rec["blocked_s_sum"] += now - waited_from
            rec["pulls"] += 1
            if stamped:
                oldest = stamped[0][1]
                if rec["first_put"] is None:
                    rec["first_put"] = oldest
                rec["last_put"] = stamped[-1][1]
                rec["items"] += len(stamped)
                self._unacked = oldest
                dwell = now - oldest
                rec["dwell_s_sum"] += dwell
                rec["dwell_s_max"] = max(rec["dwell_s_max"], dwell)
            return [item for item, _ in stamped], done

    def _acknowledge(self, acked_at: float) -> None:
        rec = self._rec
        took = acked_at - self._unacked
        self._unacked = None
        rec["acked"] += 1
        rec["deliver_s_sum"] += took
        rec["deliver_s_max"] = max(rec["deliver_s_max"], took)
        if rec["first_ack"] is None:
            rec["first_ack"], rec["first_deliver_s"] = acked_at, took
        rec["last_ack"] = acked_at

    def stamp(self, **stamps: Optional[float]) -> None:
        """Stamps of the record that only the queue's holder knows
        (``received``, ``started``)."""
        self._rec.update(stamps)

    def close(self) -> None:
        """The consumer is gone: wake a blocked ``take``, stop the pump
        (it closes its iterator from its own thread), tell the producer
        through ``on_close``, close the record. Idempotent."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            source, self._source = self._source, None
            self._cond.notify_all()
            # Whole, or none for a stream that no delivery ever left
            # (cancelled before its first item).
            record = self._rec if self._rec["pulls"] else None
        if source is not None:  # never pumped: nothing else will close it
            _close_iterator(source[0])
        if self.on_close is not None:
            self.on_close()
        if record is not None and self.on_record is not None:
            self.on_record(record)
        self.record = record

    def __iter__(self) -> "StreamQueue":
        return self

    def __next__(self) -> Any:
        items, _ = self.take(1)
        if not items:
            raise StopIteration
        return items[0]


def _close_iterator(iterator) -> None:
    """Run a generator's clean-up (engine cancel, slot free)."""
    close = getattr(iterator, "close", None)
    if close is None:
        return
    try:
        close()
    except Exception:
        from ray_tpu.util.ratelimit import log_every

        # A failure here can strand whatever the generator's finally
        # would have released.
        log_every("replica.stream_close", 10.0, logger,
                  "closing stream generator failed", exc_info=True)


class ReplicaActor:
    def __init__(self, cls_blob: bytes, args: tuple, kwargs: dict,
                 replica_id: str = "", owner_epoch: int = 0,
                 role: str = ""):
        from ray_tpu.core import serialization

        # Disaggregated posture ("prefill" / "decode" / "" = colocated):
        # routing-plane metadata, reported back through stats() so
        # serve.status() shows each replica's role. The hosted class is
        # identical either way — role never changes engine behavior.
        self._role = role
        if replica_id:
            # Before the user class runs: its __init__ may build the
            # engine that reads this identity for metric labels.
            _replica_ident["replica_id"] = replica_id
            _replica_ident["deployment"] = replica_id.rsplit("#", 1)[0]
        cls = serialization.loads_function(cls_blob)
        # Where placement ends: a worker holds the lease and the class,
        # and what the class's ``__init__`` does has phases of its own.
        placed = time.time()
        flightrec.record("setup.phase", phase="placement.end", t0=placed,
                         t1=placed, name=_replica_ident["deployment"],
                         cluster=runtime.cluster_address())
        self._instance = cls(*args, **kwargs)
        self._sub_slice: Optional[Dict[str, Any]] = None
        self._ongoing = 0
        self._total = 0
        self._lock = threading.Lock()
        self._streams: Dict[str, StreamQueue] = {}
        self._started = time.monotonic()
        # The controller epoch that owns this replica: assigned at
        # spawn, re-pushed by a restarted controller when it ADOPTS the
        # replica (set_owner_epoch). Exported as the serve_replica_epoch
        # gauge so `ray_tpu doctor` can flag replicas no live controller
        # epoch owns (orphan-replica).
        self._owner_epoch = int(owner_epoch)
        if replica_id:
            from ray_tpu.util import metrics as um

            um.add_collector(self._collect_epoch)

    def _collect_epoch(self) -> None:
        from ray_tpu.serve import metrics as smetrics

        smetrics.REPLICA_EPOCH.set(
            float(self._owner_epoch),
            {"deployment": _replica_ident["deployment"]})

    def set_owner_epoch(self, epoch: int) -> None:
        """Adoption handshake from a restarted controller: monotonic —
        a zombie's stale push can't regress the owning epoch."""
        self._owner_epoch = max(self._owner_epoch, int(epoch))

    def handle_request(self, method: str, args: tuple, kwargs: dict,
                       multiplexed_model_id: str = "",
                       deadline_s: Optional[float] = None):
        with self._lock:
            self._ongoing += 1
            self._total += 1
        _current_model_id.value = multiplexed_model_id
        _current_deadline.value = (time.monotonic() + deadline_s
                                   if deadline_s is not None else None)
        try:
            target = (self._instance if method == "__call__"
                      else getattr(self._instance, method))
            return target(*args, **kwargs)
        finally:
            _current_model_id.value = ""
            _current_deadline.value = None
            with self._lock:
                self._ongoing -= 1

    # ------------------------------------------------- streaming sessions
    #
    # A streaming callable's items reach the consumer INCREMENTALLY: every
    # stream has one ``StreamQueue`` between its producer and the
    # consumer's pulls (``next_chunks``, actor calls). A pull blocks until
    # the queue holds ONE item and returns with it and whatever else the
    # queue holds by then, up to ``max_items``: an item that exists is
    # never held for one that does not yet. So a producer slower than its
    # consumer (a decode engine: one token a step) ships each item alone,
    # and one that is ahead ships ``max_items`` a delivery. The callable
    # either returns its own ``StreamQueue``, filled from its own thread
    # (the decode engine's ``on_token``), or any other iterable, which a
    # pump thread runs at most one delivery ahead of the consumer, so
    # production is still backpressured by the pulls. A stream ends when
    # its producer calls ``end`` (the engine: on every way a request can
    # end), not when a timed wait notices (reference: proxy.py's
    # streaming responses over ASGI receive/send; here the handle is the
    # transport).

    def start_stream(self, method: str, args: tuple, kwargs: dict,
                     multiplexed_model_id: str = "",
                     deadline_s: Optional[float] = None) -> str:
        import uuid

        started = time.time()
        with self._lock:
            self._ongoing += 1
            self._total += 1
        _current_model_id.value = multiplexed_model_id
        deadline = (time.monotonic() + deadline_s
                    if deadline_s is not None else None)
        _current_deadline.value = deadline
        try:
            target = (self._instance if method == "__call__"
                      else getattr(self._instance, method))
            result = target(*args, **kwargs)
            if not isinstance(result, StreamQueue):
                result = StreamQueue.pumping(iter(result),
                                             multiplexed_model_id, deadline)
        except BaseException:
            with self._lock:
                self._ongoing -= 1
            raise
        finally:
            _current_model_id.value = ""
            _current_deadline.value = None
        from ray_tpu.util import tracing

        result.stamp(received=tracing.received(), started=started)
        sid = uuid.uuid4().hex[:16]
        self._streams[sid] = result
        return sid

    def next_chunks(self, stream_id: str, max_items: int = 16,
                    acked_at: Optional[float] = None):
        """One delivery: (items, done). Blocks for the stream's next item
        and returns it with whatever else is ready, at most ``max_items``;
        an error the producer ended with is raised once the items before
        it are delivered. The stream's ongoing slot frees when it is
        done. ``acked_at``: when the caller's consumer was done with the
        delivery before this one (``time.time()``; the router sends it,
        the stream's record keeps it)."""
        stream = self._streams.get(stream_id)
        if stream is None:
            raise KeyError(f"unknown stream {stream_id}")
        try:
            items, done = stream.take(max_items, acked_at)
        except BaseException:
            self.cancel_stream(stream_id)
            raise
        if done:
            self.cancel_stream(stream_id)
        return items, done

    def cancel_stream(self, stream_id: str) -> None:
        stream = self._streams.pop(stream_id, None)
        if stream is None:
            return
        try:
            stream.close()
        except Exception:
            from ray_tpu.util.ratelimit import log_every

            # close() is the producer's clean-up (engine cancel, slot
            # free) — a failure here can strand engine state.
            log_every("replica.stream_close", 10.0, logger,
                      "closing stream failed", exc_info=True)
        with self._lock:
            self._ongoing -= 1
        from ray_tpu.core.config import config as rt_config

        if rt_config.serve_metrics_enabled and stream.record is not None:
            from ray_tpu.serve import metrics as smetrics

            # Once a stream, from its closed record: never once a pull.
            smetrics.observe_stream(
                stream.record, {"deployment": _replica_ident["deployment"]})

    def set_topology(self, assignment: Dict[str, Any]) -> None:
        """Sub-slice assignment from the serve controller (which chips
        of which slice this replica spans). Stored here and forwarded to
        the user instance when it cares (e.g. LlamaDecodeDeployment
        reports it through replica_metrics; a real multi-host replica
        would select jax devices by the assignment's chip coords)."""
        self._sub_slice = dict(assignment)
        fwd = getattr(self._instance, "set_topology", None)
        if callable(fwd):
            fwd(assignment)

    def set_admission(self, queue_max: int) -> bool:
        """Admission-cap override from the serve controller (the
        autopilot shed-tenant action): forwarded to the user instance's
        ``set_admission`` when it implements one, else applied to a
        hosted ``engine``'s ``queue_max`` directly. Returns whether
        anything applied (a deployment with no bounded queue has
        nothing to shed)."""
        fwd = getattr(self._instance, "set_admission", None)
        if callable(fwd):
            fwd(int(queue_max))
            return True
        eng = getattr(self._instance, "engine", None)
        if eng is not None and hasattr(eng, "queue_max"):
            eng.queue_max = max(1, int(queue_max))
            return True
        return False

    def engine_timeline(self) -> Dict[str, Any]:
        """The hosted instance's step-timeline dump (empty for non-engine
        deployments): phase rows + page/compile events, merged by
        ``ray_tpu timeline --serve`` into the cross-process trace."""
        fn = getattr(self._instance, "timeline", None)
        if callable(fn):
            try:
                return dict(fn())
            except Exception:
                from ray_tpu.util.ratelimit import log_every

                log_every("replica.timeline", 30.0, logger,
                          "instance timeline dump failed", exc_info=True)
        return {"rows": []}

    def stats(self) -> Dict[str, Any]:
        models = loaded_model_ids(self._instance)
        # Instance-reported metrics (e.g. a DecodeEngine's backlog as
        # "load" and its prefix-cache residency as "prefixes"): merged in
        # so the controller autoscales on decode backlog — a full decode
        # queue behind idle HTTP concurrency is NOT zero load — and the
        # router can steer shared prefixes to the replica holding them.
        out: Dict[str, Any] = {}
        metrics = getattr(self._instance, "replica_metrics", None)
        if callable(metrics):
            try:
                out = dict(metrics())
            except Exception:
                out = {}
        with self._lock:
            out.update({"ongoing": self._ongoing, "total": self._total,
                        "models": models,
                        "uptime_s": time.monotonic() - self._started})
        if self._role:
            out["role"] = self._role
        return out

    def ping(self) -> str:
        return "pong"
