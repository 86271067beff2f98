"""Continuous-batching decode engine: the replica-side half of LLM serving.

Reference shape: the reference serves generation through its model-agnostic
replica call path + streaming (``serve/_private/replica.py:231``,
``proxy.py:761``) and leaves batching to vLLM-style engines; here the
engine is TPU-native and owns the jitted programs directly:

* ONE decode program per rung of a fixed ladder of view widths, each
  compiled once. Requests join and leave the running batch between decode
  steps (continuous batching) — a joining request's prompt is prefilled
  into the pages of the shared KV pool that its slot's block table maps,
  then the shared ``paged_decode_step`` advances every active slot
  together, over the flat list of the pages those slots hold.
* Static shapes throughout: slot count and cache capacity are fixed at
  engine construction (pick the bucket for your SLO), and the ladder
  follows from them; per-slot ``length`` masking makes ragged occupancy
  exact, so there are NO recompiles at steady state — the serving
  property that matters on TPU.
* Streaming: each emitted token is pushed to the request's callback;
  ``serve``'s streaming HTTP path turns that into chunked responses.

Single-threaded by design: the engine runs inside one replica actor
(``max_concurrency`` keeps request intake concurrent; the decode loop is
the serial consumer), matching how a chip is actually scheduled.
"""

from __future__ import annotations

import functools
import itertools
import logging
import os
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.core.errors import (DeadlineExceededError, OverloadedError,
                                 RequestCancelledError)
from ray_tpu.util import flightrec

logger = logging.getLogger(__name__)

_req_ids = itertools.count(1)


@dataclass(eq=False)  # identity semantics: the generated __eq__ would
#   compare numpy token arrays elementwise (list.remove on the requeue
#   would crash on different-length prompts)
class _Request:
    tokens: np.ndarray                     # prompt ids, (S,)
    max_new_tokens: int
    temperature: float
    eos_id: Optional[int]
    on_token: Optional[Callable[[int], None]]
    done: threading.Event = field(default_factory=threading.Event)
    output: List[int] = field(default_factory=list)
    slot: int = -1
    generated: int = 0
    error: Optional[str] = None
    on_token_error: Optional[str] = None   # first on_token callback failure
    on_done: Optional[Callable[["_Request"], None]] = None  # told the
    #   moment the request ends, whichever way (and when on_token fails):
    #   a stream wakes on it instead of polling ``done``
    submitted_at: float = field(default_factory=time.monotonic)
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    prefix_len: int = 0                    # cached tokens NOT re-prefilled
    prefix_pages: List[int] = field(default_factory=list)  # spliced pages
    prompt_len: int = 0       # ORIGINAL prompt length (tokens grows when
    #   a preempted request re-queues with its emitted tokens absorbed)
    prefilled: int = 0        # prompt tokens prefilled so far (chunked)
    # ------------------------------------------- disaggregated handoff
    prefill_only: bool = False  # terminal = filled pages, not tokens: the
    #   request ends at first-token with its KV pages gathered to host
    #   as the handoff payload instead of entering the decode loop
    handoff: Optional[Dict[str, Any]] = None  # prefill_only result: k/v
    #   page payloads + committed_len + first_token + page geometry
    adopt: Optional[Dict[str, Any]] = None    # decode-side twin: payload
    #   to scatter into this engine's pool at admission (zero recompute)
    # --------------------------------------------------- request lifecycle
    request_id: str = ""
    deadline: Optional[float] = None       # absolute monotonic; None = none
    cancelled: bool = False                # cooperative-cancel flag
    admitted: bool = False                 # left the pending queue
    status: str = "pending"                # terminal: completed |
    #   cancelled | deadline_exceeded | error
    # ------------------------------------------------------ observability
    trace: Optional[tuple] = None          # (trace_id, span_id) captured
    #   at submit: the engine's loop thread attributes queue-wait /
    #   prefill / decode spans back to the submitting request's trace
    admitted_at: Optional[float] = None    # first prefill dispatch
    preemptions: int = 0                   # times requeued by page pressure

    def terminal_error(self) -> Optional[BaseException]:
        """This request's terminal outcome as its typed error (None for
        a request that completed, or has not ended)."""
        if self.status == "cancelled":
            return RequestCancelledError(
                f"request {self.request_id} cancelled after "
                f"{self.generated} tokens")
        if self.status == "deadline_exceeded":
            return DeadlineExceededError(
                f"request {self.request_id} exceeded its deadline after "
                f"{self.generated} tokens")
        if self.error:
            return RuntimeError(self.error)
        return None

    def raise_for_status(self) -> None:
        """Re-raise this request's terminal outcome as its typed error."""
        err = self.terminal_error()
        if err is not None:
            raise err


def _pool_of(cache: Dict[str, Any]) -> Dict[str, Any]:
    """The model's pool out of the engine's cache: every leaf but the
    slots' cursors."""
    return {name: leaf for name, leaf in cache.items()
            if name != "length"}


class DecodeEngine:
    """Continuous batcher over one model's paged programs.

    ``model`` is the module (or object) the programs come from;
    ``ray_tpu.models.llama_decode`` unless one is given. It provides
    ``compute_weights``, ``init_page_pool``, ``paged_prefill``,
    ``paged_prefill_suffix``, ``paged_decode_step``, ``live_page_view``,
    ``cache_bucket`` and ``sample_batch``, and may provide
    ``shard_decode_state``: a mesh is refused at construction for a
    model that lacks it. The pool is the model's pytree, carried whole
    (docs/SERVING.md, "The model seam"). A model may also say
    ``page_kinds(config)``: which kinds of page its pool has and what
    each keeps (all tokens, or the last ``window``); the engine then
    keeps an allocator and a block table a kind (``_windows``), and such
    a model runs without the prefix index. And it may say
    ``slot_state(config)``: the pool's leaves that are indexed by SLOT
    (``[layers, slots + 1, ...]``, the last row scratch) and not by page,
    a recurrent layer's state; its prefill programs are then told each
    row's slot and whether its prompt ends there (``_prefill_tables``).
    A model whose ``page_kinds`` names NO kind keeps slot state alone: the
    engine then has no allocator, no block table and no view, its decode
    is one program that is told which slots step, and the slots alone
    bind admission (docs/SERVING.md, "A model with no page kind").

    ``slots`` concurrent sequences of up to ``capacity`` tokens share
    one paged KV pool. ``step()`` advances every active slot one token;
    ``submit()`` enqueues a request (prefilled into a free slot at the
    next step boundary). Run ``serve_forever`` in a thread inside a
    replica, or drive ``step()`` manually in tests."""

    # The name of the one page kind of a model that names none.
    DEFAULT_KIND = "full"

    def __init__(self, params, config, slots: int = 4,
                 capacity: int = 1024, prefill_bucket: int = 128,
                 prefix_pool_entries: Optional[int] = None,
                 prefix_match_min_tokens: Optional[int] = None,
                 queue_max: Optional[int] = None,
                 page_tokens: Optional[int] = None,
                 pool_pages: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 prefix_max_pages: Optional[int] = None,
                 mesh_shape=None, mesh=None,
                 step_timeline: Optional[int] = None,
                 metrics_enabled: Optional[bool] = None,
                 trace_spans: Optional[bool] = None,
                 metrics_deployment: Optional[str] = None,
                 model=None):
        t_entry = time.time()
        import jax

        from ray_tpu.core.config import config as rt_config
        from ray_tpu.serve.paging import (PageAllocator, PagedPrefixIndex,
                                          WindowPages)

        if model is None:
            from ray_tpu.models import llama_decode as model
        self._jax = jax
        self._ld = ld = model
        # A model may lack the one optional program; a mesh, which needs
        # it, is refused here, before anything is built for it.
        if (mesh is not None or mesh_shape is not None
                or rt_config.decode_mesh_shape) and not hasattr(
                    ld, "shard_decode_state"):
            raise ValueError(
                f"model {ld.__name__} has no shard_decode_state: this "
                f"engine cannot run it on a mesh")
        # Counters a model's decode step returns beside its logits
        # (a float32 vector of whole numbers, one entry a name); they
        # reach the host behind the token ids, in the same transfer.
        self._step_stats = tuple(getattr(ld, "STEP_STATS", ()))
        # The one tree every program reads, held in the compute dtype:
        # rounded once here, not in every decode step and prefill chunk.
        # The caller's arrays are left alone (a test's reference reads
        # them afterwards); whoever owns the masters frees them.
        self.params = params = ld.compute_weights(params, config)
        self.config = config
        self.slots = slots
        self.capacity = capacity
        self.prefill_bucket = prefill_bucket
        # ------------------------------------------- GSPMD serving mesh
        # mesh/mesh_shape turns the engine model-parallel: one replica
        # spans every device of a (batch, model) decode_mesh. Weights,
        # KV state and activations carry NamedShardings; the jitted
        # programs below get out_shardings and trace under the decode
        # axis rules (parallel.sharding.DECODE_RULES) — XLA inserts all
        # collectives, and no contraction dim is ever partitioned.
        if mesh is None:
            ms = mesh_shape
            if ms is None and rt_config.decode_mesh_shape:
                from ray_tpu.core.topology import parse_topology

                ms = parse_topology(rt_config.decode_mesh_shape)
            if ms is not None:
                from ray_tpu.parallel.mesh import decode_mesh

                mesh = decode_mesh(tuple(ms))
        self.mesh = mesh
        if mesh is not None:
            batch_ax = mesh.shape.get("batch", 1)
            if slots % batch_ax:
                raise ValueError(
                    f"slots ({slots}) must be a multiple of the mesh "
                    f"batch axis ({batch_ax}) — per-slot activations "
                    f"shard over it")
            self.params, self._shardings = ld.shard_decode_state(
                params, config, mesh)
            self._rules = self._shardings["rules"]
        else:
            self._shardings = None
            self._rules = None
        # -------------------------------------------------- paged KV pool
        # K/V for all slots live in one device pool of fixed-size pages
        # addressed through per-slot block tables: slots hold only the
        # pages their sequence covers, prefix hits splice page ids with
        # zero copies, and the pool may be overcommitted (more slots
        # than whole rows fit).
        pt = (rt_config.kv_page_tokens if page_tokens is None
              else page_tokens)
        self.page_tokens = int(pt)
        if self.page_tokens <= 0:
            raise ValueError(
                f"kv_page_tokens must be positive, got {self.page_tokens}")
        if capacity % self.page_tokens:
            raise ValueError(
                f"capacity ({capacity}) must be a multiple of "
                f"kv_page_tokens ({self.page_tokens})")
        # Chunked-prefill interleaving rides on the suffix program (a
        # chunk IS a suffix prefill from pos=prefilled).
        chunk_tok = (rt_config.prefill_chunk_tokens
                     if prefill_chunk_tokens is None
                     else prefill_chunk_tokens)
        self.prefill_chunk_tokens = int(chunk_tok)
        if self.prefill_chunk_tokens:
            c = 1
            while c * 2 <= self.prefill_chunk_tokens:
                c *= 2
            self.prefill_chunk_tokens = c  # pow2: bounds the bucket set
        self.slot_pages_max = capacity // self.page_tokens
        pp = (rt_config.kv_pool_pages if pool_pages is None
              else pool_pages)
        self.pool_pages = int(pp) or slots * self.slot_pages_max
        # Page kinds. A model that names none has ONE, which keeps every
        # token (``_pages``, ``_block_tables``, ``_slot_pages``: all of
        # the engine before kinds). A model whose ``page_kinds`` names
        # more has that one first and, behind it, kinds that keep a
        # WINDOW of tokens (sliding-window layers): each with an
        # allocator and a table a slot of its own (``WindowPages``),
        # asked for pages wherever the first kind is and handed its dead
        # pages back at the step that passes them (``_trim_windows``).
        # A window kind's pool holds what the slots keep between steps
        # and the prefills in flight, which are written through pages: a
        # chunk's, or an admission wave's whole prompts.
        kinds = (ld.page_kinds(config) if hasattr(ld, "page_kinds")
                 else {self.DEFAULT_KIND: {"window": None, "leaves": None}})
        # NO kind: every leaf of the model's pool is slot state,
        # ``[layers, slots + 1, ...]`` whatever a sequence's length, so
        # there is nothing to page. ``_kind`` is None, there is no
        # allocator, no block table and no view, one rung of no rows (ONE
        # decode program, told which slots step), and every later mention
        # of a page is behind ``_kind``: a free slot seats a request whose
        # prompt and answer fit ``capacity``, and nothing is ever
        # preempted for memory. (``page_tokens`` stays the engine's
        # argument and sizes nothing.)
        self._kind, *others = kinds or (None,)
        if self._kind is None:
            self.pool_pages = self.slot_pages_max = 0
            self._pages = self._block_tables = self._slot_pages = None
        elif kinds[self._kind]["window"] is not None or any(
                kinds[k]["window"] is None for k in others):
            raise ValueError(
                f"model {ld.__name__}: the first page kind keeps every "
                f"token and the others a window, got {kinds}")
        else:
            self._pages = PageAllocator(self.pool_pages)
            self._block_tables = np.zeros(
                (slots, self.slot_pages_max), np.int32)
            self._slot_pages: List[List[int]] = [[] for _ in range(slots)]
        self._windows: Dict[str, WindowPages] = {}
        for name in others:
            keep = -(-int(kinds[name]["window"]) // self.page_tokens) + 1
            in_flight = (self._seq_pages(self.prefill_chunk_tokens)
                         if self.prefill_chunk_tokens
                         else self.slot_pages_max)
            pages = min(slots * self.slot_pages_max,
                        slots * keep + max(4, slots // 4) * in_flight)
            self._windows[name] = WindowPages(
                pages, slots, self.slot_pages_max, self.page_tokens,
                kinds[name]["window"])
        # Slot state. A model may keep leaves of its pool a SLOT and not a
        # page (``slot_state``: a recurrent layer's state, ``[layers,
        # slots + 1, ...]``). The engine owns no program for them: a row
        # that starts at position 0 starts from zero in the model's own
        # prefill (so seating, and a preemption's recompute, reset it), a
        # chunk reads what the last chunk left, and a decode step leaves
        # the state of a slot outside its view as it is. What the engine
        # owes is each prefill row's slot, the scratch row ``slots`` for a
        # pad row (``_prefill_tables``).
        self._state_leaves = (tuple(ld.slot_state(config))
                              if hasattr(ld, "slot_state") else ())
        if self._kind is None and not self._state_leaves:
            raise ValueError(
                f"model {ld.__name__} names no page kind and no slot "
                f"state: it would cache nothing")
        # The pool is the model's own pytree (leaves ``[layers,
        # pages + 1, page_tokens, ...]``: K and V per head for llama,
        # one latent row a token for deepseek, K and V a kind for mimo);
        # the engine carries it whole beside the slots' cursors and
        # never looks inside.
        pool = ld.init_page_pool(
            config, {} if self._kind is None
            else {self._kind: self.pool_pages,
                  **{k: w.alloc.pages for k, w in self._windows.items()}}
            if self._windows else self.pool_pages, self.page_tokens,
            **({"slots": slots} if self._state_leaves else {}))
        # Bytes of state one slot holds (the step log's ``state_bytes``
        # counts the seated slots').
        self._slot_state_bytes = sum(
            pool[name].nbytes // pool[name].shape[1]
            for name in self._state_leaves)
        self._pool_names = tuple(pool)
        # Which kind's page ids index a leaf (the handoff's leaf map).
        self._leaf_kind = {
            leaf: next((k for k, d in kinds.items()
                        if d["leaves"] and leaf in d["leaves"]), self._kind)
            for leaf in pool}
        self.cache = {**pool,
                      "length": jax.numpy.zeros((slots,),
                                                jax.numpy.int32)}
        # The widths a decode step's view of the pool may take: the step
        # reads the pages its slots hold, padded up to the next rung, and
        # each rung is one compiled program. Powers of two from 64 rows
        # to every page of every slot, so a step reads at most twice
        # what it needs and the set stays small (six at 32 x 64).
        top = slots * self.slot_pages_max
        rungs = [64]
        while rungs[-1] * 2 < top:
            rungs.append(rungs[-1] * 2)
        # (With no page kind: one rung of no rows, one decode program.)
        self._view_ladder = tuple(n for n in rungs if n < top) + (top,)
        if self.mesh is not None:
            # Commit the KV state onto the mesh: the shared page pool
            # shards its kv-head dim over "model" (HBM-per-chip drops
            # with the model axis). ``length`` stays replicated (bytes,
            # host-read every step).
            self._cache_sharding = dict(self._shardings["pool"])
            self.cache = jax.device_put(self.cache, self._cache_sharding)
        else:
            self._cache_sharding = None
        # Devices this engine's programs run on (what stats() reports):
        # the mesh's, or the process default device.
        self._devices = (list(mesh.devices.flat) if mesh is not None
                         else jax.devices()[:1])
        from ray_tpu.util.compile_cache import compile_watch

        self._compile_watch = compile_watch()
        self._free = list(range(slots))
        self._active: Dict[int, _Request] = {}
        self._prefilling: Dict[int, _Request] = {}  # chunked, mid-prefill
        self._requeue: List[_Request] = []  # preempted/pushed-back, FIFO
        self._pending: "queue.Queue[_Request]" = queue.Queue()
        # What the one-token decode samples from, a slot each: the token
        # it reads and the temperature it draws at (0 = greedy). The
        # host's copies; the device's are ``_state_dev``, ``_temps_dev``.
        self._tokens = np.zeros((slots,), np.int32)
        self._temps = np.zeros((slots,), np.float32)
        self._stop = threading.Event()
        self._loop_thread: Optional[threading.Thread] = None  # serve_forever
        self._work = threading.Event()
        # ------------------------------------------- request lifecycle
        # Bounded admission: past queue_max pending requests, submit()
        # sheds with OverloadedError at enqueue (<1 ms) instead of
        # queueing into minutes of latency under overload.
        if queue_max is None:
            queue_max = rt_config.decode_queue_max
        self.queue_max = int(queue_max) if queue_max else slots * 8
        # The configured cap, kept so a runtime shed override
        # (set_admission) can be lifted back to it.
        self._default_queue_max = self.queue_max
        # request_id -> live request, for cancel(); guarded by _reqs_lock
        # (intake/cancel threads vs the decode loop).
        self._requests: Dict[str, _Request] = {}
        self._reqs_lock = threading.Lock()
        self._queued_cancelled = 0  # cancelled but not yet dequeued
        self._queued_tokens = 0     # prompt tokens waiting for prefill
        #   (pending queue + requeue; advisory gauge, unlocked int ops)
        self.shed = 0               # requests rejected by the queue cap
        self.cancelled = 0          # requests ended by cancel()
        self.deadline_exceeded = 0  # requests ended by their deadline
        self.preempted = 0          # requests requeued by page pressure
        self.prefill_chunks = 0     # chunked-prefill programs dispatched
        self.prefill_chunks_ahead = 0  # ... of them behind a decode
        #   whose ids were yet to be fetched (``_prefill_ahead``)
        self._ema_request_s = 0.0   # EMA of admitted-request service time
        self._last_purge = 0.0      # dead-entry queue-purge throttle
        # Prefix KV cache: the index pins PAGE RANGES of the shared pool
        # (PagedPrefixIndex) — inserts and splices are zero-copy
        # block-table edits. ``prefix_pool_entries`` 0 turns it off.
        entries = (rt_config.prefix_pool_entries
                   if prefix_pool_entries is None else prefix_pool_entries)
        min_tokens = (rt_config.prefix_match_min_tokens
                      if prefix_match_min_tokens is None
                      else prefix_match_min_tokens)
        self.prefix = None
        if entries > 0 and (self._windows or self._state_leaves):
            # A shared boundary would need the full pages up to it AND
            # the window pages that hold the ``window`` tokens before it,
            # which the index does not pin (and the state at it, which
            # nothing keeps): a model with a window kind or slot state
            # runs without it (docs/SERVING.md, "Page kinds").
            logger.info(
                "model %s has page kinds %s and slot state %s: this engine "
                "runs without the prefix index", ld.__name__, list(kinds),
                list(self._state_leaves))
        elif entries > 0:
            pmax = (rt_config.kv_prefix_max_pages
                    if prefix_max_pages is None else prefix_max_pages)
            self.prefix = PagedPrefixIndex(
                self._pages, self.page_tokens,
                max_pages=int(pmax) or max(1, self.pool_pages // 4),
                min_tokens=min_tokens)
        # A program that ends in a sample returns the sample: token ids
        # cross to the host, never (slots, vocab) logits. The decode's
        # whole result is one int32 vector, ``[ids | the model's
        # counters | the step counter]``, and it is the next decode's
        # input as it lies on the device: a step that follows a step
        # uploads its view and nothing else. ANY host write to
        # ``_tokens`` drops ``_state_dev`` (the next step uploads the
        # host's tokens and ``steps``); the temperatures go up again
        # only when a slot's changed.
        self._state_dev = None
        self._temps_dev = None
        self._live_pages = 0     # of the last view built (``_live_view``)
        self._rung = 0           # and the rung it was built on
        # Suffix prefills bucket on a finer grid than full prefills: the
        # whole point is that the suffix is short, so padding it back up
        # to prefill_bucket would refund most of the win.
        self._suffix_bucket_min = max(8, min(16, prefill_bucket))
        # Params are ARGUMENTS (not closure captures), or jit would bake
        # the weights into the program as constants. Donating the cache
        # hands a program the pool's own buffer; that it WRITES there is
        # the forwards' doing: they carry the pool through their layer
        # loop and scatter the new rows into it
        # (``llama_decode._scan_layers``), so no program holds or copies
        # a second pool. Its shape is the same outside every program.
        # Mesh engines pin program outputs to the committed shardings
        # (token outputs replicated for the host to read, KV
        # state staying exactly where device_put placed it, so
        # donation reuses the sharded buffers); single-chip engines
        # pass no shardings at all — their jaxprs are byte-identical
        # to pre-mesh builds.
        if self.mesh is not None:
            rep = self._shardings["replicated"]
            cache_out = {"out_shardings": (rep, self._cache_sharding)}
        else:
            cache_out = {}
        # One program per (n, bucket) power-of-two pair: admission
        # scatters K/V into pool pages through the wave's block tables,
        # the suffix program doubles as the chunked-prefill
        # continuation, and decode gathers the flat list of the pages
        # its slots hold (``_live_view``: one program a rung of
        # ``_view_ladder``). ``width`` (suffix) = static leading
        # block-table columns the wave touches — cost scales with
        # prefix+suffix, not max context.
        self._paged_prefill = self._mesh_scoped(self._program(
            "paged_prefill", self._paged_prefill_impl,
            static_argnames=("n", "bucket"),
            donate_argnums=(1,), **cache_out))
        self._paged_suffix = self._mesh_scoped(self._program(
            "paged_suffix", self._paged_suffix_impl,
            static_argnames=("n", "bucket", "width"),
            donate_argnums=(1,), **cache_out))
        self._decode = self._mesh_scoped(self._program(
            "decode", self._paged_decode_impl, donate_argnums=(1,),
            **cache_out))
        # Disaggregated adopt: scatter handed-off page payloads into
        # the pool (pure data movement, no model math) and park the
        # slot cursor at the committed length. Cache-only output, so
        # mesh engines pin just the cache sharding.
        self._adopt_pages = self._mesh_scoped(self._program(
            "adopt_pages", self._adopt_pages_impl,
            static_argnames=("width",),
            donate_argnums=(0,),
            **({"out_shardings": self._cache_sharding}
               if self.mesh is not None else {})))
        self.steps = 0
        self.tokens_out = 0
        # ---------------------------------------------- observability
        # SLO metrics + trace spans are per-REQUEST (terminal outcomes,
        # admission, per-wave prefills) and the step recorder is about
        # ten slices and one deque append per STEP — nothing here
        # touches the per-token path. Measured on a TPU v5e (PERF.md,
        # PR 24, InternLM2-1.8B at 14.5 active slots): the median
        # decode step is 81.14 ms with the slices against 81.20 ms
        # without them, no difference; the slices themselves cost
        # under 50 us a step (tests/test_step_slices.py).
        from ray_tpu.serve.replica import replica_ident
        from ray_tpu.serve.steplog import StepTimeline

        self._obs_metrics = (rt_config.serve_metrics_enabled
                             if metrics_enabled is None else metrics_enabled)
        self._obs_spans = (rt_config.serve_trace_spans
                           if trace_spans is None else trace_spans)
        ident = replica_ident()
        self._mtags = {"deployment": (metrics_deployment
                                      or ident["deployment"] or "-")}
        self._replica_id = ident["replica_id"]
        self.steplog = StepTimeline(
            rt_config.decode_step_timeline
            if step_timeline is None else step_timeline)
        # Step-log events from other threads (a stream's record, closed
        # on a pull's thread): the log is this loop's alone, so they
        # wait here for the next step's ``reap``.
        self._inbox: deque = deque()
        self._compiled: set = set()  # program keys dispatched once
        self._prefill_waves = 0      # prefill programs dispatched
        # The chunk dispatched behind the last decode (``_prefill_ahead``)
        # until the next step's tick has met it: ``(slot, ids)``, the ids
        # a device array where the chunk ends its prompt, else None.
        self._ahead: Optional[Tuple[int, Any]] = None
        # Disaggregated handoff accounting (engine side; the per-replica
        # lease ledger lives on the deployment wrapper).
        self.handoffs_published = 0  # prefill_only captures completed
        self.handoffs_adopted = 0    # adopted seats completed
        self._handoff_phases: List[Dict[str, Any]] = []  # pending steplog
        #   phase rows, drained into the next _steplog_row
        flightrec.record(
            "setup.phase", phase="engine_build", t0=t_entry, t1=time.time(),
            slots=slots, pool_bytes=sum(
                x.nbytes for x in jax.tree.leaves(self.cache)))

    @staticmethod
    def _program(name: str, impl, **jit_kwargs):
        """``jax.jit(impl)`` under the stable name ``engine_<name>``,
        ``name`` being the first element of the key the program is
        dispatched under (``_dispatch_fresh``): the name a profiler
        trace's per-program line and the lowered module carry, where
        the method's own name (``_paged_decode_impl``) would change
        with the next refactor. A name is metadata: the program and
        its outputs are what they were."""
        import jax

        @functools.wraps(impl)
        def program(*args, **kwargs):
            return impl(*args, **kwargs)

        program.__name__ = program.__qualname__ = f"engine_{name}"
        return jax.jit(program, **jit_kwargs)

    def _mesh_scoped(self, fn):
        """Mesh engines trace every program inside the decode axis-rules
        context (``constrain`` sites in the model resolve against it);
        single-chip engines get the callable back untouched."""
        if self.mesh is None:
            return fn
        from ray_tpu.parallel.sharding import axis_rules

        def scoped(*args, **kwargs):
            with axis_rules(self.mesh, self._rules):
                return fn(*args, **kwargs)

        return scoped

    # ------------------------------------------------------ jitted bodies

    def _sample(self, logits, temps, stream: int, counter):
        """The ids a program returns in place of its ``logits``
        (``sample_batch``: argmax, or a draw where a row has a
        temperature). The engine's randomness is two counter-based
        streams, one a kind of program: ``stream`` 0 the decode steps
        (``counter`` = ``steps``), 1 the prefills (``counter`` = the
        prefill programs dispatched so far). The keys are XLA's own
        bit generator's (``unsafe_rbg``): one operation in each of the
        engine's ~35 programs where threefry is some hundred traced
        and compiled in each, which a replica's start pays for."""
        import jax

        key = jax.random.fold_in(
            jax.random.key(stream, impl="unsafe_rbg"), counter)
        return self._ld.sample_batch(logits, temps, key)

    def _paged_prefill_impl(self, params, cache, tokens_rows, lengths,
                            bt, slot_ids, temps, wave, n, bucket):
        """Batched paged admission: causal prefill of ``n`` prompts in
        ONE device call, K/V scattered into the pool pages ``bt`` maps
        (one program per (n, bucket) power-of-two pair). Returns each
        prompt's first token, sampled at ``temps``."""
        ld = self._ld
        pool = _pool_of(cache)
        logits, pool = ld.paged_prefill(params, tokens_rows[:, :bucket],
                                        pool, bt, self.config,
                                        lengths=lengths)
        return self._sample(logits, temps, 1, wave), {
            **pool, "length": cache["length"].at[slot_ids].set(lengths)}

    def _paged_suffix_impl(self, params, cache, tokens_rows, prefix_lens,
                           lengths, bt, slot_ids, temps, wave, n, bucket,
                           width):
        """Suffix prefill over paged context: the prefix-hit splice
        (shared pages arrive through ``bt`` — the block table IS the
        splice, no copies) and the chunked-prefill continuation step.
        ``bt`` is pre-sliced to ``width`` leading page columns so
        gather/attention cost scales with prefix + suffix. Returns the
        token after each row's last, sampled at ``temps``; nobody
        fetches it from a chunk that is not its prompt's last."""
        ld = self._ld
        pool = _pool_of(cache)
        logits, pool = ld.paged_prefill_suffix(
            params, tokens_rows[:, :bucket], pool, bt, self.config,
            prefix_lens, lengths)
        return self._sample(logits, temps, 1, wave), {
            **pool, "length": cache["length"].at[slot_ids].set(lengths)}

    def _paged_decode_impl(self, params, cache, state, view, temps):
        """THE decode step. ``state`` is the int32 vector ``[a token a
        slot | the model's ``STEP_STATS`` | the step counter]``; the
        result is the same vector one step on: the ids sampled at
        ``temps`` on the counter's key, this step's counters, the
        counter plus one. All the host fetches of a step, and the next
        step's input where it lies."""
        import jax.numpy as jnp

        pool = _pool_of(cache)
        logits, pool, lens, *stats = self._ld.paged_decode_step(
            params, pool, view, cache["length"], state[:self.slots],
            self.config)
        toks = self._sample(logits, temps, 0, state[-1])
        return jnp.concatenate(
            [toks, *(s.astype(jnp.int32) for s in stats),
             state[-1:] + 1]), {**pool, "length": lens}

    def _adopt_pages_impl(self, cache, payload, ids, slot_ids,
                          lengths, width):
        """Adopt a handed-off prefill: scatter ``width`` page payloads
        into the pool at ``ids`` and park the slot cursor at the
        committed length. Pure data movement — no model math — so the
        adopted state is bit-identical to having prefilled locally.
        Pad columns target the scratch page (id 0, never read) with
        zero payloads; ``width`` is the pow-2 compile bucket."""
        return {
            **{name: cache[name].at[:, ids[self._leaf_kind[name]] if
                                    isinstance(ids, dict) else ids].set(pages)
               for name, pages in payload.items()},
            "length": cache["length"].at[slot_ids].set(lengths),
        }

    def _dispatch_fresh(self, key: tuple, call,
                        then: Optional[str] = None, **attrs):
        """Dispatch one of the engine's donated programs, marking the
        FIRST dispatch of each program key as a compile in the step
        log. The compile goes through JAX's persistent cache like any
        other program: jax/jaxlib 0.9.0 reload donated executables
        correctly (compile, exit, reload in a new process, identical
        results — checked on the CPU backend and on a TPU v5e).

        With the step log on, the dispatch is the step's ``launch``
        slice (the upload of its arguments until the call returns),
        tagged ``program=key[0]`` — or the ROLE the caller names, where
        one program serves two (``prefill_chunk``) — and ``attrs``. A
        dispatch whose output nobody fetches names in ``then`` the
        slice the step goes on with."""
        # ``call()`` is made from THIS frame, first dispatch or not: the
        # Python stack under a program is part of its lowered text's
        # locations, so a frame between here and the program gives every
        # program another compile-cache key and a slower lowering (mixed:
        # +0.46 s a prefill key on a v5e, PERF.md section 6, PR 55).
        fresh = key not in self._compiled
        if fresh:
            self._compiled.add(key)
            before, t0 = self._compile_watch.snapshot(), time.time()
        if not self.steplog.enabled:
            out = call()
            if fresh:
                self._first_dispatched(key, t0, before)
            return out
        attrs.setdefault("program", key[0])
        if "view_pages" in attrs:
            # Of the rung's rows those that hold a page of a stepping
            # slot; the others are padding a program need not read.
            attrs["live_pages"] = self._live_pages
        if self._state_leaves and "view_pages" in attrs:
            # The slots whose state this decode advances.
            attrs["state_slots"] = len(self._active)
        if self._windows and "view_pages" in attrs:
            # A decode over page kinds: what the window kinds' lists hold
            # beside the first kind's, their length in pages and the
            # tokens in the stepping slots' windows.
            attrs["window_pages"] = sum(
                self.slots * w.keep for w in self._windows.values())
            attrs["window_tokens"] = sum(
                min(r.prompt_len + r.generated, w.window)
                for r in self._active.values()
                for w in self._windows.values())
        self.steplog.begin("launch", **attrs)
        out = call()
        if fresh:
            self._first_dispatched(key, t0, before)
        if then is not None:
            self.steplog.begin(then)
        return out

    def _prefill_attrs(self, cross_rows: int, **more: Any) -> Dict[str, Any]:
        """What a prefill's ``launch`` says beside its tokens for a model
        with slot state, whose prefill runs the layers behind its cache
        for the rows that END their prompt only: ``cross_rows``, how many
        those are (0 for a chunk that ends none)."""
        if not self._state_leaves:
            return {}
        return {"cross_rows": cross_rows, **more}

    def _slice(self, name: str, **attrs: Any) -> None:
        """The step goes on in slice ``name`` (``serve/steplog.py``).
        Round a blocking ``np.array(...)`` it is ``fetch`` before — the
        host waits for the device there — and the next slice after."""
        if self.steplog.enabled:
            self.steplog.begin(name, **attrs)

    # --------------------------------------------- paged page accounting

    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """``n`` pool pages, reclaiming prefix-index pins under pressure.
        None = genuinely dry (caller preempts or backs off)."""
        if self._pages.free_count < n and self.prefix is not None:
            self.prefix.reclaim(n - self._pages.free_count)
        got = self._pages.alloc(n)
        if got is None:
            return None
        try:
            if self.steplog.enabled:
                self.steplog.event("page-alloc", n=n, page_kind=self._kind,
                                   free=self._pages.free_count)
        except BaseException:
            # Exception-safety for the lease: an event-recording
            # failure must hand the pages back, not strand them.
            self._pages.free(got)
            raise
        return got

    def _set_slot_pages(self, slot: int, pages: List[int]) -> None:
        self._block_tables[slot, :] = 0
        self._block_tables[slot, :len(pages)] = pages
        self._slot_pages[slot] = pages

    def _grow_slot(self, slot: int, pages: List[int]) -> None:
        have = self._slot_pages[slot]
        self._block_tables[slot, len(have):len(have) + len(pages)] = pages
        self._slot_pages[slot] = have + pages

    def _seq_pages(self, tokens: int) -> int:
        return -(-tokens // self.page_tokens)

    # ------------------------------------------------- window page kinds

    def _windows_missing(self, slot: int, tokens: int) -> bool:
        """Whether some window kind lacks the free pages to cover the
        slot's first ``tokens`` positions."""
        return any(w.missing(slot, tokens) > w.alloc.free_count
                   for w in self._windows.values())

    def _grow_windows(self, slot: int, tokens: int) -> None:
        """Every window kind covers the slot's positions below
        ``tokens`` (the caller has checked ``_windows_missing``)."""
        for kind, w in self._windows.items():
            n = w.grow(slot, tokens)
            if n and self.steplog.enabled:
                self.steplog.event("page-alloc", n=n, page_kind=kind,
                                   free=w.alloc.free_count)

    def _trim_windows(self, slot: int, position: int) -> None:
        """The slot's next query is at ``position``: its window pages
        wholly behind that query's window go back to their allocator."""
        for kind, w in self._windows.items():
            n = w.trim(slot, position)
            if n and self.steplog.enabled:
                self.steplog.event("page-free", n=n, page_kind=kind,
                                   free=w.alloc.free_count)

    def _prefill_tables(self, slots: List[int], positions: List[int],
                        bt: Optional[np.ndarray], bucket: int,
                        ends: Optional[List[bool]] = None,
                        n: Optional[int] = None):
        """The block tables a prefill program takes: ``bt`` itself for a
        model of one kind and no slot state; else a dict, the first
        kind's ``bt`` under its name and, a window kind, the columns
        that cover ``bucket`` tokens from each row's position and its
        window before it, with the index of their first page
        (``<kind>_first``); for a model with slot state also ``slots``,
        each row's slot, and ``ends``, whether the row's prompt ends in
        this program (``None``: every row's does); for a model with no
        page kind ``slots`` and ``ends`` alone (``bt`` is None). ``n`` is
        the program's rows where no ``bt`` says it: those past ``slots``
        are pad rows. A pad row
        repeats the last row's pages of every kind, which it writes with
        the same values, and names the scratch row ``self.slots`` of the
        state, never a real slot."""
        import jax.numpy as jnp

        if not self._windows and not self._state_leaves:
            return jnp.asarray(bt)
        out = {} if bt is None else {self._kind: jnp.asarray(bt)}
        pads = (len(bt) if n is None else n) - len(slots)
        for kind, w in self._windows.items():
            width = -(-(bucket + w.window) // self.page_tokens) + 1
            cols = [w.columns(s, p, width)
                    for s, p in zip(slots, positions)]
            cols += cols[-1:] * pads
            out[kind] = jnp.asarray(np.stack([c for c, _ in cols]))
            out[f"{kind}_first"] = jnp.asarray(
                np.asarray([f for _, f in cols], np.int32))
        if self._state_leaves:
            out["slots"] = jnp.asarray(
                np.asarray(list(slots) + [self.slots] * pads, np.int32))
            ends = [True] * len(slots) if ends is None else list(ends)
            out["ends"] = jnp.asarray(
                np.asarray(ends + ends[-1:] * pads, bool))
        return out

    def _live_view(self, tables: np.ndarray,
                   slot_pages: List[List[int]]) -> np.ndarray:
        """The view the decode about to be dispatched reads
        (``llama_decode.live_page_view``): the pages ``slot_pages`` of
        the ACTIVE slots, on the smallest rung of the ladder that holds
        them. Count after ``_ensure_decode_pages``: the pages must cover
        every token the program writes. Idle and mid-prefill slots own
        no row of it. A model with no page kind is given, in a view's
        place, which slots step, (slots,) bool: the others keep their
        state bit for bit."""
        if self._kind is None:
            steps = np.zeros((self.slots,), bool)
            steps[list(self._active)] = True
            return steps
        counts = np.zeros((self.slots,), np.int32)
        for slot in self._active:
            counts[slot] = len(slot_pages[slot])
        # The rows that hold a page: what the dispatch's ``launch`` says
        # beside the rung.
        self._live_pages = int(counts.sum())
        # A model may lay its rows out in groups (``view_rows``).
        rows_of = getattr(self._ld, "view_rows", None)
        live = self._live_pages if rows_of is None else rows_of(counts)
        self._rung = next(n for n in self._view_ladder if n >= live)
        return self._view(tables, counts, self._rung)

    def _view(self, tables: np.ndarray, counts: np.ndarray, rung: int):
        """The model's ``live_page_view`` on ``rung`` rows. For a model
        with window kinds it is given, and gives, a dict a kind: the
        first kind's list and, a window kind, the pages the stepping
        slots hold, a fixed ``keep`` a slot."""
        if not self._windows:
            return self._ld.live_page_view(tables, counts, rung)
        stepping = counts > 0
        return self._ld.live_page_view(
            {self._kind: tables,
             **{k: w.table for k, w in self._windows.items()}},
            {self._kind: counts,
             **{k: (np.asarray(w.first),
                    np.where(stepping, np.asarray(w.held), 0))
                for k, w in self._windows.items()}},
            {self._kind: rung,
             **{k: w.keep for k, w in self._windows.items()}})

    def _ensure_decode_pages(self) -> None:
        """Every active slot can write its next token. Oldest
        slots are served first; when the pool is dry even after
        reclaiming prefix pins, the YOUNGEST admitted request is
        preempted (recompute-style requeue) — the oldest request always
        makes progress, so this terminates. (With no page kind there is
        nothing to ensure.)"""
        if self._kind is None:
            return
        for slot in sorted(self._active,
                           key=lambda s: self._active[s].submitted_at):
            while True:
                req = self._active.get(slot)
                if req is None:
                    break  # preempted while serving an older slot
                upto = req.prompt_len + req.generated
                # The step's query is at ``upto - 1``: what lies behind
                # its window goes back before anything is asked.
                self._trim_windows(slot, upto - 1)
                need = self._seq_pages(upto) - len(self._slot_pages[slot])
                dry = self._windows_missing(slot, upto)
                if need <= 0 and not dry:
                    self._grow_windows(slot, upto)
                    break
                got = None if dry else self._alloc_pages(need)
                if got is not None:
                    self._grow_slot(slot, got)
                    self._grow_windows(slot, upto)
                    break
                if not self._preempt_one():
                    break  # nothing left to preempt: caller's slot only

    def _preempt_one(self) -> bool:
        """Requeue the youngest admitted request to free its pages
        (vLLM-style recompute preemption): its prompt plus every token
        emitted so far re-enters the queue as one prefill, so the
        stream continues exactly where it left off after re-admission."""
        cands = list(self._active.items()) + list(self._prefilling.items())
        if not cands:
            return False
        slot, req = max(cands, key=lambda it: it[1].submitted_at)
        # What the preemption throws away, counted before the slot goes:
        # the prompt tokens this seat prefilled (a spliced prefix cost
        # nothing) and the pages it held.
        discarded = (req.prefilled if slot in self._prefilling
                     else len(req.tokens)) - req.prefix_len
        held = len(self._slot_pages[slot]) + sum(
            w.held[slot] for w in self._windows.values())
        self._active.pop(slot, None)
        self._prefilling.pop(slot, None)
        self._release_slot(slot)
        absorbed = len(req.tokens) - req.prompt_len
        tail = np.asarray(req.output[absorbed:], np.int32)
        if len(tail):
            req.tokens = np.concatenate([req.tokens, tail])
        req.slot = -1
        req.prefix_pages = []
        req.prefix_len = 0
        req.prefilled = 0
        self.preempted += 1
        req.preemptions += 1
        if self._obs_metrics:
            from ray_tpu.serve import metrics as smetrics

            smetrics.PREEMPTIONS.inc(1.0, self._mtags)
        if self.steplog.enabled:
            self.steplog.event("preempt", request=req.request_id,
                               tokens=req.generated,
                               prefilled=discarded, pages=held)
        if self._obs_spans and req.trace is not None:
            from ray_tpu.util import tracing

            now = time.time()
            tracing.record_span("preempt", now, now, ctx=req.trace,
                                request=req.request_id,
                                tokens=req.generated)
        self._requeue.insert(0, req)
        self._queued_tokens += len(req.tokens)
        with self._reqs_lock:
            req.admitted = False  # cancel-while-requeued counts as queued
        self._work.set()
        return True

    # ------------------------------------------------------------ intake

    def set_admission(self, queue_max: Optional[int]) -> int:
        """Runtime admission-cap override (the autopilot shed-tenant
        action, via ReplicaActor.set_admission): requests past the new
        cap shed at enqueue with OverloadedError. ``None``/``0``
        restores the configured default. Returns the cap in effect."""
        self.queue_max = (max(1, int(queue_max)) if queue_max
                          else self._default_queue_max)
        return self.queue_max

    def submit(self, prompt_tokens, max_new_tokens: int = 32,
               temperature: float = 0.0, eos_id: Optional[int] = None,
               on_token: Optional[Callable[[int], None]] = None,
               deadline_s: Optional[float] = None,
               request_id: Optional[str] = None,
               prefill_only: bool = False,
               adopt: Optional[Dict[str, Any]] = None,
               on_done: Optional[Callable[[_Request], None]] = None
               ) -> _Request:
        req = _Request(np.asarray(prompt_tokens, np.int32).reshape(-1),
                       int(max_new_tokens), float(temperature), eos_id,
                       on_token, on_done=on_done)
        req.request_id = request_id or f"req-{next(_req_ids)}"
        req.prompt_len = len(req.tokens)
        req.prefill_only = bool(prefill_only)
        if self._state_leaves and (prefill_only or adopt is not None):
            raise ValueError(
                f"model {self._ld.__name__} keeps slot state "
                f"{list(self._state_leaves)}: a handoff carries pages and "
                f"no state, so this engine neither publishes nor adopts one")
        if adopt is not None:
            self._validate_adopt(req, adopt)
            req.adopt = dict(adopt)
        if self._kind is not None and self._seq_pages(
                len(req.tokens) + req.max_new_tokens) > self.pool_pages:
            # A request no amount of preemption can seat must fail fast,
            # not live forever in the requeue list.
            raise ValueError(
                f"prompt ({len(req.tokens)}) + max_new_tokens "
                f"({req.max_new_tokens}) needs more pages than the pool "
                f"holds ({self.pool_pages} x {self.page_tokens} tokens)")
        if len(req.tokens) >= self.capacity:
            raise ValueError(
                f"prompt ({len(req.tokens)}) must be shorter than the "
                f"cache capacity ({self.capacity})")
        if len(req.tokens) + req.max_new_tokens > self.capacity:
            # Past capacity the K/V scatter at pos=length goes out of
            # bounds and JAX silently drops it — the request would return
            # wrong tokens, not an error. generate() sizes its cache as
            # cache_bucket(S + max_new_tokens); the engine's cache is
            # fixed, so the same budget must hold at admission.
            raise ValueError(
                f"prompt ({len(req.tokens)}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds the cache capacity "
                f"({self.capacity})")
        if deadline_s is not None:
            if deadline_s <= 0:
                self.deadline_exceeded += 1
                if self._obs_metrics:
                    from ray_tpu.serve import metrics as smetrics

                    smetrics.REQUESTS.inc(1.0, {
                        **self._mtags, "outcome": "deadline_exceeded"})
                raise DeadlineExceededError(
                    f"request {req.request_id} arrived with an already-"
                    f"expired deadline ({deadline_s:.3f}s)")
            req.deadline = time.monotonic() + float(deadline_s)
        if self._obs_spans:
            from ray_tpu.util import tracing

            req.trace = tracing.current()  # loop-thread spans attach here
        # Load shedding happens HERE, at enqueue — not after minutes in
        # queue. qsize() can transiently overshoot by concurrent
        # submitters, but the check bounds the queue within one wave.
        if self._pending.qsize() - self._queued_cancelled >= self.queue_max:
            self.shed += 1
            if self._obs_metrics:
                from ray_tpu.serve import metrics as smetrics

                smetrics.REQUESTS.inc(1.0, {**self._mtags,
                                            "outcome": "shed"})
            if req.trace is not None:
                from ray_tpu.util import tracing

                now = time.time()
                tracing.record_span("engine-shed", now, now,
                                    ctx=req.trace,
                                    request=req.request_id)
            raise OverloadedError(
                f"decode queue at capacity ({self.queue_max} pending, "
                f"{self.slots} slots)",
                retry_after_s=self.retry_after_estimate_s())
        with self._reqs_lock:
            self._requests[req.request_id] = req
        self._queued_tokens += len(req.tokens)
        self._pending.put(req)
        self._work.set()
        return req

    def _validate_adopt(self, req: _Request,
                        adopt: Dict[str, Any]) -> None:
        """Reject a handoff this pool cannot splice BEFORE enqueue, as
        the typed error the router maps to its colocated fallback. The
        payload must have been gathered from a pool with identical page
        geometry and head layout, and must cover exactly the prompt."""
        from ray_tpu.core.errors import HandoffAdoptError

        if int(adopt["page_tokens"]) != self.page_tokens:
            raise HandoffAdoptError(
                f"handoff page_tokens ({adopt['page_tokens']}) != this "
                f"engine's ({self.page_tokens}); pages cannot splice")
        if int(adopt["committed_len"]) != len(req.tokens):
            raise HandoffAdoptError(
                f"handoff committed_len ({adopt['committed_len']}) != "
                f"prompt length ({len(req.tokens)})")
        for name in self._pool_names:
            got = adopt[name]
            pool = self.cache[name].shape  # (L, pages+1, T, ...)
            w = self._windows.get(self._leaf_kind[name])
            want = (self._seq_pages(len(req.tokens)) if w is None
                    else w.span(len(req.tokens))[1])
            if (got.ndim != len(pool) or got.shape[0] != pool[0]
                    or tuple(got.shape[2:]) != tuple(pool[2:])
                    or got.shape[1] != want):
                raise HandoffAdoptError(
                    f"handoff payload shape {tuple(got.shape)} does not "
                    f"fit this engine's pool {tuple(pool)}")

    def retry_after_estimate_s(self) -> float:
        """How long a shed caller should wait before retrying, from the
        observed per-request service time: the queue drains ``slots``
        requests per service interval, so a rejected request's turn is
        about ``(queued / slots + 1)`` intervals away. Clamped to
        [0.5 s, 30 s]; 1 s before any request has completed."""
        if self._ema_request_s <= 0:
            return 1.0
        depth = max(0, self._pending.qsize() - self._queued_cancelled)
        est = (depth / max(1, self.slots) + 1.0) * self._ema_request_s
        return min(30.0, max(0.5, est))

    def cancel(self, request_id: str) -> bool:
        """Cooperative cancellation: mark the request; the decode loop
        drops it before prefill if still queued, or frees its slot at the
        next ``step()`` boundary if active. Returns False for unknown /
        already-finished requests (cancel is idempotent)."""
        with self._reqs_lock:
            req = self._requests.get(request_id)
            if req is None or req.done.is_set() or req.cancelled:
                return False
            req.cancelled = True
            if not req.admitted:
                # Still in the pending queue: exclude it from the load
                # signal now; _admit reconciles when it dequeues it.
                self._queued_cancelled += 1
        self._work.set()  # wake a parked loop so the drop is prompt
        return True

    # ------------------------------------------------ observability hooks
    #
    # All per-request: admission (queue-wait), wave prefills, terminal
    # outcomes. The per-token and per-step paths never touch the metrics
    # registry or the task-event buffer.

    def _mark_admitted(self, reqs: List["_Request"]) -> None:
        """Queue wait ends: the wave is about to dispatch device work.
        First admission only — a preemption requeue keeps its original
        admission time (queue_wait measures admission latency, not
        lifetime)."""
        fresh = [r for r in reqs if r.admitted_at is None]
        if not fresh:
            return
        now = time.monotonic()
        for req in fresh:
            req.admitted_at = now
        if self._obs_metrics:
            from ray_tpu.serve import metrics as smetrics

            smetrics.QUEUE_WAIT.observe_many(
                [now - r.submitted_at for r in fresh], self._mtags)
        if self._obs_spans:
            from ray_tpu.util import tracing

            wall = time.time()
            for req in fresh:
                if req.trace is not None:
                    tracing.record_span(
                        "queue-wait", wall - (now - req.submitted_at),
                        wall, ctx=req.trace, request=req.request_id)

    def _wave_span(self, name: str, t0_wall: float,
                   reqs: List["_Request"], **attrs: Any) -> None:
        """One span per request of a batched device call (the wave is
        one program; each request's trace gets its own slice of it)."""
        if not self._obs_spans:
            return
        from ray_tpu.util import tracing

        t1 = time.time()
        for req in reqs:
            if req.trace is not None:
                tracing.record_span(name, t0_wall, t1, ctx=req.trace,
                                    request=req.request_id, **attrs)

    def _first_dispatched(self, key: tuple, t0: float, before) -> None:
        """First dispatch of a program key = a jit compile on this
        engine; later dispatches of the same key are cache hits.
        ``_dispatch_fresh``, the one place that knows, calls this when
        the program's ``call()`` has returned: it leaves the set-up
        record's ``first_dispatch`` phase and the step log's
        ``jit-compile`` event. The interval is the HOST's, round
        ``call()`` as it stands (trace, lower, compile or load from the
        cache, enqueue): the program's run is waited for by whoever next
        fetches. ``before``: the compile counters at ``t0``."""
        t1 = time.time()
        after = self._compile_watch.snapshot()
        name = "/".join(str(k) for k in key)
        flightrec.record(
            "setup.phase", phase="first_dispatch", t0=t0, t1=t1, key=name,
            compiles=after["compiles"] - before["compiles"],
            compile_s=round(after["compile_s"] - before["compile_s"], 3),
            cache_hits=after["cache_hits"] - before["cache_hits"])
        if self.steplog.enabled:
            self.steplog.event("jit-compile", key=name, dt=t1 - t0)

    def post_event(self, kind: str, **attrs: Any) -> None:
        """A step-log event from ANY thread: it rides on the row of the
        next step, which a parked loop wakes for. Stamped here."""
        if self.steplog.enabled:
            self._inbox.append((kind, {"ts": time.time(), **attrs}))
            self._work.set()

    def _observe_terminal(self, req: "_Request", status: str) -> None:
        """Terminal bookkeeping shared by _finish and _retire: outcome
        counter, TTFT / inter-token histograms, and the request's
        engine-side spans (decode slice + whole-request outcome)."""
        if self._obs_metrics:
            from ray_tpu.serve import metrics as smetrics

            smetrics.REQUESTS.inc(1.0, {**self._mtags, "outcome": status})
            if req.first_token_at is not None:
                smetrics.TTFT.observe(
                    req.first_token_at - req.submitted_at, self._mtags)
                if status == "completed" and req.generated > 1:
                    # Stream duration / token, once per request: robust
                    # to chunked emission's bursty raw gaps, and never a
                    # per-token registry hit.
                    smetrics.INTER_TOKEN.observe(
                        (req.finished_at - req.first_token_at)
                        / (req.generated - 1), self._mtags)
        if self._obs_spans and req.trace is not None:
            from ray_tpu.util import tracing

            off = time.time() - time.monotonic()  # mono -> wall
            if (req.first_token_at is not None
                    and req.finished_at > req.first_token_at):
                tracing.record_span(
                    "decode", req.first_token_at + off,
                    req.finished_at + off, ctx=req.trace,
                    request=req.request_id, tokens=req.generated)
            tracing.record_span(
                "engine-request", req.submitted_at + off,
                req.finished_at + off, ctx=req.trace,
                request=req.request_id, outcome=status,
                tokens=req.generated, preemptions=req.preemptions)

    # -------------------------------------------------------- the loop

    def _admit(self) -> None:
        while self._free and (self._requeue
                              or not self._pending.empty()):
            # Drain up to len(free) pending requests (preempted requeues
            # first — they were admitted before anything still queued),
            # split them into prefix-cache hits and misses, and prefill
            # each group as ONE batched device call per prompt/suffix
            # bucket.
            wave: List[_Request] = []
            while len(wave) < len(self._free):
                if self._requeue:
                    wave.append(self._requeue.pop(0))
                    self._queued_tokens -= len(wave[-1].tokens)
                    continue
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    break
                self._queued_tokens -= len(req.tokens)
                wave.append(req)
            if not wave:
                return
            # Dead-on-arrival requests (cancelled while queued, or
            # deadline already passed) retire HERE — before any prefix
            # match or device work. They never touch the device and the
            # wave refills from the queue behind them.
            live: List[_Request] = []
            now = time.monotonic()
            for req in wave:
                with self._reqs_lock:
                    req.admitted = True
                    if req.cancelled:
                        self._queued_cancelled -= 1
                if req.cancelled:
                    self._retire(req, "cancelled")
                elif req.deadline is not None and now > req.deadline:
                    self._retire(req, "deadline_exceeded")
                else:
                    live.append(req)
            if not live:
                continue
            if not self._admit_paged(live):
                return  # pool dry: stop admitting this step

    def _admit_paged(self, live: List[_Request]) -> bool:
        """Seat a wave: prefix pages splice into the slot's
        block table with ZERO device copies, fresh pages come from the
        allocator, and long prefills hand off to the chunked-prefill
        interleaver instead of running one monolithic program. Returns
        False when the pool ran dry mid-wave (unseated requests are
        pushed back in order; admission pauses until pages free)."""
        if self._kind is None:
            return self._admit_slots(live)
        chunk = self.prefill_chunk_tokens
        full_group: List[_Request] = []
        suffix_group: List[_Request] = []
        seated: List[_Request] = []
        for i, req in enumerate(live):
            if req.adopt is not None:
                # Disaggregated adopt: the prompt's KV already exists as
                # a handed-off page payload — scatter it in, no model
                # math, no prefix match (the adopted pages ARE the
                # prompt; they get inserted into the prefix index so
                # later prompts can splice them).
                if not self._seat_adopted(req):
                    rest = live[i:]
                    for r in reversed(rest):
                        with self._reqs_lock:
                            r.admitted = False
                        self._requeue.insert(0, r)
                        self._queued_tokens += len(r.tokens)
                    break
                continue
            m = (self.prefix.match(req.tokens)
                 if self.prefix is not None else None)
            if m is not None:
                req.prefix_pages, req.prefix_len = m
            else:
                req.prefix_pages, req.prefix_len = [], 0
            suffix_len = len(req.tokens) - req.prefix_len
            if chunk > 0 and suffix_len > chunk:
                # Chunked prefill: take the slot and the spliced prefix
                # now; _prefill_tick runs the chunks between decode
                # steps (and allocates pages chunk by chunk).
                slot = self._free.pop()
                req.slot = slot  # ownership on the request before any
                #   fallible call: a raise must not strand the lease
                self._set_slot_pages(slot, req.prefix_pages)
                for w in self._windows.values():
                    w.seat(slot, req.prefix_len)
                req.prefilled = req.prefix_len
                # The cursor stays where the slot's release parked it:
                # a decode step writes a slot outside its view to the
                # scratch page, wherever the cursor is.
                self._prefilling[slot] = req
                seated.append(req)
                continue
            need = self._seq_pages(len(req.tokens)) - len(req.prefix_pages)
            # A whole prefill is written through the window kinds' pages
            # too (trimmed when it has run): every kind has room, or the
            # wave stops here.
            slot = self._free[-1]
            for w in self._windows.values():
                w.seat(slot, req.prefix_len)
            pages = (None if self._windows_missing(slot, len(req.tokens))
                     else self._alloc_pages(need))
            if pages is None:
                # Dry: drop the splice pins, push this and the rest of
                # the wave back (front, original order) and pause.
                self._pages.free(req.prefix_pages)
                req.prefix_pages = []
                req.prefix_len = 0
                rest = live[i:]
                for r in reversed(rest):
                    with self._reqs_lock:
                        r.admitted = False
                    self._requeue.insert(0, r)
                    self._queued_tokens += len(r.tokens)
                break
            slot = self._free.pop()
            req.slot = slot
            self._set_slot_pages(slot, req.prefix_pages + pages)
            self._grow_windows(slot, len(req.tokens))
            seated.append(req)
            (suffix_group if req.prefix_len else full_group).append(req)
        self._mark_admitted(seated)
        self._admit_paged_full(full_group)
        self._admit_paged_suffix(suffix_group)
        return not self._requeue

    def _admit_slots(self, live: List[_Request]) -> bool:
        """``_admit_paged`` of a model with no page kind: the wave is no
        longer than the free slots (``_admit``), so every request is seated; a
        prompt longer than a chunk goes to the chunked-prefill
        interleaver, the others prefill whole. Always True: nothing can
        run dry."""
        chunk = self.prefill_chunk_tokens
        whole: List[_Request] = []
        for req in live:
            req.slot = slot = self._free.pop()
            if chunk > 0 and len(req.tokens) > chunk:
                self._prefilling[slot] = req
            else:
                whole.append(req)
        self._mark_admitted(live)
        self._admit_paged_full(whole)
        return True

    def _seat_adopted(self, req: _Request) -> bool:
        """Seat one adopted (handed-off) request: allocate pages for the
        committed prompt, scatter the payload in with the jitted adopt
        program, and emit the handoff's first token — the request enters
        the decode loop exactly as if this engine had prefilled it.
        Returns False when the pool is dry (caller requeues; the adopt
        payload stays on the request for the retry)."""
        import jax.numpy as jnp

        adopt = req.adopt
        clen = int(adopt["committed_len"])
        slot = self._free[-1]
        for w in self._windows.values():
            w.seat(slot, clen)      # holds nothing, window at ``clen``
        pages = (None if self._windows_missing(slot, clen)
                 else self._alloc_pages(self._seq_pages(clen)))
        if pages is None:
            return False
        self._free.pop()
        req.slot = slot  # ownership on the request before any fallible
        #   call: a raise must not strand the pages
        self._set_slot_pages(slot, pages)
        self._grow_windows(slot, clen)
        req.prefix_pages, req.prefix_len = [], 0
        req.prefilled = clen
        # Pow-2 page-count bucket: one compiled adopt program per width
        # (a kind), pad columns scatter zero payloads into the scratch
        # page.
        held = {self._kind: pages,
                **{k: w.slot_pages(slot) for k, w in self._windows.items()}}
        ids = {}
        for kind, mine in held.items():
            n = 1
            while n < len(mine):
                n *= 2
            ids[kind] = np.zeros((n,), np.int32)
            ids[kind][:len(mine)] = mine
        width = len(ids[self._kind])
        padded = {}
        for name in self._pool_names:
            shape = self.cache[name].shape
            mine = ids[self._leaf_kind[name]]
            pad = np.zeros((shape[0], len(mine)) + tuple(shape[2:]),
                           adopt[name].dtype)
            pad[:, :adopt[name].shape[1]] = adopt[name]
            padded[name] = jnp.asarray(pad)
        index = ({k: jnp.asarray(v) for k, v in ids.items()}
                 if self._windows else jnp.asarray(ids[self._kind]))
        t0 = time.time()
        self.cache = self._dispatch_fresh(
            ("adopt_pages",) + tuple(len(v) for v in ids.values()),
            lambda: self._adopt_pages(
                self.cache, padded, index, jnp.asarray([slot], np.int32),
                jnp.asarray([clen], np.int32), width=width),
            then="admit")
        if self.steplog.enabled:
            self._handoff_phases.append(
                {"phase": "handoff", "t0": t0, "t1": time.time(),
                 "slot": slot, "pages": len(pages)})
        self._mark_admitted([req])
        self._post_adopt(req, slot)
        return True

    def _post_adopt(self, req: _Request, slot: int) -> None:
        """Adopted twin of _post_admit's per-request tail: prefix-index
        insert first (the adopted pages hold the full prompt, so later
        prompts sharing it splice against THIS replica), then emit the
        handoff's first token and enter the decode loop."""
        if self.prefix is not None:
            self.prefix.insert(req.tokens, self._slot_pages[slot],
                               matched_len=0)
        now = time.monotonic()
        tok = int(req.adopt["first_token"])
        if req.first_token_at is None:
            req.first_token_at = now
        self._emit(req, tok)
        self._seat(slot, tok, req.temperature)
        self._active[slot] = req
        self.handoffs_adopted += 1
        req.adopt = None  # drop the multi-MB payload promptly
        if req.generated >= req.max_new_tokens or (
                req.eos_id is not None and tok == req.eos_id):
            self._finish(slot)

    def _admit_paged_full(self, reqs: List[_Request]) -> None:
        import jax.numpy as jnp

        ld = self._ld
        by_bucket: Dict[int, List[_Request]] = {}
        for req in reqs:
            bucket = min(ld.cache_bucket(len(req.tokens),
                                         self.prefill_bucket),
                         self.capacity)
            by_bucket.setdefault(bucket, []).append(req)
        T = self.page_tokens
        # A model may bound the tokens of one prefill program
        # (``PREFILL_TOKENS_MAX``: its temporaries grow with rows x
        # bucket); a bucket's group then goes as several waves.
        cap = getattr(ld, "PREFILL_TOKENS_MAX", None)
        waves = []
        for bucket, group in by_bucket.items():
            rows = max(1, cap // bucket) if cap else len(group)
            waves += [(bucket, group[i:i + rows])
                      for i in range(0, len(group), rows)]
        for bucket, group in waves:
            n = 1
            while n < len(group):
                n *= 2
            wp = max(1, -(-bucket // T))  # bt columns covering the bucket
            rows = np.zeros((n, bucket), np.int32)
            lengths = np.zeros((n,), np.int32)
            slot_ids = np.full((n,), group[-1].slot, np.int32)
            for i, req in enumerate(group):
                rows[i, :len(req.tokens)] = req.tokens
                lengths[i] = len(req.tokens)
                slot_ids[i] = req.slot
            for i in range(len(group), n):  # idempotent pad rows
                rows[i] = rows[len(group) - 1]
                lengths[i] = lengths[len(group) - 1]
            bt = None if self._kind is None else self._block_tables[
                [r.slot for r in group]
                + [group[-1].slot] * (n - len(group)), :wp]
            self._prefill_waves += 1
            t0 = time.time()
            ids, self.cache = self._dispatch_fresh(
                ("paged_prefill", n, bucket),
                lambda: self._paged_prefill(
                    self.params, self.cache, jnp.asarray(rows),
                    jnp.asarray(lengths),
                    self._prefill_tables([r.slot for r in group],
                                         [0] * len(group), bt, bucket,
                                         n=n),
                    jnp.asarray(slot_ids), *self._draw_args(group, n),
                    n=n, bucket=bucket),
                tokens=sum(len(r.tokens) for r in group),
                **self._prefill_attrs(len(group), prefix=0))
            for req in group:
                self._trim_windows(req.slot, len(req.tokens))
            ids = self._fetch_ids(ids, "paged_prefill")
            self._wave_span("prefill", t0, group, n=len(group),
                            bucket=bucket)
            self._post_admit(group, [r.slot for r in group], ids)

    def _admit_paged_suffix(self, reqs: List[_Request]) -> None:
        """Prefix-hit paged admissions: the shared pages are already in
        the slots' block tables (zero-copy splice at _admit_paged);
        prefill only the uncached suffixes, one program per
        (n, bucket, width) tuple."""
        import jax.numpy as jnp

        ld = self._ld
        T = self.page_tokens
        by_bucket: Dict[int, List[_Request]] = {}
        for req in reqs:
            suffix_len = len(req.tokens) - req.prefix_len
            bucket = min(ld.cache_bucket(suffix_len,
                                         self._suffix_bucket_min),
                         self.capacity)
            by_bucket.setdefault(bucket, []).append(req)
        for bucket, group in by_bucket.items():
            n = 1
            while n < len(group):
                n *= 2
            need = max(-(-(r.prefix_len + bucket) // T) for r in group)
            width = 1
            while width < need:
                width *= 2
            width = min(width, self.slot_pages_max)
            rows = np.zeros((n, bucket), np.int32)
            plens = np.zeros((n,), np.int32)
            lengths = np.zeros((n,), np.int32)
            slot_ids = np.full((n,), group[-1].slot, np.int32)
            bt = np.zeros((n, width), np.int32)
            for i, req in enumerate(group):
                suffix = req.tokens[req.prefix_len:]
                rows[i, :len(suffix)] = suffix
                plens[i] = req.prefix_len
                lengths[i] = len(req.tokens)
                slot_ids[i] = req.slot
                bt[i] = self._block_tables[req.slot, :width]
            for i in range(len(group), n):  # idempotent pad rows
                rows[i] = rows[len(group) - 1]
                plens[i] = plens[len(group) - 1]
                lengths[i] = lengths[len(group) - 1]
                bt[i] = bt[len(group) - 1]
            self._prefill_waves += 1
            t0 = time.time()
            ids, self.cache = self._dispatch_fresh(
                ("paged_suffix", n, bucket, width),
                lambda: self._paged_suffix(
                    self.params, self.cache, jnp.asarray(rows),
                    jnp.asarray(plens), jnp.asarray(lengths),
                    self._prefill_tables(
                        [r.slot for r in group],
                        [r.prefix_len for r in group], bt, bucket),
                    jnp.asarray(slot_ids),
                    *self._draw_args(group, n),
                    n=n, bucket=bucket, width=width),
                tokens=sum(len(r.tokens) - r.prefix_len for r in group),
                **self._prefill_attrs(len(group)))
            for req in group:
                self._trim_windows(req.slot, len(req.tokens))
            ids = self._fetch_ids(ids, "paged_suffix")
            self._wave_span("suffix-prefill", t0, group, n=len(group),
                            bucket=bucket)
            self._post_admit(group, [r.slot for r in group], ids)

    def _prefill_tick(self) -> None:
        """Chunked-prefill interleaving: advance the OLDEST mid-prefill
        slot by at most ONE ``prefill_chunk_tokens`` chunk, then return
        so the decode step runs. A 4k-token admission thus costs active
        streams one chunk of latency per token, never its whole
        prefill. Page allocation is chunk-by-chunk; a dry pool skips
        the tick (decode keeps draining; the chunk retries next step).

        Where the last step sent this step's chunk ahead
        (``_prefill_ahead``), nothing is dispatched: one chunk lies
        between two decodes on the device. If that chunk ended its
        prompt, its ids are fetched here and the slot is seated, so it
        joins this step's decode as it would have."""
        went, self._ahead = self._ahead, None
        if went is None:
            if self._prefilling:
                self._dispatch_chunk(ahead=False)
        elif went[1] is not None:
            self._end_prefill(*went)

    def _end_prefill(self, slot: int, ids) -> None:
        """The slot's last chunk has been dispatched: fetch the first
        token it sampled and seat the slot for the decode."""
        req = self._prefilling.pop(slot)
        self._post_admit(
            [req], [slot], self._fetch_ids(ids, "prefill_chunk"))

    def _prefill_ahead(self) -> None:
        """The NEXT step's prefill tick, run behind this step's decode
        once that is dispatched and before its ids are fetched: the
        chunk's inputs (the prompt's own tokens, its slot, its pages)
        wait for no id, so the device runs it while the host samples,
        emits, reaps, admits and launches the next decode, and not
        after. It takes what is free and nothing else
        (``_free_lists_cover``): where that is short it stands back,
        and the tick runs at its usual place in the next step, after
        this step's finishes have returned their pages."""
        if self._prefilling:
            self._slice("admit")
            self._dispatch_chunk(ahead=True)

    def _free_lists_cover(self, slot: int, tokens: int) -> bool:
        """Whether the pages FREE now, no prefix pin reclaimed and
        nobody preempted, cover the slot's first ``tokens`` positions
        in every kind."""
        if self._kind is None:
            return True
        need = self._seq_pages(tokens) - len(self._slot_pages[slot])
        return (need <= self._pages.free_count
                and not self._windows_missing(slot, tokens))

    def _dispatch_chunk(self, ahead: bool) -> None:
        """Dispatch the next chunk of the oldest mid-prefill slot (there
        is one), if its pages can be had. ``ahead``: behind a decode
        whose ids are yet to be fetched; the chunk's own ids, if its
        prompt ends, then wait for the next step's tick."""
        import jax.numpy as jnp

        ld = self._ld
        T = self.page_tokens
        slot = min(self._prefilling,
                   key=lambda s: self._prefilling[s].submitted_at)
        req = self._prefilling[slot]
        remaining = len(req.tokens) - req.prefilled
        step_tok = min(self.prefill_chunk_tokens, remaining)
        bucket = min(ld.cache_bucket(step_tok, self._suffix_bucket_min),
                     self.prefill_chunk_tokens)
        if ahead and not self._free_lists_cover(
                slot, req.prefilled + step_tok):
            return
        if self._kind is None:
            # No page kind: nothing to allocate, and one program a bucket
            # (``width`` counts the columns of a table there is not).
            width, bt = 0, None
        else:
            need = self._seq_pages(req.prefilled + step_tok) \
                - len(self._slot_pages[slot])
            if self._windows_missing(slot, req.prefilled + step_tok):
                return
            if need > 0:
                got = self._alloc_pages(need)
                if got is None:
                    return
                self._grow_slot(slot, got)
            self._grow_windows(slot, req.prefilled + step_tok)
            width = 1
            while width * T < req.prefilled + bucket:
                width *= 2
            width = min(width, self.slot_pages_max)
            bt = self._block_tables[slot:slot + 1, :width]
        rows = np.zeros((1, bucket), np.int32)
        rows[0, :step_tok] = req.tokens[req.prefilled:
                                        req.prefilled + step_tok]
        ends = req.prefilled + step_tok >= len(req.tokens)
        self.prefill_chunks += 1
        attrs = self._prefill_attrs(int(ends))
        if ahead:
            self.prefill_chunks_ahead += 1
            attrs["ahead"] = 1
            if self.steplog.enabled:
                # The chunk's run on the device ends under the NEXT
                # fetch, not under the decode's that opens behind this
                # launch (``StepTimeline.enclose``).
                self.steplog.enclose("fetch", program="decode")
        t0 = time.time()
        ids, self.cache = self._dispatch_fresh(
            ("paged_suffix", 1, bucket, width),
            lambda: self._paged_suffix(
                self.params, self.cache, jnp.asarray(rows),
                jnp.asarray([req.prefilled], np.int32),
                jnp.asarray([req.prefilled + step_tok], np.int32),
                self._prefill_tables([slot], [req.prefilled], bt, bucket,
                                     ends=[ends], n=1),
                jnp.asarray([slot], np.int32),
                *self._draw_args([req], 1),
                n=1, bucket=bucket, width=width),
            then=None if ahead else "admit", program="prefill_chunk",
            tokens=step_tok, prefix=req.prefilled, **attrs)
        # The chunk's own window pages, but for the window of the next
        # position, are dead the moment the program is dispatched.
        self._trim_windows(slot, req.prefilled + step_tok)
        self._wave_span("prefill-chunk", t0, [req], tokens=step_tok,
                        prefilled=req.prefilled + step_tok,
                        prompt=len(req.tokens))
        req.prefilled += step_tok
        if ahead:
            self._ahead = (slot, ids if ends else None)
        elif ends:
            self._end_prefill(slot, ids)

    def _retire(self, req: _Request, status: str) -> None:
        """Terminal exit for a request that never held a slot."""
        req.status = status
        req.finished_at = time.monotonic()
        if status == "cancelled":
            self.cancelled += 1
        elif status == "deadline_exceeded":
            self.deadline_exceeded += 1
        self._observe_terminal(req, status)
        with self._reqs_lock:
            self._requests.pop(req.request_id, None)
        self._end(req)

    def _purge_pending(self) -> None:
        """Drop dead entries (cancelled / deadline-expired) from the
        pending queue WITHOUT waiting for a slot to free: when every
        slot is busy for minutes, admission never runs, but a cancelled
        caller's entry must still retire promptly — it would otherwise
        hold its done-event, its _requests entry, and (for expiries)
        inflate the load signal. One FIFO-preserving rotation."""
        now = time.monotonic()
        for _ in range(self._pending.qsize()):
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            with self._reqs_lock:
                dead = req.cancelled
                if dead:
                    self._queued_cancelled -= 1
                    req.admitted = True
            if dead:
                self._queued_tokens -= len(req.tokens)
                self._retire(req, "cancelled")
            elif req.deadline is not None and now > req.deadline:
                with self._reqs_lock:
                    req.admitted = True
                self._queued_tokens -= len(req.tokens)
                self._retire(req, "deadline_exceeded")
            else:
                self._pending.put(req)
        # Preempted/pushed-back requests wait in _requeue, not the
        # queue: give their cancels/deadlines the same prompt exit.
        for req in list(self._requeue):
            with self._reqs_lock:
                dead = req.cancelled
                if dead:
                    self._queued_cancelled -= 1
                    req.admitted = True
            expired = (not dead and req.deadline is not None
                       and now > req.deadline)
            if dead or expired:
                self._requeue.remove(req)
                self._queued_tokens -= len(req.tokens)
                if expired:
                    with self._reqs_lock:
                        req.admitted = True
                self._retire(req, "cancelled" if dead
                             else "deadline_exceeded")

    def _draw_args(self, group: List[_Request], n: int):
        """What a prefill program samples its ``n`` rows with: the
        rows' temperatures (pad rows repeat the last) and the counter
        of the prefills' random stream, the prefill programs dispatched
        so far, this one included. Host arrays: they go up with the
        call."""
        temps = [max(0.0, req.temperature) for req in group]
        temps += temps[-1:] * (n - len(temps))
        return (np.asarray(temps, np.float32),
                np.int32(self._prefill_waves + self.prefill_chunks))

    def _fetch_ids(self, ids, program: str) -> np.ndarray:
        """The first tokens a prefill ``program`` sampled, on the host:
        the one wait of an admission, its ``fetch`` slice."""
        self._slice("fetch", program=program, bytes=int(ids.nbytes))
        ids = np.array(ids)
        self._slice("admit")
        return ids

    def _post_admit(self, group: List[_Request], slots: List[int],
                    ids: np.ndarray) -> None:
        # The prefix insert runs BEFORE the emit/finish loop: a
        # request that completes on its very first token (max_new=1 /
        # instant EOS) is _finish-ed inside that loop, which FREES its
        # pages — pinning them afterwards would pin recycled (soon
        # overwritten) pages. Inserting first pins the slot's pages
        # while the slot still owns them; _finish then drops only the
        # slot's own references.
        if self.prefix is not None:
            for req, slot in zip(group, slots):
                self.prefix.insert(req.tokens, self._slot_pages[slot],
                                   matched_len=req.prefix_len)
        now = time.monotonic()
        for i, req in enumerate(group):
            tok = int(ids[i])
            req.slot = slots[i]
            if req.first_token_at is None:
                # Set once: a preempted request comes through here
                # again, and its first token was long delivered.
                req.first_token_at = now
            if req.prefill_only:
                # Disaggregated prefill terminal: the deliverable is the
                # slot's filled pages + the sampled first token, not an
                # emitted stream. Gather to host, then finish the slot —
                # its device pages free immediately (the prefix insert
                # above already pinned the shareable ones).
                self._capture_handoff(req, slots[i], tok)
                self._active[slots[i]] = req
                self._finish(slots[i])
                continue
            self._emit(req, tok)
            self._seat(slots[i], tok, req.temperature)
            self._active[slots[i]] = req
            if req.generated >= req.max_new_tokens or (
                    req.eos_id is not None and tok == req.eos_id):
                self._finish(slots[i])

    def _capture_handoff(self, req: _Request, slot: int,
                         first_token: int) -> None:
        """Prefill-only terminal: gather the slot's filled pages to host
        as the handoff payload. The gather is a pure device->host copy
        of page payloads (no model math), so an adopting engine's state
        is bit-identical to having prefilled there. The first sampled
        token rides the descriptor instead of being emitted here — the
        decode side emits it, keeping the client-visible stream
        identical to the colocated path."""
        t0 = time.time()
        ids = {self._kind: np.asarray(self._slot_pages[slot], np.int32),
               **{k: np.asarray(w.slot_pages(slot), np.int32)
                  for k, w in self._windows.items()}}
        # np.array (never asarray): the payload outlives later donated
        # dispatches, so it must OWN its bytes — a host view of the
        # cache would be clobbered in place (the PR 16 pin). A leaf
        # carries the pages of ITS kind: all of the prompt's, or the
        # window's (``WindowPages.span`` of the committed length).
        pages = {name: np.array(
            self.cache[name][:, ids[self._leaf_kind[name]]])
            for name in self._pool_names}
        req.handoff = {
            **pages,
            "committed_len": int(req.prompt_len),
            "first_token": int(first_token),
            "page_tokens": self.page_tokens,
            "nbytes": int(sum(a.nbytes for a in pages.values())),
        }
        self.handoffs_published += 1
        if self.steplog.enabled:
            self._handoff_phases.append(
                {"phase": "handoff", "t0": t0, "t1": time.time(),
                 "slot": slot, "pages": int(len(ids[self._kind]))})

    def _seat(self, slot: int, tok: int, temperature: float = 0.0) -> None:
        """The HOST writes what ``slot`` decodes from next, token and
        temperature: the device's copy of the tokens is stale from
        here, and that of the temperatures if this one differs."""
        self._tokens[slot] = tok
        self._state_dev = None
        temperature = max(0.0, temperature)
        if self._temps[slot] != temperature:
            self._temps[slot] = temperature
            self._temps_dev = None

    _last_cb_log = 0.0  # class-wide rate limit for callback-failure logs

    def _emit(self, req: _Request, tok: int) -> None:
        req.output.append(tok)
        req.generated += 1
        self.tokens_out += 1
        if req.on_token is None:
            return
        try:
            req.on_token(tok)
        except Exception as e:  # noqa: BLE001 — the decode loop must
            # survive a broken streaming consumer, but silently eating
            # the error made streaming failures undiagnosable. Record
            # the FIRST failure on the request and log once per request
            # (rate-limited across requests: a wedged consumer fails on
            # every token of every request).
            if req.on_token_error is None:
                req.on_token_error = f"{type(e).__name__}: {e}"
                now = time.monotonic()
                if now - DecodeEngine._last_cb_log > 1.0:
                    DecodeEngine._last_cb_log = now
                    logger.warning(
                        "on_token callback failed (slot %d, %d tokens "
                        "emitted): %s", req.slot, req.generated,
                        req.on_token_error, exc_info=True)
                # The consumer gets no more tokens: let it find out now,
                # not when the request has run to its end.
                self._tell_done(req)

    def _end(self, req: _Request) -> None:
        """The one exit of every request, whichever way it ends: waiters
        on ``done`` and the ``on_done`` hook learn of it here."""
        req.done.set()
        self._tell_done(req)

    def _tell_done(self, req: _Request) -> None:
        if req.on_done is None:
            return
        try:
            req.on_done(req)
        except Exception:  # noqa: BLE001 — as for on_token: the loop lives
            logger.warning("on_done callback failed (request %s)",
                           req.request_id, exc_info=True)

    def _release_slot(self, slot: int) -> None:
        """Slot teardown shared by _finish and preemption: drops the
        slot's page references (shared prefix pages survive on the
        index's pins; exclusively-owned pages recycle immediately) and
        parks the block-table row on the scratch page."""
        if self._kind is not None:
            pages = self._slot_pages[slot]
            self._slot_pages[slot] = []
            self._block_tables[slot, :] = 0
            self._pages.free(pages)
            if pages and self.steplog.enabled:
                self.steplog.event("page-free", n=len(pages),
                                   page_kind=self._kind,
                                   free=self._pages.free_count)
        for kind, w in self._windows.items():
            n = w.release(slot)
            if n and self.steplog.enabled:
                self.steplog.event("page-free", n=n, page_kind=kind,
                                   free=w.alloc.free_count)
        self._free.append(slot)
        if self._ahead is not None and self._ahead[0] == slot:
            # Its chunk is in flight: nobody fetches what it samples.
            self._ahead = (slot, None)
        # Park the freed slot at length 0 so idle slots don't walk their
        # cursor toward the capacity edge while others decode.
        self.cache["length"] = self.cache["length"].at[slot].set(0)
        self._seat(slot, 0)

    def _finish(self, slot: int, status: str = "completed") -> None:
        req = self._active.pop(slot, None)
        if req is None:
            req = self._prefilling.pop(slot)  # died mid-chunked-prefill
        # Return the slot IMMEDIATELY after the active-pop: _free is only
        # consumed by _admit on this same thread, but stats() reads both
        # cross-thread — a device dispatch between the pop and the append
        # would show active+free < slots (a phantom wedged slot).
        self._release_slot(slot)
        req.status = status
        req.finished_at = time.monotonic()
        if status == "completed":
            # Service-time EMA feeds the shed path's Retry-After estimate.
            service = req.finished_at - req.submitted_at
            self._ema_request_s = (service if self._ema_request_s <= 0
                                   else 0.7 * self._ema_request_s
                                   + 0.3 * service)
        elif status == "cancelled":
            self.cancelled += 1
        elif status == "deadline_exceeded":
            self.deadline_exceeded += 1
        self._observe_terminal(req, status)
        with self._reqs_lock:
            self._requests.pop(req.request_id, None)
        self._end(req)

    def _reap(self) -> None:
        """Free slots whose requests are dead (cancelled, or past their
        deadline): runs at every step boundary, so a dead request costs
        at most ONE more decode step — its slot and its place in the
        batch go back to live traffic immediately (the property Orca-
        style iteration-level scheduling is for). What other threads
        posted for the step log (``post_event``) is entered here."""
        while self._inbox:
            kind, attrs = self._inbox.popleft()
            self.steplog.event(kind, **attrs)
        now = time.monotonic()
        if (self._queued_cancelled > 0
                or (now - self._last_purge > 0.5
                    and (self._requeue or not self._pending.empty()))):
            self._last_purge = now
            self._purge_pending()
        for slot in list(self._active):
            req = self._active[slot]
            if req.cancelled:
                self._finish(slot, "cancelled")
            elif req.deadline is not None and now > req.deadline:
                self._finish(slot, "deadline_exceeded")
        # Mid-chunked-prefill slots die the same way: their pages (all
        # non-shared ones) free within ONE step boundary, like actives.
        for slot in list(self._prefilling):
            req = self._prefilling[slot]
            if req.cancelled:
                self._finish(slot, "cancelled")
            elif req.deadline is not None and now > req.deadline:
                self._finish(slot, "deadline_exceeded")

    def step(self) -> int:
        """Admit pending prefills, run at most one interleaved prefill
        chunk, advance every active slot one token. Returns the number
        of active slots stepped.

        The host's order: reap, admit, the prefill tick, pages and view,
        the decode's dispatch, the NEXT step's chunk behind it
        (``_prefill_ahead``), and only then the wait for the decode's
        ids and the per-slot emit and finish; so the device has a chunk
        to run while the host does all of that for the next step. Every
        decode's ids are read and every finish applied before the next
        decode is dispatched.

        When the step recorder is on (``decode_step_timeline``), the
        step's phases (admission prefills, interleaved prefill chunk,
        decode) land as one ring row with batch occupancy — the "why
        was this token slow" record — and the row's ``slices`` say
        where the HOST's time went: ``park`` (since the previous step
        ended), then ``reap``, ``admit``, ``pages``, ``launch``,
        ``fetch``, ``sample_emit`` and ``finish`` tile the step with no
        hole (``serve/steplog.py``). Recording costs one clock read and
        one profiler annotation per slice and one deque append per
        STEP; with the ring off this path is the uninstrumented
        loop."""
        rec = self.steplog.enabled
        sl = self.steplog
        phases: List[Dict[str, Any]] = []
        t_step0 = sl.step_begin() if rec else 0.0
        if rec:
            w0 = self._prefill_waves
            c0 = self.prefill_chunks
        self._reap()
        if rec:
            sl.begin("admit")
        self._admit()
        if rec and self._prefill_waves > w0:
            phases.append({"phase": "admit", "t0": t_step0,
                           "t1": time.time(),
                           "waves": self._prefill_waves - w0})
        t0 = time.time() if rec else 0.0
        self._prefill_tick()
        if rec and self.prefill_chunks > c0:
            phases.append({"phase": "prefill_chunk", "t0": t0,
                           "t1": time.time()})
        if not self._active:
            self._steplog_row(t_step0, phases)
            return 0
        if rec:
            sl.begin("pages")
        # Page the next token in BEFORE the program runs: the block
        # tables are static across the call. May preempt the youngest
        # request (and so shrink the active set).
        self._ensure_decode_pages()
        if not self._active:
            self._steplog_row(t_step0, phases)
            return 0
        stepped = len(self._active)
        ctx = self._ctx_tokens() if rec else None
        view = self._live_view(self._block_tables, self._slot_pages)
        rung = self._rung
        t_d0 = time.time() if rec else 0.0
        # ``uploads``: the arrays this dispatch puts on the device. The
        # view always; the state and the temperatures only when the
        # host has written to them since the last step.
        state, self.cache = self._dispatch_fresh(
            ("decode", rung),
            lambda: self._decode(self.params, self.cache,
                                 *self._decode_inputs(view)),
            batch=stepped, ctx_tokens=ctx, view_pages=rung,
            uploads=1 + (self._state_dev is None)
            + (self._temps_dev is None))
        # The NEXT step's chunk goes behind the decode, before the host
        # waits for the decode's ids.
        t0, sent = time.time() if rec else 0.0, self.prefill_chunks_ahead
        self._prefill_ahead()
        if rec and self.prefill_chunks_ahead > sent:
            phases.append({"phase": "prefill_chunk", "t0": t0,
                           "t1": time.time()})
        if rec:
            sl.begin("fetch", program="decode", bytes=int(state.nbytes))
        got = np.array(state)  # ids, the model's counters, the counter
        self._state_dev = state
        if rec:
            # The model's counters belong to the launch (its row says
            # so); its annotation closed before they existed, so in a
            # profiler trace they ride on the slice after the fetch.
            counted = {name: int(v) for name, v in zip(
                self._step_stats, got[self.slots:])}
            sl.amend("launch", "decode", **counted)
            sl.begin("sample_emit", **counted)
            phases.append({"phase": "decode", "t0": t_d0,
                           "t1": time.time(), "batch": stepped})
        self.steps += 1
        for slot in list(self._active):
            req = self._active[slot]
            tok = int(got[slot])
            self._emit(req, tok)
            self._tokens[slot] = tok  # as ``_state_dev`` has it
            if req.generated >= req.max_new_tokens or (
                    req.eos_id is not None and tok == req.eos_id):
                self._finish_in_step(slot)
        self._steplog_row(t_step0, phases, ctx, rung)
        return stepped

    def _put(self, host: np.ndarray):
        """``host`` on the device(s) the programs run on, a copy."""
        if self.mesh is not None:
            return self._jax.device_put(host,
                                        self._shardings["replicated"])
        return self._jax.numpy.array(host)

    def _host_state(self) -> np.ndarray:
        """The decode's ``state`` as the host knows it: its tokens, no
        counts yet, the steps taken so far."""
        return np.concatenate(
            [self._tokens, np.zeros(len(self._step_stats), np.int32),
             np.asarray([self.steps], np.int32)])

    def _decode_inputs(self, view: np.ndarray):
        """``(state, view, temps)`` on the device for the decode about
        to be dispatched. A step that follows a step finds the state
        where the last one left it and uploads the view alone."""
        if self._state_dev is None:
            self._state_dev = self._put(self._host_state())
        if self._temps_dev is None:
            self._temps_dev = self._put(self._temps)
        return self._state_dev, self._jax.tree.map(
            self._jax.numpy.asarray, view), self._temps_dev

    def _ctx_tokens(self) -> int:
        """KV positions the decode about to be dispatched really needs:
        the active slots' context lengths, the new token included. The
        view it reads is ``view_pages`` pages wide (``_live_view``): the
        last page of each slot is part empty and the rung is rounded up,
        and ``ctx_tokens / (view_pages x page_tokens)`` is the share of
        the view that is context."""
        return sum(r.prompt_len + r.generated
                   for r in self._active.values())

    def _finish_in_step(self, slot: int) -> None:
        """``_finish`` from a step's per-slot loop, as the ``finish``
        slice; the loop goes on in ``sample_emit``."""
        self._slice("finish")
        self._finish(slot)
        self._slice("sample_emit")

    def _steplog_row(self, t0: float, phases: List[Dict[str, Any]],
                     ctx_tokens: Optional[int] = None,
                     view_pages: Optional[int] = None) -> None:
        """Close the step's timeline row; idle steps with no phases and
        no pending events record nothing (an idle engine must not churn
        useful rows out of the bounded ring)."""
        if self._handoff_phases:
            # Handoff gathers/adopts happen inside admission helpers that
            # don't see the step's phases list; merge them here so the
            # row shows the handoff slice of the step.
            phases = phases + self._handoff_phases
            self._handoff_phases = []
        if not self.steplog.enabled:
            return
        if not (phases or self.steplog.pending_events):
            self.steplog.park(len(self._active))
            return
        self.steplog.record(
            t0, time.time(), phases,
            active=len(self._active), prefilling=len(self._prefilling),
            queued=max(0, self._pending.qsize() + len(self._requeue)
                       - self._queued_cancelled),
            pages_free=(0 if self._kind is None
                        else self._pages.free_count),
            pages_pinned=(self.prefix.pinned_pages
                          if self.prefix is not None else None),
            ctx_tokens=ctx_tokens, view_pages=view_pages,
            # A model with page kinds: the pages in use a kind, and the
            # tokens whose keys and values the seated slots hold.
            **({**{f"pages_{k}": n
                   for k, n in self.pages_in_use().items()},
                "kv_tokens": self._ctx_tokens() - len(self._active) + sum(
                    r.prefilled for r in self._prefilling.values())}
               if self._windows or self._state_leaves else {}),
            # A model with slot state: the bytes of it the seated slots
            # hold, decoding or between two prefill chunks.
            **({"state_bytes": self._slot_state_bytes
                * (len(self._active) + len(self._prefilling))}
               if self._state_leaves else {}))

    def warm_decode(self) -> None:
        """Dispatch the step loop's one-token decode once at every rung
        of the view ladder, so that no step of a serving engine meets a
        rung for the first time: which rung a step takes follows the
        traffic, but the ladder is fixed by the engine's geometry, and
        whatever builds an engine for traffic calls this first. On an
        idle engine: the view is empty, so every write goes to the
        scratch page, and the cursors are parked at 0 afterwards. Nothing
        here waits for a rung to have run: the phase ``warm_decode`` of
        the set-up record is the host's time to dispatch them."""
        t0 = time.time()
        for rung, view in self._empty_views():
            _, self.cache = self._dispatch_fresh(
                ("decode", rung),
                lambda: self._decode(self.params, self.cache,
                                     *self._decode_inputs(view)))
        self.cache["length"] = self.cache["length"].at[:].set(0)
        flightrec.record("setup.phase", phase="warm_decode", t0=t0,
                         t1=time.time(), rungs=len(self._view_ladder))

    def _empty_views(self):
        """``(rung, view)`` up the ladder, each view listing no page."""
        import jax.numpy as jnp

        none = np.zeros((self.slots,), np.int32)
        for rung in self._view_ladder:
            yield rung, self._jax.tree.map(
                jnp.asarray, none.astype(bool) if self._kind is None
                else self._view(self._block_tables, none, rung))

    def serve_forever(self, idle_wait_s: float = 0.05) -> None:
        """Decode loop for a replica thread: steps while work exists,
        parks on an event while idle."""
        self._loop_thread = threading.current_thread()
        try:
            while not self._stop.is_set():
                if (self._active or self._prefilling or self._requeue
                        or not self._pending.empty() or self._inbox):
                    self.step()
                else:
                    self._work.clear()
                    self._work.wait(timeout=idle_wait_s)
        finally:
            self._loop_thread = None
            self._abort_open()

    def shutdown(self) -> None:
        """Stop the loop and end every request still open as cancelled:
        nothing steps them again, so whoever waits on one (a stream's
        pull, ``_wait_done``) must hear of it now. Engine state belongs
        to the loop's thread, so a running loop does this on its way
        out; without one the caller does."""
        self._stop.set()
        self._work.set()
        if self._loop_thread in (None, threading.current_thread()):
            self._abort_open()

    def _abort_open(self) -> None:
        with self._reqs_lock:
            open_ids = list(self._requests)
        try:
            for request_id in open_ids:
                self.cancel(request_id)
            if open_ids:
                self._purge_pending()   # the queued and the requeued
                self._reap()            # the seated: slots, pages return
        finally:
            # What that could not reach (the loop died of a step that
            # raised, perhaps mid-admission) still ends for its waiters.
            with self._reqs_lock:
                stranded = list(self._requests.values())
                self._requests.clear()
            for req in stranded:
                req.status = "cancelled"
                self._end(req)

    # ------------------------------------------------------------ stats

    def prefill_backlog_tokens(self) -> int:
        """Prompt tokens accepted but not yet prefilled: the queue (and
        requeue) plus the un-prefilled remainder of mid-chunk slots.
        TTFT debt the autoscaler must see — a replica with two queued
        4k prompts is NOT as loaded as one with two queued 16-token
        prompts, even at equal queue depth."""
        backlog = max(0, self._queued_tokens)
        for req in list(self._prefilling.values()):
            backlog += max(0, len(req.tokens) - req.prefilled)
        return backlog

    def stats(self) -> Dict[str, Any]:
        from ray_tpu.serve.paging import NO_PAGES_STATS

        active = len(self._active)
        prefilling = len(self._prefilling)
        # Live queue depth: cancelled-but-undequeued entries are dead
        # weight, not demand — the autoscaler must not scale out for
        # requests that will be dropped at admission.
        queued = max(0, self._pending.qsize() + len(self._requeue)
                     - self._queued_cancelled)
        backlog = self.prefill_backlog_tokens()
        # Backlog tokens -> load units: one prefill chunk (or one full
        # prefill bucket, unchunked) of pending prompt is about one
        # step's worth of work, i.e. one active-slot-equivalent.
        denom = self.prefill_chunk_tokens or self.prefill_bucket
        out = {
            "steps": self.steps,
            "tokens_out": self.tokens_out,
            # Mesh footprint: chips this engine spans (1 = single-chip).
            # The serve autoscaler divides load by it — a (2, 4) replica
            # is 8 chips of capacity, not one replica-unit.
            "chips": self.mesh.size if self.mesh is not None else 1,
            "mesh_shape": (list(self.mesh.devices.shape)
                           if self.mesh is not None else None),
            "active": active,
            "prefilling": prefilling,
            "slots": self.slots,
            "free_slots": len(self._free),
            "queued": queued,
            "queue_max": self.queue_max,
            # Degradation counters: shed-at-enqueue, cooperative
            # cancellations, deadline expiries, and page-pressure
            # preemptions — surfaced through replica_metrics ->
            # controller snapshot -> serve.status() so overload shows
            # up as it happens.
            "shed": self.shed,
            "cancelled": self.cancelled,
            "deadline_exceeded": self.deadline_exceeded,
            "preempted": self.preempted,
            "prefill_chunks": self.prefill_chunks,
            "prefill_chunks_ahead": self.prefill_chunks_ahead,
            "prefill_backlog_tokens": backlog,
            # Decode backlog as replica load: occupied slots + pending
            # queue depth + prefill-backlog tokens (in chunk-steps). A
            # full queue behind idle HTTP must read as load to the
            # serve autoscaler, not zero — and neither must a 4k
            # prompt mid-chunked-prefill.
            "load": active + prefilling + queued + backlog // max(1,
                                                                 denom),
            "device": self.device_stats(),
        }
        out.update(NO_PAGES_STATS if self._kind is None
                   else self._pages.stats())
        if self._windows:
            out["pages_in_use_by_kind"] = self.pages_in_use()
            out["pages_total_by_kind"] = {
                self._kind: self._pages.pages,
                **{k: w.alloc.pages for k, w in self._windows.items()}}
        if self._state_leaves:
            out["state_bytes_total"] = self._slot_state_bytes * self.slots
        out["page_tokens"] = self.page_tokens
        out["pages_pinned"] = (self.prefix.pinned_pages
                               if self.prefix is not None else 0)
        out["kv_fragmentation"] = self._fragmentation()
        out["handoffs_published"] = self.handoffs_published
        out["handoffs_adopted"] = self.handoffs_adopted
        if self.prefix is not None:
            out["prefix"] = self.prefix.stats()
        if self.steplog.enabled:
            out["step_timeline_rows"] = len(self.steplog._rows)
            out["step_timeline_dropped"] = self.steplog.dropped
        return out

    def pages_in_use(self) -> Dict[str, int]:
        """Pool pages handed out, a page kind."""
        if self._kind is None:
            return {}
        return {self._kind: self._pages.in_use,
                **{k: w.alloc.in_use for k, w in self._windows.items()}}

    def device_stats(self) -> Dict[str, Any]:
        """Where this engine runs, as JAX reports it in THIS process
        (the one that holds the chips): platform, device kind, the
        devices the engine's programs span with their live and peak
        memory, and the process's compile counters. Read-only."""
        d0 = self._devices[0]
        mem = [d.memory_stats() or {} for d in self._devices]
        return {
            "platform": d0.platform,
            "device_kind": d0.device_kind,
            "device_count": len(self._jax.devices()),
            "device_ids": [d.id for d in self._devices],
            # Chips the node's lease made visible to this process (None
            # = all local chips): two one-chip replicas both report
            # device id 0, so this is what tells their chips apart.
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "bytes_in_use": [m.get("bytes_in_use") for m in mem],
            "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in mem],
            # The tree the programs read: its bytes over all devices,
            # and the dtype of the leaves the model casts, which is the
            # compute dtype once the engine holds them.
            "weights_bytes": sum(
                w.nbytes for w in self._jax.tree.leaves(self.params)),
            # (a tied embedding is the head).
            "weights_dtype": str(self.params[
                "lm_head" if "lm_head" in self.params
                else "tok_embed"].dtype),
            "pid": os.getpid(),
            **self._compile_watch.snapshot(),
        }

    def set_metrics_deployment(self, name: str) -> None:
        """Re-label this engine's SLO metrics (benches separate their
        warmup/compile phase from the measured phase this way; requests
        observe under the label current at their TERMINAL step)."""
        self._mtags = {"deployment": name}

    def timeline(self) -> Dict[str, Any]:
        """Step-timeline dump + engine identity: the payload behind the
        replica's ``engine_timeline`` RPC and the ``ray_tpu timeline
        --serve`` merge."""
        out = self.steplog.dump()
        out["deployment"] = self._mtags["deployment"]
        out["replica_id"] = self._replica_id
        out["slots"] = self.slots
        return out

    def _fragmentation(self) -> float:
        """Internal fragmentation of the page pool: the fraction of
        allocated page-token capacity not backing a live token. Pages
        are interchangeable, so EXTERNAL fragmentation is structurally
        zero — waste is partial tail pages and dead junk, and this is
        the number that says whether page_tokens is sized right."""
        if self._kind is None:
            return 0.0
        valid: Dict[int, int] = {}
        T = self.page_tokens
        rows = ([(s, r.prompt_len + r.generated)
                 for s, r in list(self._active.items())]
                + [(s, r.prefilled)
                   for s, r in list(self._prefilling.items())])
        for slot, length in rows:
            for i, page in enumerate(self._slot_pages[slot]):
                end = min(T, length - i * T)
                if end > 0:
                    valid[page] = max(valid.get(page, 0), end)
        if self.prefix is not None:
            # Prefix-pinned pages are always full by construction.
            for page in self.prefix.pinned_page_ids():
                valid[page] = T
        in_use = self._pages.in_use
        if not in_use:
            return 0.0
        used_tokens = sum(valid.values())
        return round(max(0.0, 1.0 - used_tokens / (in_use * T)), 4)


class LlamaDecodeDeployment:
    """Serve deployment wrapping a DecodeEngine: POST {"tokens": [...],
    "max_new_tokens": N} -> {"tokens": [...]} with streaming support
    (generator handle path). Replica-per-chip: schedule with
    ``ray_actor_options={"resources": {"TPU": 1}}``.

    The class serves whatever ``model_modules`` names: the llama family
    here, another family in a subclass that overrides it and nothing
    else (``DeepseekDecodeDeployment``)."""

    @staticmethod
    def model_modules():
        """``(model, decode)``: the module that has ``PRESETS`` and
        ``init_params(config, key)``, and the one the engine takes its
        programs from (``DecodeEngine``'s ``model``)."""
        from ray_tpu.models import llama, llama_decode

        return llama, llama_decode

    def __init__(self, preset: str = "debug", slots: int = 4,
                 capacity: int = 1024, seed: int = 0,
                 config=None,
                 prefix_pool_entries: Optional[int] = None,
                 prefix_match_min_tokens: Optional[int] = None,
                 queue_max: Optional[int] = None,
                 kv_page_tokens: Optional[int] = None,
                 kv_pool_pages: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 mesh_shape=None):
        t_entry = time.time()
        import jax

        from ray_tpu import tpu
        from ray_tpu.util.compile_cache import compile_watch

        llama, ld = self.model_modules()
        watch = compile_watch()  # counts the replica's compiles from its first
        tpu.init_devices(since=t_entry)
        cfg = config or llama.PRESETS[preset]
        self.cfg = cfg
        self._sub_slice: Optional[Dict[str, Any]] = None
        # A replica never updates its weights, so the float32 masters
        # serve nothing here: this process owns them, and each leaves the
        # device as soon as its compute-dtype copy exists.
        t0 = time.time()
        params = ld.compute_weights(
            llama.init_params(cfg, jax.random.key(seed)), cfg, donate=True)
        flightrec.record("setup.phase", phase="weights", t0=t0,
                         t1=time.time(), bytes=sum(
                             w.nbytes for w in jax.tree.leaves(params)))
        self.engine = DecodeEngine(
            params, cfg, slots=slots, capacity=capacity,
            prefix_pool_entries=prefix_pool_entries,
            prefix_match_min_tokens=prefix_match_min_tokens,
            queue_max=queue_max,
            page_tokens=kv_page_tokens, pool_pages=kv_pool_pages,
            prefill_chunk_tokens=prefill_chunk_tokens,
            mesh_shape=mesh_shape, model=ld)
        # Which rung a step takes follows the traffic, and a rung met
        # first under load is seconds of compile in one request's latency.
        self.engine.warm_decode()
        # Prefill->decode handoff lease ledger (disaggregated serving):
        # tracks published-but-undischarged KV-page handoffs so the TTL
        # sweep (riding replica_metrics) can return refs nobody claimed.
        from ray_tpu.serve.handoff import HandoffLedger

        self._handoffs = HandoffLedger()
        self._thread = threading.Thread(target=self.engine.serve_forever,
                                        name="decode-loop", daemon=True)
        self._thread.start()
        now, snap = time.time(), watch.snapshot()
        flightrec.record("setup.phase", phase="ready", t0=now, t1=now,
                         compiles=snap["compiles"],
                         compile_s=snap["compile_s"],
                         cache_hits=snap["cache_hits"])

    def set_topology(self, assignment: Dict[str, Any]) -> None:
        """Sub-slice assignment pushed by the serve controller after it
        reserved this replica's chips: advisory on the virtual CPU mesh
        (the process's devices ARE the slice), the device-selection
        input on real multi-host slices. Reported back through
        ``replica_metrics`` so status/routing see where the replica
        lives."""
        self._sub_slice = dict(assignment)

    def replica_metrics(self) -> Dict[str, Any]:
        """Replica-reported load + prefix residency + degradation
        counters, merged into ``ReplicaActor.stats()``: the autoscaler
        scales on decode backlog, the router steers shared prefixes to
        the replica already holding them, and ``serve.status()`` shows
        shedding/cancellation/deadline counts as they happen."""
        s = self.engine.stats()
        out: Dict[str, Any] = {"load": s["load"], "queued": s["queued"],
                               "shed": s["shed"],
                               "cancelled": s["cancelled"],
                               "deadline_exceeded": s["deadline_exceeded"],
                               "prefill_backlog_tokens":
                               s["prefill_backlog_tokens"],
                               "chips": s["chips"],
                               "mesh_shape": s["mesh_shape"],
                               "device": s["device"]}
        sub = getattr(self, "_sub_slice", None)  # tests build bare
        #   instances around an engine without running __init__
        if sub is not None:
            out["sub_slice"] = dict(sub)
            out["slice_id"] = sub.get("slice_id")
        # Page-pool health, controller-aggregated into serve.status():
        # free/pinned pages and fragmentation say whether the replica
        # can admit, what the prefix cache holds, and whether
        # page_tokens is sized right.
        for key in ("pages_total", "pages_free", "pages_in_use",
                    "pages_pinned", "kv_fragmentation", "preempted"):
            out[key] = s[key]
        if self.engine.prefix is not None:
            out["prefix"] = s.get("prefix", {})
            out["prefixes"] = self.engine.prefix.hashes()
        ledger = getattr(self, "_handoffs", None)
        if ledger is not None:
            # The controller's reconcile stats pull doubles as the
            # handoff-lease backstop: expire entries nobody discharged
            # (router death mid-splice) and free their refs.
            self._sweep_handoffs()
            out["handoffs_live"] = ledger.live()
            out["handoff_live_bytes"] = ledger.live_bytes()
            out["handoffs_published"] = s.get("handoffs_published", 0)
            out["handoffs_adopted"] = s.get("handoffs_adopted", 0)
        return out

    def timeline(self) -> Dict[str, Any]:
        """Engine step-timeline dump (ReplicaActor.engine_timeline
        forwards here; merged into the serve Chrome trace)."""
        return self.engine.timeline()

    def _submit(self, request: Dict[str, Any], on_token=None,
                prefill_only: bool = False,
                adopt: Optional[Dict[str, Any]] = None,
                on_done=None) -> _Request:
        """Admission with the request's deadline attached: explicit
        ``deadline_s`` in the payload wins, else the deadline the serve
        stack propagated with this call (proxy header / handle
        timeout_s / ``serve_request_timeout_s``)."""
        from ray_tpu.serve.replica import request_deadline_s

        deadline_s = request.get("deadline_s")
        if deadline_s is None:
            deadline_s = request_deadline_s()
        return self.engine.submit(
            request["tokens"],
            max_new_tokens=int(request.get("max_new_tokens", 32)),
            temperature=float(request.get("temperature", 0.0)),
            eos_id=request.get("eos_id"),
            on_token=on_token,
            deadline_s=deadline_s,
            request_id=request.get("request_id"),
            prefill_only=prefill_only,
            adopt=adopt,
            on_done=on_done)

    def _wait_done(self, req: _Request) -> None:
        """Block until the engine finishes the request; a wedged decode
        loop (never-completing wait) turns into a cancel + deadline
        error rather than hanging the replica thread forever."""
        if req.deadline is not None:
            # The engine enforces the deadline; the +10 s slack only
            # covers a wedged decode loop (never-completing wait).
            if not req.done.wait(
                    max(0.1, req.deadline - time.monotonic()) + 10.0):
                self.engine.cancel(req.request_id)
                raise DeadlineExceededError(
                    f"request {req.request_id} not finished by the decode "
                    f"loop within its deadline")
        else:
            req.done.wait()

    def __call__(self, request: Dict[str, Any]):
        if request.get("stream"):
            # Generator return = the replica streams it (handle.stream /
            # HTTP chunked via X-Serve-Stream on this same route).
            return self.stream(request)
        req = self._submit(request)
        self._wait_done(req)
        req.raise_for_status()
        return {"tokens": req.output,
                "ttft_s": round(req.first_token_at - req.submitted_at, 4)}

    # --------------------------------------- disaggregated prefill/decode

    def prefill_handoff(self, request: Dict[str, Any]) -> Dict[str, Any]:
        """Disagg prefill half: run admission + (chunked) prefill into
        this engine's paged pool, then publish the filled KV pages as
        object-plane refs plus a descriptor small enough to ride the
        router splice inline (budget: ``HANDOFF_DESC_BYTE_BUDGET``).

        The returned descriptor is a LEASE: the caller must either
        adopt-ack it (``discharge_handoff``) or abort it
        (``abort_handoff``) on every path; the ledger's TTL sweep is
        the backstop for a caller that died mid-splice, and a SIGKILL
        of this replica frees the refs structurally (objects die with
        their owner process)."""
        import uuid as _uuid

        import ray_tpu

        req = self._submit(request, prefill_only=True)
        self._wait_done(req)
        req.raise_for_status()
        payload = req.handoff
        if payload is None:  # engine retired the request pre-capture
            raise RuntimeError(
                f"prefill request {req.request_id} completed without a "
                f"handoff payload")
        desc = {
            "handoff_id": _uuid.uuid4().hex[:16],
            **{f"{name}_ref": ray_tpu.put(payload[name])
               for name in self.engine._pool_names},
            "committed_len": payload["committed_len"],
            "first_token": payload["first_token"],
            "page_tokens": payload["page_tokens"],
            "nbytes": payload["nbytes"],
            "prefill_ttft_s": round(
                req.first_token_at - req.submitted_at, 4),
        }
        req.handoff = None  # the object store owns the payload now
        self._handoffs.publish_handoff(desc)
        try:
            self._observe_handoff_published(desc)
        except BaseException:
            # The lease must not outlive a failed publish tail: hand the
            # refs back before the error escapes (graftlint polices the
            # publish->discharge pairing on every raise exit).
            self._drop_handoff(desc["handoff_id"], "aborted")
            raise
        return desc

    def discharge_handoff(self, handoff_id: str) -> None:
        """Adopt-ack from the router splice: the decode replica fetched
        the page payload, so free the refs NOW (one engine step), not
        at the distributed ref tracker's grace sweep."""
        self._drop_handoff(handoff_id, "adopted")

    def abort_handoff(self, handoff_id: str) -> None:
        """Splice failure (decode replica died / cannot adopt / request
        cancelled): return the pages now. Idempotent, like discharge."""
        self._drop_handoff(handoff_id, "aborted")

    def _drop_handoff(self, handoff_id: str, event: str) -> None:
        """Discharge one published handoff and free its payload refs
        eagerly. Idempotent — the router's abort path and the TTL sweep
        may race, and the ledger referees the double discharge."""
        entry = self._handoffs.discharge_handoff(handoff_id)
        if entry is not None:
            self._discharge_entry(entry, event)

    def _sweep_handoffs(self) -> None:
        for entry in self._handoffs.sweep():
            self._discharge_entry(entry, "expired")

    def _discharge_entry(self, entry: Dict[str, Any],
                         event: str) -> None:
        import ray_tpu

        desc = entry["desc"]
        try:
            ray_tpu.free([desc.get(f"{name}_ref")
                          for name in self.engine._pool_names])
        except Exception:
            logger.warning("freeing handoff %s refs failed",
                           desc.get("handoff_id"), exc_info=True)
        if self.engine._obs_metrics:
            from ray_tpu.serve import metrics as smetrics

            tags = dict(self.engine._mtags)
            smetrics.HANDOFFS.inc(1.0, {**tags, "event": event})
            if event == "adopted":
                # publish->adopt latency: the window the pages spent as
                # host blobs between the two fleets.
                smetrics.HANDOFF_LATENCY.observe(entry["age_s"], tags)

    def _observe_handoff_published(self, desc: Dict[str, Any]) -> None:
        if not self.engine._obs_metrics:
            return
        from ray_tpu.serve import handoff as _handoff
        from ray_tpu.serve import metrics as smetrics

        tags = dict(self.engine._mtags)
        smetrics.HANDOFF_BYTES.observe(
            float(_handoff.descriptor_nbytes(desc)), tags)
        smetrics.HANDOFFS.inc(1.0, {**tags, "event": "published"})

    def _fetch_adopt(self, desc: Dict[str, Any]) -> Dict[str, Any]:
        """Pull the handed-off page payload out of the object plane and
        shape it as the engine's adopt argument. A dead prefill replica
        (refs died with their owner) surfaces as the typed adopt error
        the router maps to re-prefill."""
        import ray_tpu
        from ray_tpu.core.errors import HandoffAdoptError
        from ray_tpu.serve.replica import request_deadline_s

        timeout = request_deadline_s() or 30.0
        try:
            names = self.engine._pool_names
            pages = ray_tpu.get([desc[f"{name}_ref"] for name in names],
                                timeout=max(1.0, timeout))
        except Exception as e:
            raise HandoffAdoptError(
                f"handoff {desc.get('handoff_id')} page payload "
                f"unavailable: {e!r}") from e
        return {**dict(zip(names, pages)),
                "committed_len": desc["committed_len"],
                "first_token": desc["first_token"],
                "page_tokens": desc["page_tokens"]}

    def decode_adopted(self, request: Dict[str, Any],
                       desc: Dict[str, Any]) -> Dict[str, Any]:
        """Disagg decode half (unary): adopt the published pages into
        this engine's pool — zero recompute — and decode to completion.
        The prompt's KV never transits Python bytes-concat: page blobs
        go object-store -> scatter program -> pool."""
        req = self._submit(request, adopt=self._fetch_adopt(desc))
        self._wait_done(req)
        req.raise_for_status()
        return {"tokens": req.output,
                "ttft_s": desc.get("prefill_ttft_s", 0.0)}

    def stream_adopted(self, request: Dict[str, Any],
                       desc: Dict[str, Any]):
        """Streaming twin of ``decode_adopted``. Adoption (object-plane
        fetch + engine submit) runs in this call, like every stream's
        submit, so the replica's synchronous ``start_stream`` surfaces
        adopt failures as retryable call errors and the router can
        discharge the prefill lease the moment the stream id comes
        back."""
        return self._token_stream(request, adopt=self._fetch_adopt(desc))

    def stream(self, request: Dict[str, Any]):
        """The request's tokens as the engine emits them (drive via a
        streaming handle / HTTP chunked response). The request is
        submitted in this call: a shed or an expired deadline raises
        here, before any stream exists. Closing the stream (client
        disconnect anywhere up the stack) cancels the engine request:
        the slot frees at the next step and queued-but-unadmitted
        requests never touch the device."""
        return self._token_stream(request)

    def _token_stream(self, request: Dict[str, Any],
                      adopt: Optional[Dict[str, Any]] = None):
        """One engine request behind a ``StreamQueue`` that the engine's
        thread fills: ``on_token`` puts, and ``on_done`` ends it on every
        way the request can end, with the typed error of a mid-stream
        deadline/cancel instead of a silently truncated stream. So the
        consumer sees a token the moment it exists and the end together
        with the last one."""
        from ray_tpu.serve.replica import StreamQueue

        out = StreamQueue()

        def ended(req: _Request) -> None:
            out.end(RuntimeError(f"on_token failed: {req.on_token_error}")
                    if req.on_token_error else req.terminal_error())

        req = self._submit(request, on_token=out.put, on_done=ended,
                           adopt=adopt)
        out.on_close = lambda: (req.done.is_set()
                                or self.engine.cancel(req.request_id))
        # For an ending that forgets to tell: ``done`` is set, no hook ran.
        out.backstop = lambda: req.done.is_set() and ended(req)
        out.on_record = lambda record: self._stream_ended(req, record)
        return out

    def _stream_ended(self, req: _Request, record: Dict[str, Any]) -> None:
        """A stream's closed record (``StreamQueue``) joined with its
        request's clocks, once: to the step log as a ``stream-end`` event
        (through the engine's inbox: this is a pull's thread) and to the
        request's trace as the span ``stream``, delivery beside
        ``decode``."""
        # mono -> wall. A thread switch between the two reads makes the
        # difference too small, never too large: the largest of three.
        off = max(time.time() - time.monotonic() for _ in range(3))
        record.update(
            request=req.request_id,
            # A stream closed before its request ended is being cancelled.
            outcome=req.status if req.done.is_set() else "cancelled",
            submitted=req.submitted_at + off,
            admitted=(None if req.admitted_at is None
                      else req.admitted_at + off))
        self.engine.post_event("stream-end", **record)
        if req.trace is not None and record["first_put"] is not None:
            from ray_tpu.util import tracing

            tracing.record_span(
                "stream", record["first_put"],
                record["last_ack"] or record["last_put"], ctx=req.trace,
                request=req.request_id, items=record["items"],
                pulls=record["pulls"],
                deliver_s_max=record["deliver_s_max"])

    def health(self) -> Dict[str, Any]:
        return self.engine.stats()


class DeepseekDecodeDeployment(LlamaDecodeDeployment):
    """The same deployment over DeepSeek-V2 (``models/deepseek.py``): a
    latent paged pool, absorbed decode, held experts. The model has no
    ``shard_decode_state``, so a mesh is refused by the engine."""

    @staticmethod
    def model_modules():
        from ray_tpu.models import deepseek, deepseek_decode

        return deepseek, deepseek_decode


class MimoDecodeDeployment(LlamaDecodeDeployment):
    """The same deployment over MiMo-V2 (``models/mimo.py``): full and
    window layers over two kinds of page, held experts behind a sigmoid
    router. The model has no ``shard_decode_state``, so a mesh is refused
    by the engine; its window kind turns the prefix index off."""

    @staticmethod
    def model_modules():
        from ray_tpu.models import mimo, mimo_decode

        return mimo, mimo_decode


class Phi4FlashDecodeDeployment(LlamaDecodeDeployment):
    """The same deployment over Phi-4-mini-flash (``models/phi4flash.py``):
    a full and a window kind of page, a recurrent state a slot, one full
    layer's keys and values read by the cross-attention layers. The model
    has no ``shard_decode_state``, so a mesh is refused by the engine, and
    a handoff by ``submit``; its window kind and its state turn the prefix
    index off."""

    @staticmethod
    def model_modules():
        from ray_tpu.models import phi4flash, phi4flash_decode

        return phi4flash, phi4flash_decode


class Cohere2MoeDecodeDeployment(LlamaDecodeDeployment):
    """The same deployment over Command A+ (``models/cohere2_moe.py``):
    parallel blocks, full and window(4,096) layers over two kinds of page
    of one size, held experts behind a sigmoid router beside averaged
    shared ones. The model has no ``shard_decode_state``, so a mesh is
    refused by the engine; its window kind turns the prefix index off."""

    @staticmethod
    def model_modules():
        from ray_tpu.models import cohere2_moe, cohere2_moe_decode

        return cohere2_moe, cohere2_moe_decode


class BrumbyDecodeDeployment(LlamaDecodeDeployment):
    """The same deployment over Brumby (``models/brumby.py``): every layer
    a power-retention layer, whose only cache is a state a slot. The model
    names NO page kind, so the engine builds no allocator, table or view
    for it and its slots alone bind admission; it has no
    ``shard_decode_state``, so a mesh is refused by the engine, and a
    handoff by ``submit``; its state turns the prefix index off."""

    @staticmethod
    def model_modules():
        from ray_tpu.models import brumby, brumby_decode

        return brumby, brumby_decode


class NemotronHDecodeDeployment(LlamaDecodeDeployment):
    """The same deployment over Nemotron-H (``models/nemotron_h.py``):
    layers of ONE part each, Mamba-2 with a matrix state a slot, attention
    over one full kind of page, held experts in a latent width behind a
    sigmoid router. The model has no ``shard_decode_state``, so a mesh is
    refused by the engine, and a handoff by ``submit``; its state turns
    the prefix index off."""

    @staticmethod
    def model_modules():
        from ray_tpu.models import nemotron_h, nemotron_h_decode

        return nemotron_h, nemotron_h_decode
