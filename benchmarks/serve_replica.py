"""The deployment the serve cells run: the program's own deployment class,
which the cell's family names (``families/<family>.py``, ``Serve``), plus
the hooks a measurement needs inside the process that holds the chip. It
adds no behaviour to a request's path except a lock that ``engine.step``
takes (uncontended outside set-up) and, in a traced run, a
``TraceAnnotation`` round each step."""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List

from benchmarks import families


def bench_deployment(family: str):
    """The family's deployment class under the hooks below. The class is
    made here, per family, and goes to the replica by value (the runtime
    ships a deployment's class with cloudpickle); ``bench_family`` tells
    the replica whose reference checks its answers."""
    base = families.load(family).Serve.deployment_class()
    return type(f"Bench{base.__name__}", (BenchDecodeDeployment, base),
                {"bench_family": family})


class BenchDecodeDeployment:
    """The hooks: a mix-in in front of the program's deployment class."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._bench_gate = threading.Lock()
        self._bench_traced = False
        self._bench_rows: List[Dict[str, Any]] = []
        self._bench_last_row_t0 = 0.0
        self._bench_requests: Dict[str, Any] = {}
        inner = self.engine.step

        def step():
            with self._bench_gate:
                if not self._bench_traced:
                    return inner()
                import jax

                with jax.profiler.TraceAnnotation("bench:engine_step"):
                    n = inner()
                self._bench_keep_rows()
                return n

        # serve_forever looks ``self.step`` up at every call, so the
        # instance attribute takes over from the next step on.
        self.engine.step = step

    # ------------------------------------------------------------ set-up

    def _bench_admit(self, prompts: List[List[int]]) -> None:
        """``prompts`` as ONE admission wave (the step gate is held while
        they are queued), two tokens each, waited for."""
        with self._bench_gate:
            reqs = [self.engine.submit(p, max_new_tokens=2) for p in prompts]
        for req in reqs:
            if not req.done.wait(600):
                raise TimeoutError("warm-up request did not finish")
            req.raise_for_status()

    def bench_warm(self, groups: List[List[int]], vocab: int) -> Dict:
        """Send each group of prompt lengths as ONE admission wave, so
        that every prefill shape of the cell's traffic has run once."""
        import random

        rng = random.Random(0)
        for group in groups:
            self._bench_admit([[rng.randrange(vocab) for _ in range(n)]
                               for n in group])
        return self.engine.device_stats()

    def bench_warm_resumed(self, pairs: List[List[int]], vocab: int) -> None:
        """For each (prefix, suffix) of ``traffic.resumed_prefills``: a
        prompt whose first ``prefix`` tokens are in the prefix index and
        whose last ``suffix`` are new, as a request preempted for pages
        comes back. One long prompt goes first and puts every prefix into
        the index; the longest prefixes follow at once, before other
        entries push its tail out."""
        import random

        rng = random.Random(1)
        base = [rng.randrange(vocab) for _ in range(max(p for p, _ in pairs))]
        self._bench_admit([base])
        for prefix, suffix in sorted(pairs, reverse=True):
            self._bench_admit([base[:prefix] + [rng.randrange(vocab)
                                                for _ in range(suffix)]])

    # ------------------------------------------------------- measurement

    def _submit(self, request, *args, **kwargs):
        req = super()._submit(request, *args, **kwargs)
        rid = request.get("request_id")
        if rid:
            self._bench_requests[rid] = req
        return req

    def _bench_keep_rows(self) -> None:
        """The step log is a ring of 256 rows: copy the new ones out."""
        for row in self.engine.steplog.dump()["rows"]:
            if row["t0"] > self._bench_last_row_t0:
                self._bench_rows.append(row)
                self._bench_last_row_t0 = row["t0"]

    def bench_mark(self) -> Dict:
        """Counters at an edge of the window (differences are taken by the
        caller), on this process's clocks."""
        s = self.engine.stats()
        return {"monotonic": time.monotonic(), "wall": time.time(),
                "compiles": s["device"]["compiles"],
                "compile_s": s["device"]["compile_s"],
                "preempted": s["preempted"], "steps": s["steps"],
                "active": s["active"], "queued": s["queued"],
                "peak_bytes_in_use": s["device"]["peak_bytes_in_use"],
                "platform": s["device"]["platform"],
                "device_kind": s["device"]["device_kind"],
                "device_count": s["device"]["device_count"]}

    def bench_trace(self, on: bool, trace_dir: str = "") -> None:
        """Start or stop the profiler."""
        import jax

        if on:
            jax.profiler.start_trace(trace_dir)
        else:
            jax.profiler.stop_trace()

    def bench_keep_steps(self) -> None:
        """From now on keep every step-log row and annotate every step."""
        self._bench_rows = []
        self._bench_traced = True

    def bench_dump(self) -> Dict:
        """Step rows kept while traced, and every request's engine clocks
        (host monotonic) by ``request_id``."""
        clocks = {
            rid: [r.submitted_at, r.admitted_at, r.first_token_at,
                  r.finished_at, r.preemptions]
            for rid, r in self._bench_requests.items()}
        return {"rows": self._bench_rows, "clocks": clocks}

    # -------------------------------------------------------- correctness

    def bench_reference_margins(self, prompts: List[List[int]],
                                answers: List[List[int]]) -> List[float]:
        """Teacher-force the plain float32 reference on prompt + answer
        with THIS replica's weights; for every served token return how far
        its reference logit lies below that position's maximum."""
        serve = families.load(self.bench_family).Serve
        return serve.reference_margins(self.engine.params, self.cfg,
                                       prompts, answers)
