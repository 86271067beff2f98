"""The one traffic generator: every mix is a data file of parameters.

A serving mix fixes a MULTISET of (prompt, answer) lengths, the stratified
quantiles of its length distributions, which is the same for every seed.
The seed only permutes the order, jitters the due times and draws the token
ids, so two seeds offer the same work in another order. Open-loop arrivals
are paced: request ``i`` is due at ``(i + u_i) / rate`` with ``u_i`` uniform
in [0, 1), one arrival per interval. Nothing here imports JAX.
"""

from __future__ import annotations

import json
import math
import os
import random
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Sequence, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, root: str = _HERE) -> Dict:
    """The traffic file ``traffic/<name>.json`` under ``root``."""
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def stratified_lengths(dist: Dict, n: int) -> List[int]:
    """``n`` lengths at the mid-quantiles ``(j + 0.5) / n`` of ``dist``,
    clipped to its ``min``/``max``: the same list for every seed."""
    if n <= 0:
        return []
    kind = dist["dist"]
    if kind == "fixed":
        return [int(dist["value"])] * n
    if kind != "lognormal":
        raise ValueError(f"unknown length distribution {kind!r}")
    mu, sigma = math.log(dist["median"]), float(dist["sigma"])
    std = NormalDist()
    out = []
    for j in range(n):
        x = math.exp(mu + sigma * std.inv_cdf((j + 0.5) / n))
        out.append(int(min(dist["max"], max(dist["min"], round(x)))))
    return out


def length_pairs(prompt: Dict, answer: Dict, n: int,
                 rng: random.Random) -> List[Tuple[int, int]]:
    """``n`` (prompt, answer) pairs: each marginal is its stratified
    multiset, and the pairing of the two is one fixed shuffle (seed 0 of
    its own generator), so the multiset of PAIRS is also the same for every
    seed. ``rng`` only permutes the order in which the pairs are sent."""
    prompts = stratified_lengths(prompt, n)
    answers = stratified_lengths(answer, n)
    random.Random(0).shuffle(answers)
    pairs = list(zip(prompts, answers))
    rng.shuffle(pairs)
    return pairs


def paced_due_times(n: int, rate: float, rng: random.Random,
                    first_interval: int = 0) -> List[float]:
    """Due time of request ``i``: ``(first_interval + i + u_i) / rate``."""
    return [(first_interval + i + rng.random()) / rate for i in range(n)]


@dataclass
class Request:
    index: int
    phase: str            # lead_in | window | drain
    due_s: float          # open loop: offset from schedule start
    prompt_len: int
    answer_len: int
    token_seed: int       # the prompt's ids are drawn from this

    def tokens(self, vocab: int) -> List[int]:
        rng = random.Random(self.token_seed)
        return [rng.randrange(vocab) for _ in range(self.prompt_len)]


def open_loop_schedule(mix: Dict, seed: int, window_s: float
                       ) -> List[Request]:
    """Lead-in, window and drain of one open-loop run. The window opens at
    ``lead_in_s`` after the schedule starts and holds exactly
    ``round(rate * window_s)`` requests, whose multiset does not depend on
    the seed; lead-in and drain have a multiset of their own."""
    rate = float(mix["rate_rps"])
    rng = random.Random(seed)
    n_lead = round(rate * mix["lead_in_s"])
    n_win = round(rate * window_s)
    n_drain = round(rate * mix["drain_s"])
    out: List[Request] = []
    first = 0
    for phase, n in (("lead_in", n_lead), ("window", n_win),
                     ("drain", n_drain)):
        pairs = length_pairs(mix["prompt"], mix["answer"], n, rng)
        dues = paced_due_times(n, rate, rng, first)
        for (p, a), due in zip(pairs, dues):
            out.append(Request(len(out), phase, due, p, a,
                               rng.getrandbits(48)))
        first += n
    return out


def closed_loop_requests(mix: Dict, seed: int) -> List[Request]:
    """The fixed multiset a closed loop draws from, in the seed's order;
    clients take the next one when their last request ends and the list
    wraps round if a run outlasts it."""
    rng = random.Random(seed)
    pairs = length_pairs(mix["prompt"], mix["answer"], int(mix["requests"]),
                         rng)
    return [Request(i, "closed", 0.0, p, a, rng.getrandbits(48))
            for i, (p, a) in enumerate(pairs)]


def _pow2(n: int, minimum: int) -> int:
    c = minimum
    while c < n:
        c *= 2
    return c


def prefill_programs(n: int, chunk: int, page: int) -> set:
    """The prefill programs one prompt of ``n`` tokens runs on the paged
    engine, as ``serve/decode.py`` buckets them: a prompt that fits one
    chunk is one whole prefill in a power-of-two bucket (>= 128); a longer
    one goes chunk by chunk, each chunk a (bucket, width) pair where the
    bucket is the power of two (>= 16) over the chunk's tokens and the
    width the power of two over the pages that prefix + bucket cover. A
    wrong guess here costs compiles inside the window, which
    ``compiles_in_window.*`` reports."""
    if not chunk or n <= chunk:
        return {("full", _pow2(n, 128))}
    out, done = set(), 0
    while done < n:
        step = min(chunk, n - done)
        bucket = min(_pow2(step, 16), chunk)
        out.add((bucket, _pow2(-(-(done + bucket) // page), 1)))
        done += step
    return out


def warm_lengths(lengths: Sequence[int], chunk: int, page: int
                 ) -> List[int]:
    """Prompt lengths the set-up sends once so that the window compiles
    nothing: every program the traffic's own lengths run, and for every
    block-table width they reach every chunk bucket as well (a request
    preempted for pages comes back with its answer so far added to its
    prompt, at a length the multiset does not hold). Greedy cover, shortest
    prompts first."""
    needed = set()
    for n in lengths:
        needed |= prefill_programs(n, chunk, page)
    widths = {k[1] for k in needed if k[0] != "full"}
    b = 16
    while chunk and b <= chunk:
        needed |= {(b, w) for w in widths if w * page > chunk}
        b *= 2
    candidates = set(lengths)
    if chunk:
        top = max(lengths)
        for done in range(chunk, top + 1, chunk):
            b = 16
            while b <= chunk:
                if done + b <= top + chunk:
                    candidates.add(done + b)
                b *= 2
    out: List[int] = []
    for n in sorted(candidates):
        new = prefill_programs(n, chunk, page) & needed
        if new:
            out.append(n)
            needed -= new
    return out


def resumed_prefills(longest: int, chunk: int, page: int, have: set
                     ) -> List[Tuple[int, int]]:
    """(cached prefix, suffix) lengths that between them run every prefill
    program through which a request preempted for pages can come back and
    that ``have`` (a set of ``prefill_programs``) lacks. The prefix index
    keeps a prompt's pages up to the largest power of two within it, so
    the request returns with a cached prefix that is a power of two of at
    least one page, and prefills the rest (its prompt's tail and its
    answer so far), a chunk at most at a time: a bucket from 16 to the
    chunk at the block-table width that prefix + bucket cover. ``longest``
    is the most a request can hold, prompt and answer. Above the knee
    preemption is the rule, and a program met first inside the window
    compiles there."""
    out, seen = [], set(have)
    prefix = page
    while prefix < longest:
        bucket = 16
        # A suffix of ``bucket // 2 + 1`` tokens already runs ``bucket``.
        while bucket <= chunk and prefix + (bucket > 16) * bucket // 2 \
                < longest:
            program = (bucket, _pow2(-(-(prefix + bucket) // page), 1))
            if program not in seen:
                seen.add(program)
                out.append((prefix, min(bucket, longest - prefix)))
            bucket *= 2
        prefix *= 2
    return out
