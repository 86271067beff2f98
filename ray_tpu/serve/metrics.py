"""Serve-plane SLO instruments + the one summary the surfaces share.

The SLOs TPU serving is judged by are latency DISTRIBUTIONS — TTFT and
time-per-output-token — not point gauges ("Fine-Tuning and Serving
Gemma on Cloud TPU", PAPERS.md). This module registers them as
``util.metrics`` Counter/Histogram instruments labeled by deployment,
recorded by the decode engine / router / proxy per REQUEST (never per
token or per step — the decode loop must not pay a registry lock per
step), flushed through the existing per-process metrics flusher to the
cluster controller, and read back identically by:

* the HTTP proxy's ``/metrics`` route (Prometheus exposition text),
* ``serve.status()``'s per-deployment ``slo`` summaries,
* the dashboard's serve panel.

One registry, one aggregation path (``slo_summary``), one answer.
"""

from __future__ import annotations

from typing import Any, Dict, List

from ray_tpu.util.metrics import (Counter, Gauge, Histogram, counter_totals,
                                  histogram_summary, merge_histograms)

# Latency grids sized for decode serving: TTFT spans admission-queue
# waits (ms) through multi-second prefill backlogs; inter-token spans
# sub-ms TPU steps through seconds of CPU-host steps.
_TTFT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)
_TOKEN_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                  0.1, 0.25, 0.5, 1.0, 2.5)
_HTTP_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                 5.0, 10.0, 30.0, 60.0)

TTFT = Histogram(
    "serve_ttft_s",
    "Time to first token: engine submit -> first emitted token "
    "(includes queue wait and prefill). The ENGINE's interval: it "
    "leaves out what comes before the submit (HTTP read, routing, the "
    "start_stream call: serve_ingress_s) and after the emit (the "
    "stream's wake-up, the reply and the socket write: "
    "serve_stream_delivery_s).",
    boundaries=_TTFT_BUCKETS, tag_keys=("deployment",))

INTER_TOKEN = Histogram(
    "serve_inter_token_s",
    "Per-output-token latency of one request's stream: (finish - first "
    "token) / (tokens - 1), observed once per completed request "
    "(robust to chunked emission's bursty raw gaps).",
    boundaries=_TOKEN_BUCKETS, tag_keys=("deployment",))

QUEUE_WAIT = Histogram(
    "serve_queue_wait_s",
    "Engine admission-queue wait: submit -> prefill dispatch.",
    boundaries=_TTFT_BUCKETS, tag_keys=("deployment",))

HTTP_LATENCY = Histogram(
    "serve_http_request_s",
    "HTTP proxy request latency (headers in -> response written), "
    "labeled by resolved deployment.",
    boundaries=_HTTP_BUCKETS, tag_keys=("deployment",))

STREAM_PULL_ITEMS = Histogram(
    "serve_stream_pull_items",
    "Items one next_chunks reply carried from a replica's stream to the "
    "router: ~1 when the consumer keeps up with the producer (one token "
    "a decode step), up to the delivery cap (16) when the producer is "
    "ahead. Recorded once per stream, when it ends, from the stream's "
    "record: one observation a reply, each at the stream's mean (count "
    "and sum are exact, a quantile is one of streams' means).",
    boundaries=(1, 2, 4, 8, 15, 16), tag_keys=("deployment",))

STREAM_DELIVERY = Histogram(
    "serve_stream_delivery_s",
    "One delivery of a stream, from the put of its oldest item on the "
    "replica to the consumer's acknowledgement (the router is back for "
    "more: for the HTTP proxy, after the socket write): the wake-up of "
    "the pull, the reply and the write. Recorded once per stream from "
    "its record: one observation an acknowledged delivery, the slowest "
    "at its own time and the others at their mean (count and sum are "
    "exact). The last delivery of a stream is never acknowledged.",
    boundaries=_TOKEN_BUCKETS, tag_keys=("deployment",))

INGRESS = Histogram(
    "serve_ingress_s",
    "What a streamed request spends before the engine has it: the "
    "start of its root span in the calling process (the proxy's "
    "http:<route>) -> engine submit. HTTP read and parse, routing, the "
    "start_stream actor call. Needs serve_trace_spans and one clock "
    "across the two processes (one host).",
    boundaries=_TTFT_BUCKETS, tag_keys=("deployment",))

REQUESTS = Counter(
    "serve_requests_total",
    "Engine request outcomes: completed | cancelled | deadline_exceeded "
    "| shed | error.",
    tag_keys=("deployment", "outcome"))

HTTP_REQUESTS = Counter(
    "serve_http_requests_total",
    "HTTP proxy responses by status code.",
    tag_keys=("deployment", "code"))

RETRIES = Counter(
    "serve_router_retries_total",
    "Router retries after replica death (attempts beyond the first).",
    tag_keys=("deployment",))

PREEMPTIONS = Counter(
    "serve_preemptions_total",
    "Engine recompute-preemptions under page pressure.",
    tag_keys=("deployment",))

# Disaggregated prefill/decode handoff (ROADMAP #3). Descriptor bytes
# prove the handoff rides the object plane by reference: the descriptor
# is block-table metadata (~hundreds of bytes), never the KV payload
# itself — a descriptor past a few KiB means someone inlined pages.
# Latency is publish -> adopt (the lease's open interval); the counter's
# event tag closes the books: published == adopted + aborted + expired
# at quiescence, anything else is a leaked lease.
_HANDOFF_BYTE_BUCKETS = (128.0, 256.0, 512.0, 1024.0, 2048.0, 4096.0,
                         8192.0, 16384.0, 65536.0)

HANDOFF_BYTES = Histogram(
    "serve_handoff_bytes",
    "Pickled size of one prefill->decode handoff descriptor (block-table "
    "metadata + ObjectRefs, NOT the KV payload).",
    boundaries=_HANDOFF_BYTE_BUCKETS, tag_keys=("deployment",))

HANDOFF_LATENCY = Histogram(
    "serve_handoff_latency_s",
    "KV-page handoff lease lifetime: publish on the prefill replica -> "
    "adopt acknowledged by the decode side.",
    boundaries=_TTFT_BUCKETS, tag_keys=("deployment",))

HANDOFFS = Counter(
    "serve_handoffs_total",
    "KV-page handoff lease events: published | adopted | aborted | "
    "expired. published - (adopted + aborted + expired) is the number "
    "of leases currently open; nonzero at quiescence means leaked "
    "pages/refs.",
    tag_keys=("deployment", "event"))

PENDING_RELEASES = Gauge(
    "serve_pending_subslice_releases",
    "Sub-slice release RPCs awaiting retry after a head blip "
    "(ServeController._pending_releases depth — growth means chips are "
    "stranded until the reconcile loop gets through).")

CONTROLLER_EPOCH = Gauge(
    "serve_controller_epoch",
    "Monotonic serve-controller epoch (bumped on every controller "
    "(re)start via the core epoch lease). A delta >= 2 over a doctor "
    "window means the controller is crash-looping "
    "(controller-flapping); the max across sources is the OWNING epoch "
    "replicas are checked against.")

REPLICA_EPOCH = Gauge(
    "serve_replica_epoch",
    "The controller epoch that owns this replica (assigned at spawn, "
    "re-pushed at adoption). A replica whose epoch stays below the "
    "live controller epoch — or that reports with no controller series "
    "at all — is serving traffic nobody reconciles (orphan-replica).",
    tag_keys=("deployment",))



def observe_stream(record: Dict[str, Any], tags: Dict[str, str]) -> None:
    """The three series a closed stream record feeds (``StreamQueue``'s;
    ``submitted`` is there when an engine joined its clocks)."""
    pulls, acked = record["pulls"], record["acked"]
    STREAM_PULL_ITEMS.observe_many([record["items"] / pulls] * pulls, tags)
    if acked:
        worst = record["deliver_s_max"]
        rest = (record["deliver_s_sum"] - worst) / max(1, acked - 1)
        STREAM_DELIVERY.observe_many([worst] + [rest] * (acked - 1), tags)
    received, submitted = record["received"], record.get("submitted")
    if received is not None and submitted is not None:
        INGRESS.observe(submitted - received, tags)


# Outcomes worth a counter key even at zero; keeps dashboards stable.
OUTCOMES = ("completed", "cancelled", "deadline_exceeded", "shed", "error")

_HISTOGRAMS = {
    "ttft_s": "serve_ttft_s",
    "inter_token_s": "serve_inter_token_s",
    "queue_wait_s": "serve_queue_wait_s",
    "http_request_s": "serve_http_request_s",
    "stream_pull_items": "serve_stream_pull_items",
    "stream_delivery_s": "serve_stream_delivery_s",
    "ingress_s": "serve_ingress_s",
    "handoff_bytes": "serve_handoff_bytes",
    "handoff_latency_s": "serve_handoff_latency_s",
}


def slo_summary(aggregated: Dict[str, List[Dict[str, Any]]]
                ) -> Dict[str, Dict[str, Any]]:
    """Per-deployment SLO view from the controller's aggregated metrics
    (``list_metrics``): histogram summaries (count/mean/p50/p99) for
    TTFT, inter-token, queue-wait and HTTP latency, plus outcome /
    retry / preemption counter totals. The single source of truth
    behind ``serve.status()``, the dashboard serve panel and the bench
    percentile rows."""
    out: Dict[str, Dict[str, Any]] = {}

    def rec(deployment: str) -> Dict[str, Any]:
        return out.setdefault(deployment, {})

    for field, name in _HISTOGRAMS.items():
        for key, entry in merge_histograms(aggregated, name).items():
            dep = dict(key).get("deployment", "-")
            rec(dep)[field] = histogram_summary(entry)
    for key, total in counter_totals(aggregated,
                                     "serve_requests_total").items():
        tags = dict(key)
        dep = tags.get("deployment", "-")
        rec(dep).setdefault("outcomes", {})[
            tags.get("outcome", "?")] = int(total)
    for name, field in (("serve_router_retries_total", "retries"),
                        ("serve_preemptions_total", "preempted"),
                        ("serve_http_requests_total", "http_responses")):
        for key, total in counter_totals(aggregated, name).items():
            tags = dict(key)
            dep = tags.get("deployment", "-")
            if name == "serve_http_requests_total":
                rec(dep).setdefault(field, {})[
                    tags.get("code", "?")] = int(total)
            else:
                rec(dep)[field] = rec(dep).get(field, 0) + int(total)
    for key, total in counter_totals(aggregated,
                                     "serve_handoffs_total").items():
        tags = dict(key)
        dep = tags.get("deployment", "-")
        rec(dep).setdefault("handoffs", {})[
            tags.get("event", "?")] = int(total)
    return out
