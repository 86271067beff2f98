"""KV-page handoff ledger: the prefill->decode lease of disaggregated
serving.

Disaggregated serving (ROADMAP #3) splits a request across two replica
fleets: a PREFILL replica absorbs the prompt into its paged pool, then
publishes the filled KV pages as object-plane ObjectRefs plus a few
hundred bytes of descriptor; a DECODE replica adopts the pages into its
own pool and streams from the first decode step. Between publish and
adopt the pages live as host-side object-store blobs owned by the
prefill replica's process — this ledger is the accounting for that
window (the serve twin of ``train/pipeline_plane.RefLedger``, which
plays the same role for pipeline activations).

Lease discipline (graftlint ``RESOURCE_METHOD_PAIRS`` polices the
pairing): ``publish_handoff`` registers a descriptor whose refs the
process keeps alive; ``discharge_handoff`` — adopt-ack or abort, either
way — must run on EVERY exception path, directly or through a
self-callee chain. Escape hatches for paths no code can cover:

* prefill replica SIGKILL — the refs' owner process died, so the
  object plane frees the blobs structurally (``_RefTracker`` abandons
  deltas to dead owners); nothing strands.
* router death mid-splice — nobody will discharge, so ``sweep()``
  (driven by the controller's reconcile stats pull, every ~0.25 s)
  expires entries past ``serve_handoff_ttl_s`` and hands their refs
  back to the caller to free. Expiry after a successful adopt is
  harmless: the decode replica already fetched the bytes, and
  freeing a fetched blob just drops storage.
"""

from __future__ import annotations

import pickle
import threading
import time
from typing import Any, Dict, List, Optional

# Budget on the serialized DESCRIPTOR (refs + block geometry + first
# token — never the page payload, which rides the object store): the
# router splice forwards it inline with the request, so it must stay
# RPC-header-sized.
HANDOFF_DESC_BYTE_BUDGET = 8192


def descriptor_nbytes(desc: Dict[str, Any]) -> int:
    """Serialized size of a handoff descriptor (ObjectRefs reduce to
    (id, owner_addr) — ~100 B each, never the payload)."""
    return len(pickle.dumps(desc, protocol=5))


class HandoffLedger:
    """Per-replica registry of published-but-undischarged handoffs.

    Thread-safe: publish runs on replica request threads, sweep on the
    stats/metrics pull path. Entries are keyed by the descriptor's
    ``handoff_id``; values keep the publish timestamp so discharge can
    report the publish->adopt latency."""

    def __init__(self, ttl_s: Optional[float] = None):
        from ray_tpu.core.config import config as rt_config

        self._ttl_s = (rt_config.serve_handoff_ttl_s
                       if ttl_s is None else float(ttl_s))
        self._lock = threading.Lock()
        self._entries: Dict[str, Dict[str, Any]] = {}

    # ------------------------------------------------------------ lease

    def publish_handoff(self, desc: Dict[str, Any]) -> Dict[str, Any]:
        """Register a published handoff; the caller owns discharging it
        (adopt-ack or abort) on every path. Returns ``desc``."""
        with self._lock:
            self._entries[desc["handoff_id"]] = {
                "desc": desc, "t_publish": time.monotonic()}
        return desc

    def discharge_handoff(self, handoff_id: str
                          ) -> Optional[Dict[str, Any]]:
        """Pop a published entry (adopt-ack, abort, or expiry all land
        here). Returns ``{"desc", "age_s"}`` or None when the entry was
        already discharged — discharge is idempotent by design: the
        router's abort path and the TTL sweep may race, and both sides
        freeing is a double-free only the ledger can referee."""
        with self._lock:
            entry = self._entries.pop(handoff_id, None)
        if entry is None:
            return None
        return {"desc": entry["desc"],
                "age_s": time.monotonic() - entry["t_publish"]}

    # ------------------------------------------------------------ sweep

    def sweep(self, now: Optional[float] = None) -> List[Dict[str, Any]]:
        """Pop entries older than the TTL and return them (desc +
        age_s); the caller frees their refs and counts them expired.
        Rides the replica stats pull, so the controller's reconcile
        loop doubles as the returns-the-pages backstop."""
        now = time.monotonic() if now is None else now
        expired: List[Dict[str, Any]] = []
        with self._lock:
            for hid in [h for h, e in self._entries.items()
                        if now - e["t_publish"] > self._ttl_s]:
                entry = self._entries.pop(hid)
                expired.append({"desc": entry["desc"],
                                "age_s": now - entry["t_publish"]})
        return expired

    # ------------------------------------------------------------ stats

    def live(self) -> int:
        with self._lock:
            return len(self._entries)

    def live_bytes(self) -> int:
        """Payload bytes pinned by undischarged handoffs (the number
        that says whether the prefill fleet is leaking)."""
        with self._lock:
            return sum(int(e["desc"].get("nbytes", 0))
                       for e in self._entries.values())
