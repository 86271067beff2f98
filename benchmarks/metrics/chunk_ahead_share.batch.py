"""Share of the window's prefill chunks that went AHEAD: the ``launch``
slices of the role ``prefill_chunk`` that say ``ahead`` (dispatched behind a
decode whose ids the host had yet to fetch, PR 53, so the host's sample, emit,
reap, admit and launch ran under the chunk on the device) over all of them.
``None`` where the window dispatched no chunk; 0 from a program that sends
none ahead."""

from benchmarks import progtrace


def read(ctx):
    chunks = [s for r in progtrace.sliced_rows(ctx) for s in r["slices"]
              if s["name"] == "launch"
              and s.get("program") == "prefill_chunk"]
    if not chunks:
        return None
    return sum(1 for s in chunks if s.get("ahead")) / len(chunks)
