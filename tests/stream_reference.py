"""The plain reference that engine streams are held to, and the one margin.

Two programs that compute the same logits (a whole-prompt prefill, its
split into chunks or behind a spliced prefix, a gather through a block
table, a mesh layout) round differently in bfloat16, and a greedy stream
flips where the top two logits lie closer than that rounding. So a stream
is not compared token for token with another program's: the reference
(``llama_decode.prefill`` + ``decode_step``, the single-sequence path no
engine runs) is teacher-forced along the SERVED tokens, and each served
token's reference logit must lie within ``MARGIN`` of its position's
maximum. This is what the benchmark's ``correct`` does on the chip
(``benchmarks/reference/llama_ref.py::served_token_margins``, 0.25 at
92,544 logits of a 24-layer model)."""

from functools import lru_cache, partial

import numpy as np

# Logits of one position from two such programs, compared directly. The
# debug-size models of these tests (2-4 layers, random weights, logits of a
# few units) put them within 0.024 of each other (the one logit that
# test_suffix_prefill_matches_full_prefill failed on at 0.02).
LOGITS_ATOL = 0.05

# A token that is first under logits within LOGITS_ATOL of the reference's
# lies within twice that under the reference's maximum (seen: 0.0063). A
# dropped layer, a misplaced page or a wrong position moves logits by
# whole units.
MARGIN = 2 * LOGITS_ATOL


@lru_cache(maxsize=None)
def _reference_programs(cfg):
    """The reference's two programs, jitted once a configuration."""
    import jax

    from ray_tpu.models import llama_decode as ld

    return (jax.jit(partial(ld.prefill, config=cfg)),
            jax.jit(partial(ld.decode_step, config=cfg)))


def reference_margins(params, cfg, prompt, served):
    """``max(logits) - logits[token]`` under the reference at the position
    of each served token, teacher-forced on ``prompt + served``."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    prefill, step = _reference_programs(cfg)
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    width = ld.cache_bucket(len(prompt), 16)
    rows = np.zeros((1, width), np.int32)
    rows[0, :len(prompt)] = prompt
    cache = ld.init_cache(
        cfg, 1, ld.cache_bucket(len(prompt) + len(served), width))
    logits, cache = prefill(
        params, jnp.asarray(rows), cache,
        lengths=jnp.asarray([len(prompt)], jnp.int32))
    out = []
    for tok in served:
        row = np.asarray(logits[0], np.float32)
        out.append(float(row.max() - row[int(tok)]))
        logits, cache = step(params, cache, jnp.asarray([tok], jnp.int32))
    return out


def assert_stream_is_the_references(params, cfg, prompt, served,
                                    margin=MARGIN):
    """Every served token is the reference's first choice at its
    position, or within ``margin`` of it in logit."""
    margins = reference_margins(params, cfg, prompt, served)
    worst = int(np.argmax(margins)) if margins else 0
    assert not margins or margins[worst] <= margin, (
        f"served token {worst} ({int(served[worst])}) lies "
        f"{margins[worst]:.4f} under the reference's maximum "
        f"(margin {margin}); stream {list(map(int, served))}")


def assert_logits_close(got, want, atol=LOGITS_ATOL):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0, atol=atol)
