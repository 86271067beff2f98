"""``ops/chunk_attention.py`` alone, in the Pallas interpreter, against
plain float32 attention: both variants, the sink, the heads a program
takes of a key head's group, and the rule that says which tiles need no
mask."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import chunk_attention as ca


def _reference(q, k, v, q_offsets, k_offsets, scale, window, sink):
    """(B, H, S, Dv) float32: every score, a full mask, one softmax."""
    B, H, S, _ = q.shape
    KV, C = k.shape[1], k.shape[2]
    q, k, v = (np.asarray(a.astype(jnp.float32)) for a in (q, k, v))
    k, v = (np.repeat(a, H // KV, axis=1) for a in (k, v))
    s = np.einsum("bhqd,bhcd->bhqc", q, k) * scale
    rows = np.asarray(q_offsets)[:, None] + np.arange(S)[None, :]
    cols = np.asarray(k_offsets)[:, None] + np.arange(C)[None, :]
    seen = rows[:, :, None] >= cols[:, None, :]
    if window is not None:
        seen &= rows[:, :, None] - cols[:, None, :] < window
    s = np.where(seen[:, None], s, -np.inf)
    logit = (np.full((H,), -np.inf) if sink is None
             else np.asarray(sink))[None, :, None]
    m = np.maximum(s.max(-1), logit)
    m = np.where(np.isfinite(m), m, 0.0)
    p = np.exp(s - m[..., None])
    return np.einsum("bhqc,bhcd->bhqd", p, v) \
        / (p.sum(-1) + np.exp(logit - m))[..., None]


# name: heads, key heads, D, Dv, queries, keys, window, sink, q_offsets,
# k_offsets, (block_q, block_k) to force or None, the VMEM budget (the
# heads it is to allow) or None, the heads a program is expected to take
SMALL = (16, 128)
CASES = {
    # Queries 300..363 over four key tiles of 128: the first query tile
    # meets two interior tiles, the diagonal's and a dead one.
    "full_interior_edge_dead": (4, 2, 128, 128, 64, 512, None, False,
                                [300], [0], SMALL, None, 2),
    "full_one_head_a_program": (2, 2, 128, 128, 32, 256, None, False,
                                [140], [0], SMALL, None, 1),
    "full_sixteen_to_a_key_head": (16, 1, 128, 128, 32, 256, None, False,
                                   [140], [0], SMALL, None, 16),
    # The budget allows 12 heads of the 16: the largest divisor is 8.
    "full_budget_between_divisors": (16, 1, 128, 128, 32, 256, None, False,
                                     [140], [0], SMALL, 12, 8),
    "full_budget_of_one": (16, 1, 128, 128, 32, 256, None, True,
                           [140], [0], SMALL, 1, 1),
    "full_two_rows": (4, 2, 128, 128, 32, 384, None, False,
                      [0, 200], [0, 0], SMALL, None, 2),
    "full_sink": (4, 2, 128, 128, 32, 256, None, True,
                  [130], [0], SMALL, None, 2),
    "full_under_a_tile": (4, 2, 128, 128, 16, 200, None, False,
                          [150], [0], None, None, 2),
    "full_wide_keys": (8, 2, 192, 128, 32, 300, None, False,
                       [200], [0], SMALL, None, 4),
    "full_narrow_values": (8, 2, 24, 16, 32, 300, None, True,
                           [200], [0], SMALL, None, 4),
    # Keys from 384 on, a window of 260: its trailing edge, an interior
    # tile and the diagonal in one query tile's four steps.
    "window_k_offset": (4, 2, 128, 128, 64, 512, 260, False,
                        [700], [384], SMALL, None, 2),
    "window_interior_tiles": (4, 1, 128, 128, 32, 640, 300, False,
                              [900], [512], SMALL, None, 4),
    "window_sink_wide_keys": (8, 4, 192, 128, 32, 256, 12, True,
                              [330], [256], SMALL, None, 2),
    "window_two_rows": (4, 2, 128, 128, 32, 384, 150, True,
                        [5, 600], [0, 384], SMALL, None, 2),
    "window_keys_not_whole_tiles": (4, 2, 128, 128, 16, 75, 30, False,
                                    [50], [0], None, None, 2),
    "window_starts_at_zero": (2, 1, 64, 128, 32, 64, 512, False,
                              [0], [0], None, None, 2),
}


def _tile_kinds(S, C, q_off, k_off, window, block_q, block_k):
    """The grid steps of one row by kind, by the kernel's own arithmetic
    on plain integers: ``{"interior", "edge", "dead"} -> count``."""
    key_tiles = -(-C // block_k)
    steps = key_tiles if window is None else min(
        key_tiles, -(-(block_q + window - 1) // block_k) + 1)
    kinds = {"interior": 0, "edge": 0, "dead": 0}
    for q0 in range(q_off, q_off + S, block_q):
        first = 0 if window is None \
            else max(q0 - (window - 1) - k_off, 0) // block_k
        for tile in range(first, first + steps):
            c0 = k_off + tile * block_k
            live = tile < key_tiles and q0 + block_q - 1 >= c0 and (
                window is None or c0 + block_k - 1 > q0 - window)
            kinds["dead" if not live else "interior" if ca._inside(
                q0, c0, block_q, block_k, window) else "edge"] += 1
    return kinds


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunk_attention_matches_plain_attention(case, monkeypatch):
    (H, KV, D, dv, S, C, window, has_sink, q_off, k_off, forced, budget,
     heads) = CASES[case]
    if forced is not None:
        monkeypatch.setattr(ca, "tiles", lambda queries, window: forced)
    block_q, block_k = ca.tiles(S, window)
    block_k = min(block_k, -(-C // 128) * 128)
    d = -(-D // 128) * 128
    if budget is not None:
        monkeypatch.setattr(ca, "VMEM_BUDGET", ca._vmem_bytes(
            budget, block_q, block_k, d, dv, 2))
    assert ca.heads_a_step(H // KV, block_q, block_k, d, dv, 2) == heads
    if case in ("full_interior_edge_dead", "window_k_offset"):
        kinds = _tile_kinds(S, C, q_off[0], k_off[0], window, block_q,
                            block_k)
        assert min(kinds.values()) > 0, kinds
    keys = jax.random.split(jax.random.key(len(case)), 4)
    B = len(q_off)
    q = jax.random.normal(keys[0], (B, H, S, D)).astype(jnp.bfloat16)
    k = jax.random.normal(keys[1], (B, KV, C, D)).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (B, KV, C, dv)).astype(jnp.bfloat16)
    sink = jax.random.normal(keys[3], (H,)) if has_sink else None
    scale = D ** -0.5
    got = ca.chunk_attention(q, k, v, jnp.asarray(q_off, jnp.int32),
                             jnp.asarray(k_off, jnp.int32), scale,
                             window=window, sink=sink)
    assert got.shape == (B, H, S, dv) and got.dtype == q.dtype
    want = _reference(q, k, v, q_off, k_off, scale, window, sink)
    # The models' logits tests hold the programs to 2e-4 in float32 and
    # to bfloat16's rounding of the output here (values up to ~2).
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), want,
                               atol=2e-2, rtol=0)


def test_chunk_attention_float32_is_the_reference_to_rounding(monkeypatch):
    """In float32 nothing is rounded but the order of the sums: the
    kernel's two bodies and the heads' loop agree with plain attention to
    1e-5 where interior, edge and dead tiles meet in one call."""
    monkeypatch.setattr(ca, "tiles", lambda queries, window: SMALL)
    H, KV, D, S, C = 8, 2, 128, 64, 512
    keys = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(keys[0], (2, H, S, D))
    k = jax.random.normal(keys[1], (2, KV, C, D))
    v = jax.random.normal(keys[2], (2, KV, C, D))
    sink = jax.random.normal(keys[3], (H,))
    for window, k_off in ((None, [0, 0]), (270, [128, 0])):
        q_off = [400, 9]
        got = ca.chunk_attention(q, k, v, jnp.asarray(q_off, jnp.int32),
                                 jnp.asarray(k_off, jnp.int32), 0.09,
                                 window=window, sink=sink)
        want = _reference(q, k, v, q_off, k_off, 0.09, window, sink)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("window", [None, 1, 5, 8, 13, 40])
def test_an_interior_tile_has_no_masked_pair(window):
    """The rule against a brute-force mask over small tiles: a tile the
    rule calls interior has no masked pair, and a tile with one is never
    interior. (A tile with no masked pair IS interior: the rule is exact,
    not merely safe.)"""
    block_q, block_k = 8, 4
    for q0 in range(0, 48):
        for c0 in range(0, 64, block_k):
            rows = q0 + np.arange(block_q)[:, None]
            cols = c0 + np.arange(block_k)[None, :]
            seen = rows >= cols
            if window is not None:
                seen &= rows - cols < window
            inside = bool(ca._inside(q0, c0, block_q, block_k, window))
            assert inside == bool(seen.all()), (q0, c0, window)
