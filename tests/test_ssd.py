"""``ops/ssd.py`` against the recurrence written out position by position:
the three forms of one sum (the recurrence, the chunked matmul form with a
state, one token at a time), ragged rows, sub-chunk edges, a state handed
from one chunk to the next, and the Pallas decode step in the interpreter
against the same expression in ``jnp``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd

H, P, G, N = 8, 4, 2, 16      # a toy layer: 4 heads a group


def _inputs(key, B, T, dtype=jnp.float32):
    ks = jax.random.split(key, 7)
    x = jax.random.normal(ks[0], (B, T, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, T, G, N), dtype)
    Cm = jax.random.normal(ks[4], (B, T, G, N), dtype)
    D = jax.random.normal(ks[5], (H,))
    S = jax.random.normal(ks[6], (B, H, P, N))
    return x, dt, A, Bm, Cm, D, S


def step_jnp(x, dt, A, Bm, Cm, D, state, rows, live):
    """``ssd.ssd_step`` as XLA fuses it: the slots' rows gathered, the
    update written out, the rows scattered back."""
    K = x.shape[1] // Bm.shape[1]
    s0 = state[rows]
    b = jnp.repeat(Bm.astype(jnp.float32), K, axis=1)           # (B, H, N)
    c = jnp.repeat(Cm.astype(jnp.float32), K, axis=1)
    xf = x.astype(jnp.float32)
    s1 = jnp.exp(dt * A)[..., None, None] * s0 \
        + (dt[..., None] * xf)[..., None] * b[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", s1, c, precision="highest") \
        + D[None, :, None] * xf
    s1 = jnp.where(live[:, None, None, None], s1, s0)
    return (jnp.where(live[:, None, None], y, 0.0),
            state.at[rows].set(s1))


def recurrence(x, dt, A, Bm, Cm, D, S, lengths=None):
    """Position by position, a head at a time: no chunks, no matmul form."""
    B, T = x.shape[:2]
    K = H // G
    ys = []
    for t in range(T):
        b = jnp.repeat(Bm[:, t], K, axis=1)                     # (B, H, N)
        c = jnp.repeat(Cm[:, t], K, axis=1)
        step = dt[:, t]
        if lengths is not None:
            step = jnp.where((t < lengths)[:, None], step, 0.0)
        S = jnp.exp(step * A)[..., None, None] * S \
            + (step[..., None] * x[:, t])[..., None] * b[:, :, None, :]
        ys.append(jnp.einsum("bhpn,bhn->bhp", S, c, precision="highest")
                  + D[None, :, None] * x[:, t])
    return jnp.stack(ys, axis=1), S


@pytest.mark.parametrize("T,sub", [(7, 4), (16, 4), (33, 8), (64, 64),
                                   (40, 128)])
def test_chunked_is_the_recurrence(T, sub):
    x, dt, A, Bm, Cm, D, S = _inputs(jax.random.key(T), 2, T)
    want_y, want_S = recurrence(x, dt, A, Bm, Cm, D, S)
    y, S1 = ssd.ssd_chunk(x, dt, A, Bm, Cm, D, S, sub_chunk=sub)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(S1, want_S, rtol=1e-5, atol=1e-5)


def test_ragged_rows_leave_the_state_at_their_last_real_position():
    T = 24
    x, dt, A, Bm, Cm, D, S = _inputs(jax.random.key(3), 3, T)
    lengths = jnp.asarray([24, 13, 0])
    y, S1 = ssd.ssd_chunk(x, dt, A, Bm, Cm, D, S, lengths, sub_chunk=8)
    for row, n in enumerate([24, 13]):
        one = tuple(a[row:row + 1, :n] for a in (x, dt))
        bc = tuple(a[row:row + 1, :n] for a in (Bm, Cm))
        want_y, want_S = recurrence(one[0], one[1], A, *bc, D,
                                    S[row:row + 1])
        np.testing.assert_allclose(y[row, :n], want_y[0], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(S1[row], want_S[0], rtol=1e-5, atol=1e-5)
    # A row of padding alone keeps its state bit for bit.
    assert np.array_equal(np.asarray(S1[2]), np.asarray(S[2]))


def test_a_padded_position_leaves_the_state_bit_for_bit():
    x, dt, A, Bm, Cm, D, S = _inputs(jax.random.key(4), 2, 16)
    lengths = jnp.asarray([5, 9])
    _, short = ssd.ssd_chunk(x[:, :9], dt[:, :9], A, Bm[:, :9], Cm[:, :9],
                             D, S, lengths, sub_chunk=16)
    _, padded = ssd.ssd_chunk(x, dt, A, Bm, Cm, D, S, lengths, sub_chunk=4)
    np.testing.assert_allclose(short, padded, rtol=1e-5, atol=1e-5)
    # Sub-chunks of padding alone, behind a state that real positions
    # left: it comes out as it went in.
    _, again = ssd.ssd_chunk(x, dt, A, Bm, Cm, D, padded,
                             jnp.zeros((2,), jnp.int32), sub_chunk=4)
    assert np.array_equal(np.asarray(again), np.asarray(padded))


@pytest.mark.parametrize("cut", [4, 8, 13])
def test_a_state_carried_across_a_chunk_edge(cut):
    T = 20
    x, dt, A, Bm, Cm, D, S = _inputs(jax.random.key(cut), 2, T)
    want_y, want_S = recurrence(x, dt, A, Bm, Cm, D, S)
    y0, S0 = ssd.ssd_chunk(x[:, :cut], dt[:, :cut], A, Bm[:, :cut],
                           Cm[:, :cut], D, S, sub_chunk=4)
    y1, S1 = ssd.ssd_chunk(x[:, cut:], dt[:, cut:], A, Bm[:, cut:],
                           Cm[:, cut:], D, S0, sub_chunk=4)
    np.testing.assert_allclose(jnp.concatenate([y0, y1], 1), want_y,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(S1, want_S, rtol=1e-5, atol=1e-5)


def _leaf(key, rows):
    return jax.random.normal(key, (rows, H, P, N), jnp.float32)


@pytest.mark.parametrize("block,unroll", [(None, None), (2, 1), (4, 2),
                                          (8, 4)])
def test_one_token_is_the_recurrence_and_the_jnp_form(block, unroll):
    B = 3
    x, dt, A, Bm, Cm, D, _ = _inputs(jax.random.key(5), B, 1)
    leaf = _leaf(jax.random.key(6), 2 * (B + 1))
    # Layer 1 of two: slots at rows 4, 5, 6 and the scratch row 7.
    rows = jnp.asarray([4, 5, 6])
    live = jnp.ones((B,), bool)
    args = (x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D)
    y, out = ssd.ssd_step(*args, leaf, rows, live, block=block,
                          unroll=unroll)
    want_y, want_S = recurrence(x, dt, A, Bm, Cm, D, leaf[4:7])
    np.testing.assert_allclose(y, want_y[:, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out[4:7], want_S, rtol=1e-5, atol=1e-5)
    y2, out2 = step_jnp(*args, leaf, rows, live)
    np.testing.assert_allclose(y, y2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out, out2, rtol=1e-5, atol=1e-5)
    # The other layer's rows and the scratch row are as they were.
    assert np.array_equal(np.asarray(out[:4]), np.asarray(leaf[:4]))
    assert np.array_equal(np.asarray(out[7]), np.asarray(leaf[7]))


def test_a_slot_outside_the_step_keeps_its_state_bit_for_bit():
    B = 4
    x, dt, A, Bm, Cm, D, _ = _inputs(jax.random.key(7), B, 1)
    leaf = _leaf(jax.random.key(8), B + 1)
    live = jnp.asarray([True, False, True, False])
    rows = jnp.where(live, jnp.arange(B), B)
    y, out = ssd.ssd_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D, leaf,
                          rows, live, block=2)
    y2, out2 = step_jnp(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D, leaf,
                        rows, live)
    for slot in (1, 3, B):
        assert np.array_equal(np.asarray(out[slot]), np.asarray(leaf[slot]))
    assert not np.asarray(y[1]).any() and not np.asarray(y[3]).any()
    np.testing.assert_allclose(y, y2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out, out2, rtol=1e-5, atol=1e-5)


def test_bfloat16_operands_keep_a_float32_state():
    x, dt, A, Bm, Cm, D, S = _inputs(jax.random.key(9), 2, 16, jnp.bfloat16)
    y, S1 = ssd.ssd_chunk(x, dt, A, Bm, Cm, D, S, sub_chunk=8)
    assert y.dtype == jnp.float32 and S1.dtype == jnp.float32
    f32 = tuple(a.astype(jnp.float32) for a in (x, Bm, Cm))
    want_y, want_S = recurrence(f32[0], dt, A, f32[1], f32[2], D, S)
    np.testing.assert_allclose(y, want_y, rtol=0.1, atol=0.25)
    np.testing.assert_allclose(S1, want_S, rtol=0.05, atol=0.1)
    leaf = _leaf(jax.random.key(10), 3)
    rows, live = jnp.asarray([0, 1]), jnp.ones((2,), bool)
    yk, outk = ssd.ssd_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D,
                            leaf, rows, live)
    yj, outj = step_jnp(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D, leaf,
                        rows, live)
    np.testing.assert_allclose(yk, yj, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(outk, outj, rtol=1e-5, atol=1e-5)


def test_heads_a_block_at_the_served_widths():
    assert ssd.heads_a_block(128, 64, 128) == 64     # 2 MiB of state
    assert ssd.heads_a_block(8, 4, 16) == 8
