"""``ops/ssd.py`` against the recurrence written out position by position:
the three forms of one sum (the recurrence, the chunked matmul form with a
state, one token at a time), ragged rows, sub-chunk edges, a state handed
from one chunk to the next, and the two Pallas kernels in the interpreter,
each against the same expression in ``jnp`` (``chunk_jnp``, ``step_jnp``:
what XLA makes of them is what ``microbench_ssd.py`` times the kernels
against)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd

H, P, G, N = 8, 4, 2, 16      # a toy layer: 4 heads a group


# (H, P, G, N): the served 16 heads a group with heads of 64, two of which
# share a product's lanes, eight products a group; three groups of two.
SERVED_RATIO = (32, 64, 2, 16)
SHAPES = [(H, P, G, N), SERVED_RATIO, (6, 8, 3, 8)]


def _inputs(key, B, T, dtype=jnp.float32, dims=(H, P, G, N)):
    H, P, G, N = dims
    ks = jax.random.split(key, 7)
    x = jax.random.normal(ks[0], (B, T, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, T, G, N), dtype)
    Cm = jax.random.normal(ks[4], (B, T, G, N), dtype)
    D = jax.random.normal(ks[5], (H,))
    S = jax.random.normal(ks[6], (B, H, P, N))
    return x, dt, A, Bm, Cm, D, S


def chunk_jnp(x, dt, A, Bm, Cm, D, state, lengths=None, sub_chunk=128):
    """``ssd.ssd_chunk`` as XLA runs it (the package's form until PR 58): a
    ``lax.scan`` over the sub-chunks, the decay mask and the stacked ``y``
    through HBM. The same roundings at the same places: the operands of
    every matmul in ``x``'s dtype (the mask times ``C B^T``, ``x left``, the
    state before its read), the rest float32."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    K = H // G
    f32 = jnp.float32
    dt = dt.astype(f32)
    if lengths is not None:
        dt = jnp.where(jnp.arange(T)[None, :, None] < lengths[:, None, None],
                       dt, 0.0)
    C = min(sub_chunk, T)
    short = -T % C
    if short:
        x, dt, Bm, Cm = (jnp.pad(a, [(0, 0), (0, short)]
                                 + [(0, 0)] * (a.ndim - 2))
                         for a in (x, dt, Bm, Cm))
    causal = jnp.tril(jnp.ones((C, C), bool))
    A = A.astype(f32)

    def cut(a):
        """(B, T, ...) -> (T / C, B, C, ...), sub-chunks leading."""
        return jnp.moveaxis(a.reshape((B, -1, C) + a.shape[2:]), 1, 0)

    def body(S, inp):
        xc, dtc, bc, cc = inp                     # (B, C, ...)
        a = jnp.cumsum(dtc * A, axis=1)                         # (B, C, H)
        last = a[:, -1]                                         # (B, H)
        xg = xc.reshape(B, C, G, K, P)
        # Inside the sub-chunk: pairs (t, i), i <= t, a group's C B^T once.
        cb = jnp.einsum("btgn,bign->bgti", cc, bc,
                        preferred_element_type=f32)
        decay = jnp.exp(jnp.where(
            causal[None, :, :, None],
            a[:, :, None, :] - a[:, None, :, :], -jnp.inf))     # b t i h
        w = cb[:, :, None] * (decay * dtc[:, None]).transpose(
            0, 3, 1, 2).reshape(B, G, K, C, C)                  # b g k t i
        y = jnp.einsum("bgkti,bigkp->btgkp", w.astype(xc.dtype), xg,
                       preferred_element_type=f32)
        # What came before it: the state, decayed up to each position.
        Sg = S.reshape(B, G, K, P, N)
        read = jnp.einsum("btgn,bgkpn->btgkp", cc, Sg.astype(cc.dtype),
                          preferred_element_type=f32)
        y = y + jnp.exp(a).reshape(B, C, G, K)[..., None] * read
        # The state after it: each position decayed to the sub-chunk's end.
        left = (jnp.exp(last[:, None] - a) * dtc).reshape(B, C, G, K)
        Sg = jnp.exp(last).reshape(B, G, K)[..., None, None] * Sg \
            + jnp.einsum("bigkp,bign->bgkpn",
                         (xg.astype(f32) * left[..., None]).astype(xc.dtype),
                         bc, preferred_element_type=f32)
        return Sg.reshape(B, H, P, N), y.reshape(B, C, H, P)

    state, y = jax.lax.scan(body, state.astype(f32),
                            tuple(cut(a) for a in (x, dt, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, -1, H, P)[:, :T]
    return y + D.astype(f32)[:, None] * x[:, :T].astype(f32), state


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
    K = x.shape[2] // Bm.shape[2]
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


def _close(got, want, flips=0.01):
    """The same roundings at the same places: all but a few elements (a
    float32 sum in another order can tip one rounding of an operand) agree
    to float32's last digits, and no element is further off than one such
    tip; a rounding moved or left out shows in most of them."""
    got, want = np.asarray(got), np.asarray(want)
    scale = np.abs(want).max()
    off = np.abs(got - want)
    assert (off > 1e-5 * scale).mean() <= flips
    assert off.max() <= 2.0 ** -6 * scale


@pytest.mark.parametrize("dims", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_kernel_is_the_jnp_form_rounding_for_rounding(dims, dtype):
    T = 40
    args = _inputs(jax.random.key(11), 2, T, dtype, dims)
    lengths = jnp.asarray([T, 21])
    y, S1 = ssd.ssd_chunk(*args, lengths, sub_chunk=8)
    want_y, want_S = chunk_jnp(*args, lengths, sub_chunk=8)
    assert y.dtype == jnp.float32 and S1.dtype == jnp.float32
    _close(y[0], want_y[0])
    _close(y[1, :21], want_y[1, :21])
    _close(S1, want_S)
    if dtype == jnp.bfloat16:
        # A state rounded before its update, or a mask left unrounded, is
        # not within this: the float32 form of the same inputs is not.
        f32 = tuple(a.astype(jnp.float32) if a.dtype == dtype else a
                    for a in args)
        loose, _ = chunk_jnp(*f32, lengths, sub_chunk=8)
        with pytest.raises(AssertionError):
            _close(loose[0], want_y[0])


@pytest.mark.parametrize("dims", SHAPES[1:])
def test_groups_of_many_heads_are_the_recurrence(dims):
    x, dt, A, Bm, Cm, D, S = _inputs(jax.random.key(12), 2, 19,
                                     dims=dims)
    want_y, want_S = recurrence(x, dt, A, Bm, Cm, D, S)
    y, S1 = ssd.ssd_chunk(x, dt, A, Bm, Cm, D, S, sub_chunk=8)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(S1, want_S, rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("lengths", [(32, 9, 0, 17), (8, 8, 8, 8),
                                     (1, 0, 31, 16)])
def test_sub_chunks_past_a_rows_last_position_are_skipped(lengths):
    """Whole sub-chunks of padding: the state passes them bit for bit (it
    is what the last real position left) and their rows of ``y`` are
    zeros; a row of none keeps its state as it came."""
    T, sub = 32, 8
    x, dt, A, Bm, Cm, D, S = _inputs(jax.random.key(13), 4, T,
                                     dims=SERVED_RATIO)
    n = jnp.asarray(lengths)
    y, S1 = ssd.ssd_chunk(x, dt, A, Bm, Cm, D, S, n, sub_chunk=sub)
    for row, real in enumerate(lengths):
        used = -(-real // sub) * sub        # the sub-chunks that ran
        assert not np.asarray(y[row, used:]).any()
        if real == 0:
            assert np.array_equal(np.asarray(S1[row]), np.asarray(S[row]))
            continue
        one = tuple(a[row:row + 1, :used] for a in (x, dt))
        bc = tuple(a[row:row + 1, :used] for a in (Bm, Cm))
        short_y, short_S = ssd.ssd_chunk(
            one[0], one[1], A, *bc, D, S[row:row + 1],
            jnp.asarray([real]), sub_chunk=sub)
        assert np.array_equal(np.asarray(S1[row]), np.asarray(short_S[0]))
        assert np.array_equal(np.asarray(y[row, :real]),
                              np.asarray(short_y[0, :real]))
        want_y, want_S = recurrence(
            *(a[:, :real] for a in one), A, *(a[:, :real] for a in bc), D,
            S[row:row + 1])
        np.testing.assert_allclose(y[row, :real], want_y[0], rtol=1e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(S1[row], want_S[0], rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("T,sub", [(5, 8), (3, 128), (21, 8), (130, 128)])
def test_a_length_under_or_across_a_sub_chunk(T, sub):
    x, dt, A, Bm, Cm, D, S = _inputs(jax.random.key(T), 2, T,
                                     dims=SHAPES[2])
    lengths = jnp.asarray([T, T - 2])
    want_y, want_S = chunk_jnp(x, dt, A, Bm, Cm, D, S, lengths, sub)
    y, S1 = ssd.ssd_chunk(x, dt, A, Bm, Cm, D, S, lengths, sub_chunk=sub)
    assert y.shape == x.shape
    np.testing.assert_allclose(y[0], want_y[0], rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(y[1, :T - 2], want_y[1, :T - 2], rtol=1e-5,
                               atol=2e-5)
    np.testing.assert_allclose(S1, want_S, rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("cut", [8, 13, 24])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_two_calls_hand_the_state_on_as_one_call_keeps_it(cut, dtype):
    """A chunk edge on a sub-chunk's edge is the same sum in the same
    order; elsewhere the sub-chunks fall differently and the float32 sums
    with them."""
    T, sub = 32, 8
    x, dt, A, Bm, Cm, D, S = _inputs(jax.random.key(14), 2, T, dtype,
                                     SERVED_RATIO)
    y, S1 = ssd.ssd_chunk(x, dt, A, Bm, Cm, D, S, sub_chunk=sub)
    y0, S0 = ssd.ssd_chunk(x[:, :cut], dt[:, :cut], A, Bm[:, :cut],
                           Cm[:, :cut], D, S, sub_chunk=sub)
    y1, S2 = ssd.ssd_chunk(x[:, cut:], dt[:, cut:], A, Bm[:, cut:],
                           Cm[:, cut:], D, S0, sub_chunk=sub)
    both = jnp.concatenate([y0, y1], 1)
    if cut % sub == 0:
        assert np.array_equal(np.asarray(both), np.asarray(y))
        assert np.array_equal(np.asarray(S2), np.asarray(S1))
    else:
        tol = {"rtol": 0.05, "atol": 0.3} if dtype == jnp.bfloat16 \
            else {"rtol": 1e-5, "atol": 2e-5}
        np.testing.assert_allclose(both, y, **tol)
        np.testing.assert_allclose(S2, S1, **tol)


def test_the_step_continues_what_the_chunk_kernel_left():
    """A prompt's chunk and the decode's first token: the two kernels on one
    state are the recurrence over both."""
    T = 21
    x, dt, A, Bm, Cm, D, S = _inputs(jax.random.key(15), 2, T + 1,
                                     dims=SERVED_RATIO)
    want_y, want_S = recurrence(x, dt, A, Bm, Cm, D, S)
    _, S0 = ssd.ssd_chunk(x[:, :T], dt[:, :T], A, Bm[:, :T], Cm[:, :T], D,
                          S, sub_chunk=8)
    leaf = jnp.concatenate([S0, jnp.zeros_like(S0[:1])])
    y, out = ssd.ssd_step(x[:, T], dt[:, T], A, Bm[:, T], Cm[:, T], D, leaf,
                          jnp.arange(2), jnp.ones((2,), bool))
    np.testing.assert_allclose(y, want_y[:, T], rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(out[:2], want_S, rtol=1e-5, atol=2e-5)


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
