"""The selective scan of a Mamba-1 layer (``ops/selective_scan.py``) against
a plain ``lax.scan`` of its recurrence, one position an iteration: chunk
lengths that do and do not divide the sequence, masked tails that leave the
state untouched, a state carried from one call into the next, and the
decode's one-position update."""

import numpy as np
import pytest


def _inputs(B=2, S=19, D=24, N=4, seed=0):
    rng = np.random.default_rng(seed)
    f = np.float32
    return dict(
        x=rng.standard_normal((B, S, D)).astype(f),
        dt=np.log1p(np.exp(rng.standard_normal((B, S, D)))).astype(f),
        A=-np.exp(rng.standard_normal((N, D))).astype(f),
        Bm=rng.standard_normal((B, S, N)).astype(f),
        C=rng.standard_normal((B, S, N)).astype(f),
        D=rng.standard_normal((D,)).astype(f),
        state=rng.standard_normal((B, N, D)).astype(f))


def _plain(x, dt, A, Bm, C, D, state, lengths=None):
    """The recurrence, row by row and position by position."""
    import jax
    import jax.numpy as jnp

    def row(x, dt, Bm, C, s0, n):
        def step(s, inp):
            t, x_t, dt_t, b_t, c_t = inp
            new = jnp.exp(dt_t[None] * A) * s \
                + (dt_t * x_t)[None] * b_t[:, None]
            s = jnp.where(t < n, new, s)
            return s, (new * c_t[:, None]).sum(0) + D * x_t

        return jax.lax.scan(step, s0, (jnp.arange(x.shape[0]), x, dt, Bm,
                                       C))

    n = jnp.full((x.shape[0],), x.shape[1]) if lengths is None \
        else jnp.asarray(lengths)
    state, y = jax.vmap(row)(x, dt, Bm, C, state, n)
    return np.asarray(y), np.asarray(state)


@pytest.mark.parametrize("unroll", [1, 4, 8, 19, 32])
def test_chunks_that_do_and_do_not_divide_give_the_plain_scan(unroll):
    from ray_tpu.ops.selective_scan import selective_scan

    a = _inputs()
    y, state = selective_scan(**a, unroll=unroll)
    want_y, want_state = _plain(**a)
    assert np.abs(np.asarray(y) - want_y).max() < 1e-5
    assert np.abs(np.asarray(state) - want_state).max() < 1e-5


@pytest.mark.parametrize("lengths", [[19, 7], [0, 19], [1, 18]])
def test_a_masked_tail_leaves_the_state_untouched(lengths):
    import jax.numpy as jnp

    from ray_tpu.ops.selective_scan import selective_scan

    a = _inputs(seed=1)
    y, state = selective_scan(**a, lengths=jnp.asarray(lengths))
    want_y, want_state = _plain(**a, lengths=lengths)
    assert np.abs(np.asarray(state) - want_state).max() < 1e-5
    for b, n in enumerate(lengths):
        if n:
            diff = np.asarray(y)[b, :n] - want_y[b, :n]
            assert np.abs(diff).max() < 1e-5
    # A row with no real position hands its state back bit for bit.
    if 0 in lengths:
        b = lengths.index(0)
        assert np.array_equal(np.asarray(state)[b], a["state"][b])


def test_a_state_carried_across_two_calls_is_one_scan():
    from ray_tpu.ops.selective_scan import selective_scan

    a = _inputs(S=23, seed=2)
    want_y, want_state = _plain(**a)
    cut = 9
    head = {k: (v[:, :cut] if k in ("x", "dt", "Bm", "C") else v)
            for k, v in a.items()}
    tail = {k: (v[:, cut:] if k in ("x", "dt", "Bm", "C") else v)
            for k, v in a.items()}
    y0, mid = selective_scan(**head)
    y1, state = selective_scan(**{**tail, "state": mid})
    got = np.concatenate([np.asarray(y0), np.asarray(y1)], axis=1)
    assert np.abs(got - want_y).max() < 1e-5
    assert np.abs(np.asarray(state) - want_state).max() < 1e-5


def test_the_one_position_update_is_the_scans_step():
    from ray_tpu.ops.selective_scan import selective_scan, selective_step

    a = _inputs(S=1, seed=3)
    y, state = selective_scan(**a)
    y1, state1 = selective_step(a["x"][:, 0], a["dt"][:, 0], a["A"],
                                a["Bm"][:, 0], a["C"][:, 0], a["D"],
                                a["state"])
    assert np.abs(np.asarray(y)[:, 0] - np.asarray(y1)).max() < 1e-6
    assert np.abs(np.asarray(state) - np.asarray(state1)).max() < 1e-6
