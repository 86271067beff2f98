"""The selective scan of a Mamba-1 layer (arXiv:2312.00752), with a state
carried in and out:

    s_t = exp(dt_t A) * s_{t-1} + (dt_t x_t) B_t^T        (N x D, float32)
    y_t = C_t . s_t + D * x_t

``selective_scan`` runs a chunk of ``S`` positions a row from the state the
last chunk left; ``selective_step`` is the decode's one position. The state
is laid out ``(N, D)``: the ``d_state`` = 16 numbers of a channel lie on
sublanes and the channels on lanes, where ``(D, N)`` would pad every 16 to
a 128-lane tile, eight times the bytes.

A position that is padding has ``dt`` 0: ``exp(0) = 1`` and ``0 * x B`` add
nothing, so the state passes it untouched (the caller masks ``dt`` by its
rows' lengths; ``lengths`` here does it for him).

The scan is a ``lax.scan`` over time whose body is one fused elementwise
update of the ``(B, N, D)`` state, ``unroll`` positions an iteration, the
positions padded up to whole iterations with ``dt`` 0."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

UNROLL = 8


def selective_step(x, dt, A, Bm, C, D, state) -> Tuple[jax.Array, jax.Array]:
    """One position. ``x``, ``dt`` (B, D); ``A`` (N, D); ``Bm``, ``C`` (B,
    N); ``D`` (D,); ``state`` (B, N, D): all float32. Returns ``(y, state)``
    with ``y`` (B, D)."""
    decay = jnp.exp(dt[:, None, :] * A[None])
    state = decay * state + (dt * x)[:, None, :] * Bm[:, :, None]
    y = jnp.sum(state * C[:, :, None], axis=1) + D[None] * x
    return y, state


def selective_scan(x, dt, A, Bm, C, D, state,
                   lengths: Optional[jax.Array] = None,
                   unroll: int = UNROLL) -> Tuple[jax.Array, jax.Array]:
    """``S`` positions a row. ``x``, ``dt`` (B, S, D); ``A`` (N, D);
    ``Bm``, ``C`` (B, S, N); ``D`` (D,); ``state`` (B, N, D) the state
    before position 0; ``lengths`` (B,) the real positions of each row (the
    rest leave the state untouched; their ``y`` is junk). Float32
    throughout. Returns ``(y (B, S, D), the state after each row's last
    real position)``."""
    B, S, _ = x.shape
    if lengths is not None:
        dt = jnp.where(jnp.arange(S)[None, :, None] < lengths[:, None, None],
                       dt, 0.0)
    unroll = max(1, min(unroll, S))
    short = -S % unroll
    if short:
        pad = [(0, 0), (0, short), (0, 0)]
        x, dt, Bm, C = (jnp.pad(a, pad) for a in (x, dt, Bm, C))

    def chunks(a):
        """(B, S, F) -> (S / unroll, unroll, B, F), time leading."""
        return a.transpose(1, 0, 2).reshape(-1, unroll, B, a.shape[-1])

    def body(state, inp):
        xs, dts, bs, cs = inp
        ys = []
        for t in range(unroll):
            y, state = selective_step(xs[t], dts[t], A, bs[t], cs[t], D,
                                      state)
            ys.append(y)
        return state, jnp.stack(ys)

    state, y = jax.lax.scan(body, state, tuple(
        chunks(a) for a in (x, dt, Bm, C)))
    y = y.reshape(-1, B, y.shape[-1])[:S].transpose(1, 0, 2)
    return y, state
