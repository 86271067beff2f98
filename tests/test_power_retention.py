"""``ops/power_retention.py`` against the mechanism written out pair by pair:
the expansion's identity in the layout held, the three forms of one sum
(quadratic, chunked with a state, one token at a time), a state handed from
one chunk to the next, and the Pallas decode step in the interpreter against
the same expression in ``jnp``."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import power_retention as pr

D, BLOCK = 16, 4          # a toy head: 4 blocks, 10 pairs of 16 = 160 rows
J, G = 2, 3
SCALE = D ** -0.5


def _inputs(key, B, T, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, T, J, G, D), dtype)
    k = jax.random.normal(ks[1], (B, T, J, D), dtype)
    v = jax.random.normal(ks[2], (B, T, J, D), dtype)
    gate = jax.random.uniform(ks[3], (B, T, J), jnp.float32, 0.8, 0.999)
    return q, k, v, jnp.log(gate)


def _zero_state(B):
    R = pr.phi_rows(D, BLOCK)
    return (jnp.zeros((B, J, D, R), jnp.float32),
            jnp.zeros((B, J, R), jnp.float32))


def quadratic(q, k, v, log_g, degree=2, eps=pr.EPS):
    """``o_t = sum_i a[t, i] v_i / (sum_i a[t, i] + eps)`` with every pair
    written out: no state, no chunks."""
    T = q.shape[1]
    cum = jnp.cumsum(log_g, axis=1)                          # (B, T, J)
    s = jnp.einsum("btjgd,bijd->bjgti", q, k,
                   precision="highest") * SCALE
    decay = jnp.exp(cum[:, :, None] - cum[:, None, :])       # (B, t, i, J)
    seen = jnp.tril(jnp.ones((T, T), bool))[None, :, :, None]
    a = s ** degree * jnp.where(seen, decay, 0.0).transpose(
        0, 3, 1, 2)[:, :, None]
    num = jnp.einsum("bjgti,bijd->btjgd", a, v, precision="highest")
    return num / (a.sum(-1).transpose(0, 3, 1, 2) + eps)[..., None]


def test_phi_rows_at_the_served_head():
    assert pr.phi_rows(128, 16) == 9216
    assert pr.phi_rows(D, BLOCK) == 160
    # The distinct monomials, which the layout pads to whole tiles.
    assert 128 * 129 // 2 == 8256 < 9216 < 128 * 128


@pytest.mark.parametrize("d,block", [(16, 4), (32, 16), (128, 16)])
def test_phi_dot_is_the_square_of_the_dot(d, block):
    kq, kk = jax.random.split(jax.random.key(0))
    q = jax.random.normal(kq, (5, d), jnp.float32)
    k = jax.random.normal(kk, (5, d), jnp.float32)
    got = jnp.sum(pr.phi(q, block) * pr.phi(k, block), -1)
    want = jnp.sum(q * k, -1) ** 2
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    assert pr.phi(q, block).shape[-1] == pr.phi_rows(d, block)


@pytest.mark.parametrize("T,sub", [(7, 4), (16, 4), (33, 8), (64, 64)])
def test_chunked_is_quadratic(T, sub):
    q, k, v, lg = _inputs(jax.random.key(T), 2, T)
    want = quadratic(q, k, v, lg)
    got, _, _ = pr.retention_chunk(q, k, v, lg, *_zero_state(2),
                                   scale=SCALE, block=BLOCK, sub_chunk=sub)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_ragged_rows_leave_the_state_at_their_last_real_position():
    T = 24
    q, k, v, lg = _inputs(jax.random.key(3), 2, T)
    lengths = jnp.asarray([T, 13], jnp.int32)
    got, S, z = pr.retention_chunk(q, k, v, lg, *_zero_state(2), scale=SCALE,
                                   block=BLOCK, sub_chunk=8, lengths=lengths)
    want = quadratic(q, k, v, lg)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1, :13], want[1, :13], rtol=1e-5,
                               atol=1e-5)
    _, S13, z13 = pr.retention_chunk(
        q[1:, :13], k[1:, :13], v[1:, :13], lg[1:, :13], *_zero_state(1),
        scale=SCALE, block=BLOCK, sub_chunk=8)
    np.testing.assert_allclose(S[1], S13[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z[1], z13[0], rtol=1e-5, atol=1e-5)


def test_a_state_carried_across_a_chunk_edge():
    T, cut = 40, 24
    q, k, v, lg = _inputs(jax.random.key(4), 1, T)
    want = quadratic(q, k, v, lg)
    first, S, z = pr.retention_chunk(
        q[:, :cut], k[:, :cut], v[:, :cut], lg[:, :cut], *_zero_state(1),
        scale=SCALE, block=BLOCK, sub_chunk=8)
    second, _, _ = pr.retention_chunk(
        q[:, cut:], k[:, cut:], v[:, cut:], lg[:, cut:], S, z,
        scale=SCALE, block=BLOCK, sub_chunk=8)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), want,
                               rtol=1e-5, atol=1e-5)
    # Without the state the second chunk is another sum.
    alone, _, _ = pr.retention_chunk(
        q[:, cut:], k[:, cut:], v[:, cut:], lg[:, cut:], *_zero_state(1),
        scale=SCALE, block=BLOCK, sub_chunk=8)
    assert float(jnp.abs(alone - want[:, cut:]).max()) > 1e-2


def step_jnp(q, k, v, log_g, S, z, steps, layer=0, *, scale: float,
             block: int = pr.BLOCK, eps: float = pr.EPS):
    """``pr.retention_step`` as one expression for XLA to fuse: what the
    kernel is held to here and timed against on the chip
    (``microbench_retention.py``: 6.57 ms a layer against the kernel's
    1.91, PR 52, which is why ``ops/`` keeps the kernel alone)."""
    B = q.shape[0]
    with jax.named_scope(pr.NAME):
        fq, fk, gate = pr._step_inputs(q, k, log_g, block)
        S0 = jax.lax.dynamic_index_in_dim(S, layer, 0, False)[:B]
        S1 = gate[..., None, None] * S0 \
            + v.astype(jnp.float32)[..., :, None] * fk[..., None, :]
        num = jnp.einsum("bjgr,bjdr->bjgd", fq, S1.astype(fq.dtype),
                         preferred_element_type=jnp.float32)
        num = jnp.where(steps[:, None, None, None], num, 0.0)
        S1 = jnp.where(steps[:, None, None, None], S1, S0)
        S = jax.lax.dynamic_update_slice(S, S1[None], (layer, 0, 0, 0, 0))
        den, z = pr._step_normaliser(fq, fk, gate, z, layer, steps)
        return num / (den + eps / scale ** 2)[..., None], S, z


def _leaves(layers, slots):
    R = pr.phi_rows(D, BLOCK)
    return (jnp.zeros((layers, slots + 1, J, D, R), jnp.float32),
            jnp.zeros((layers, slots + 1, J, R), jnp.float32))


@pytest.mark.parametrize("step", [pr.retention_step, step_jnp],
                         ids=["pallas", "jnp"])
def test_one_token_at_a_time_is_quadratic(step):
    B, T, layer = 3, 9, 1
    q, k, v, lg = _inputs(jax.random.key(5), B, T)
    want = quadratic(q, k, v, lg)
    S, z = _leaves(2, B)
    every = jnp.ones((B,), bool)
    for t in range(T):
        o, S, z = step(q[:, t], k[:, t], v[:, t], lg[:, t], S, z, every,
                       layer, scale=SCALE, block=BLOCK)
        # A sum of one or two pairs can be a square near zero, which the
        # expansion reaches as a sum of 160 signed products: ill-conditioned
        # in any form, and the quadratic form's own rounding there.
        tol = 1e-5 if t >= 2 else 1e-3
        np.testing.assert_allclose(o, want[:, t], rtol=tol, atol=tol)
    # The other layer and the scratch row were never written.
    assert not np.asarray(S[0]).any() and not np.asarray(S[1, B]).any()
    # And the state the steps leave is the chunked form's.
    _, Sc, zc = pr.retention_chunk(q, k, v, lg, S[0, :B], z[0, :B],
                                   scale=SCALE, block=BLOCK, sub_chunk=4)
    np.testing.assert_allclose(S[layer, :B], Sc, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z[layer, :B], zc, rtol=1e-5, atol=1e-5)


def test_pallas_step_is_the_jnp_step_and_spares_a_slot_outside_it():
    B = 4
    q, k, v, lg = _inputs(jax.random.key(6), B, 3)
    kS, kz = jax.random.split(jax.random.key(7))
    S, z = _leaves(2, B)
    S = jax.random.normal(kS, S.shape, jnp.float32)
    z = jnp.abs(jax.random.normal(kz, z.shape, jnp.float32)) + 1.0
    steps = jnp.asarray([True, False, True, False])
    Sp, zp, Sj, zj = S, z, S, z
    for t in range(3):
        op, Sp, zp = pr.retention_step(
            q[:, t], k[:, t], v[:, t], lg[:, t], Sp, zp, steps, 1,
            scale=SCALE, block=BLOCK)
        oj, Sj, zj = step_jnp(
            q[:, t], k[:, t], v[:, t], lg[:, t], Sj, zj, steps, 1,
            scale=SCALE, block=BLOCK)
        np.testing.assert_allclose(op, oj, rtol=1e-5, atol=1e-5)
        assert not np.asarray(op[1]).any() and not np.asarray(op[3]).any()
    np.testing.assert_allclose(Sp, Sj, rtol=1e-6, atol=1e-6)
    # Bit for bit: the slots outside the step, the other layer, the
    # scratch row.
    for got in (Sp, Sj):
        np.testing.assert_array_equal(got[1, 1], S[1, 1])
        np.testing.assert_array_equal(got[1, 3], S[1, 3])
        np.testing.assert_array_equal(got[0], S[0])
        np.testing.assert_array_equal(got[1, B], S[1, B])
    np.testing.assert_array_equal(zp[1, 1], z[1, 1])
    assert float(jnp.abs(Sp[1, 0] - S[1, 0]).max()) > 1e-3


def test_bfloat16_operands_keep_a_float32_state():
    q, k, v, lg = _inputs(jax.random.key(8), 1, 32, jnp.bfloat16)
    o, S, z = pr.retention_chunk(q, k, v, lg, *_zero_state(1), scale=SCALE,
                                 block=BLOCK, sub_chunk=8)
    assert o.dtype == S.dtype == z.dtype == jnp.float32
    want = quadratic(*(a.astype(jnp.float32) for a in (q, k, v)), lg)
    assert float(jnp.abs(o - want).max()) < 0.1 * float(jnp.abs(want).max())


@pytest.mark.parametrize("degree", [1, 3])
def test_another_degree_is_another_sum(degree):
    q, k, v, lg = _inputs(jax.random.key(9), 1, 12)
    got, _, _ = pr.retention_chunk(q, k, v, lg, *_zero_state(1),
                                   scale=SCALE, block=BLOCK, sub_chunk=4)
    other = quadratic(q, k, v, lg, degree=degree)
    assert float(jnp.abs(got - other).max()) > 1e-2
    assert math.isfinite(float(jnp.abs(other).max()))


@pytest.mark.parametrize("d,block", [(16, 4), (128, 16)])
def test_phi_by_selection_is_phi(d, block):
    x = jax.random.normal(jax.random.key(2), (3, 2, d), jnp.float32)
    np.testing.assert_allclose(pr.phi_lanes(x, block), pr.phi(x, block),
                               rtol=1e-6, atol=1e-6)
    xb = x.astype(jnp.bfloat16)
    got = pr.phi_lanes(xb, block)
    assert got.dtype == jnp.bfloat16
    want = pr.phi(xb.astype(jnp.float32), block)
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=1e-2,
                               atol=1e-6)
