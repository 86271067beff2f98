"""Brumby (``models/brumby.py``, ``models/brumby_decode.py``) against its
plain reference (``benchmarks/reference/brumby_ref.py``) at the debug
preset, in float32 on the CPU: the program computes by STATE (whole prefill,
padded waves, chunks that hand their state on, one token at a time) and the
reference by PAIRS, LOGITS compared; and each of the mechanism's pieces
changes the result when it is left out."""

import dataclasses

import numpy as np
import pytest

TOL = 2e-4
MOVES = 1e-2


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_tpu.models import brumby

    cfg = brumby.PRESETS["debug"]
    return cfg, brumby.init_params(cfg, jax.random.key(7))


def _tokens(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


def _program(cfg):
    """The suffix program, jitted under a NEW function: a trace is cached
    by the function it traced, and a test that patches the module's pieces
    must not be handed another test's."""
    import jax

    from ray_tpu.models import brumby_decode as bd

    return jax.jit(lambda params, toks, pool, tables, plens, lens:
                   bd.paged_prefill_suffix(params, toks, pool, tables, cfg,
                                           plens, lens))


def _chunk(cfg, params, pool, parts, starts, width, pads=0, slots=None,
           program=None):
    """One ``paged_prefill_suffix`` over rows ``parts`` (token arrays) that
    start at ``starts``, right-padded to ``width``, row ``r`` in slot
    ``slots[r]`` (``r``), with ``pads`` pad rows that repeat the last row
    and name the scratch row of the state."""
    import jax.numpy as jnp

    n = len(parts)
    scratch = pool["S"].shape[1] - 1
    toks = np.zeros((n + pads, width), np.int32)
    plens = np.zeros((n + pads,), np.int32)
    lens = np.zeros((n + pads,), np.int32)
    for r in range(n + pads):
        src = min(r, n - 1)
        toks[r, :len(parts[src])] = parts[src]
        plens[r], lens[r] = starts[src], starts[src] + len(parts[src])
    tables = {"slots": jnp.asarray(
        list(range(n) if slots is None else slots) + [scratch] * pads,
        jnp.int32), "ends": jnp.ones((n + pads,), bool)}
    return (program or _program(cfg))(
        params, jnp.asarray(toks), pool, tables, jnp.asarray(plens),
        jnp.asarray(lens))


def _pool(cfg, slots):
    from ray_tpu.models import brumby_decode as bd

    return bd.init_page_pool(cfg, {}, 4, slots=slots)


def _prefilled(cfg, params, tokens, chunk, between=None):
    """ONE sequence through the suffix program in chunks of ``chunk``
    (each padded to the chunk's width); ``between(pool)`` may change the
    pool between two chunks. Returns the last chunk's logits and the
    pool."""
    pool = _pool(cfg, 1)
    logits, program = None, _program(cfg)
    for p in range(0, len(tokens), chunk):
        if p and between is not None:
            pool = between(pool)
        logits, pool = _chunk(cfg, params, pool, [tokens[p:p + chunk]], [p],
                              chunk, program=program)
    return np.asarray(logits[0]), pool


def _reference(cfg, params, tokens, rows):
    from benchmarks.reference import brumby_ref

    return np.asarray(brumby_ref.logits(params, tokens, cfg, rows=rows))


def test_the_model_names_no_page_kind_and_its_pool_is_state_alone(model):
    from ray_tpu.models import brumby_decode as bd

    cfg, _ = model
    assert bd.page_kinds(cfg) == {}
    pool = _pool(cfg, 3)
    assert tuple(pool) == bd.slot_state(cfg) == ("S", "z")
    assert pool["S"].shape == (cfg.n_layers, 4, cfg.n_kv_heads,
                               cfg.head_dim, cfg.state_rows)
    assert pool["z"].shape == (cfg.n_layers, 4, cfg.n_kv_heads,
                               cfg.state_rows)
    assert cfg.state_rows == 160 and cfg.published_state_rows == 136


def test_the_served_widths_hold_the_issues_count():
    from ray_tpu.models import brumby

    cfg = dataclasses.replace(brumby.BrumbyConfig(), n_layers=8)
    assert cfg.state_rows == 9216 and cfg.published_state_rows == 8256
    assert brumby.param_count(cfg) == 8 * 330_352_896 + 5120 \
        + 2 * 151_936 * 5120
    with pytest.raises(ValueError, match="degree"):
        dataclasses.replace(cfg, degree=3)


def test_the_gate_has_no_bias_and_the_seeded_weights_alone_hold_it_open(
        model):
    """The layer is the published one: its leaves are the published
    matrices and norms, no gate bias. The seeded weights give the gate its
    level by a constant channel of the stream (``brumby._gate_channel``):
    no layer writes to it, and ``sigmoid(W_g u)`` lies where a state
    remembers tens to a thousand tokens, not round 0.5."""
    import jax

    from benchmarks.reference import brumby_ref
    from ray_tpu.models import brumby

    cfg, params = model
    assert sorted(params["layers"]) == sorted(
        ["norm1", "wq", "wk", "wv", "wg", "q_norm", "k_norm", "wo",
         "norm2", "w_gate", "w_up", "w_down"])
    x = params["tok_embed"][_tokens(cfg, 200, seed=5)]
    gates = []
    for l in range(cfg.n_layers):
        assert np.allclose(np.asarray(x[:, -1]), brumby.GATE_CHANNEL)
        layer = brumby_ref._at(params["layers"], l)
        u = brumby_ref._rms(x, layer["norm1"], cfg.norm_eps)
        gates.append(np.asarray(jax.nn.sigmoid(u @ layer["wg"])))
        x = brumby_ref._mixer(params["layers"], l, x, cfg, None)
        x = x + sum(brumby_ref._mlp_part(params["layers"], l, x, None, cfg,
                                         None, part)
                    for part in range(brumby_ref.MLP_BLOCKS))
    low, mid, high = np.percentile(np.concatenate(gates), [5, 50, 95])
    # (The debug widths are narrow and spread wider than the served ones.)
    assert 0.5 < low < mid < high < 0.9999 and 0.9 < mid < 0.998, (
        low, mid, high)


@pytest.mark.parametrize("n,chunk", [(37, 64), (37, 16), (23, 8)])
def test_prefill_whole_and_chunked_gives_the_references_logits(model, n,
                                                               chunk):
    """A chunk's last part is shorter than its width: the padded positions
    leave the state as it was."""
    cfg, params = model
    tokens = _tokens(cfg, n)
    got, _ = _prefilled(cfg, params, tokens, chunk)
    want = _reference(cfg, params, tokens, [n - 1])[0]
    assert np.abs(got - want).max() < TOL


def test_a_wave_of_padded_rows_gives_each_rows_logits(model):
    """Three prompts of different lengths as one whole-prefill wave of
    four rows: every row gets the reference's logits at ITS last token,
    and the pad row's state lands in the scratch row."""
    cfg, params = model
    parts = [_tokens(cfg, n, seed=n) for n in (9, 30, 17)]
    logits, pool = _chunk(cfg, params, _pool(cfg, 3), parts, [0, 0, 0], 32,
                          pads=1)
    for r, part in enumerate(parts):
        want = _reference(cfg, params, part, [len(part) - 1])[0]
        assert np.abs(np.asarray(logits[r]) - want).max() < TOL, r
    S = np.asarray(pool["S"])
    assert np.abs(S[:, 3]).max() > 0           # the pad row wrote scratch
    np.testing.assert_allclose(S[:, 3], S[:, 2], rtol=1e-5, atol=1e-6)


def test_decode_through_the_state_gives_the_references_logits(model):
    """Two slots prefilled in chunks, then eight tokens each one at a
    time, a third slot idle: every step's logits are the reference's at
    that position, and the idle slot's state stays bit for bit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import brumby_decode as bd

    cfg, params = model
    seqs = [_tokens(cfg, 29, seed=1), _tokens(cfg, 21, seed=2)]
    cut = [21, 13]
    pool = _pool(cfg, 3)
    program = _program(cfg)
    for p in range(0, 24, 8):
        live = [r for r in range(2) if p < cut[r]]
        _, pool = _chunk(cfg, params, pool,
                         [seqs[r][p:min(p + 8, cut[r])] for r in live],
                         [p] * len(live), 8, slots=live, program=program)
    # Slot 2 holds something a step must not touch.
    pool = {**pool, "S": pool["S"].at[:, 2].set(1.5),
            "z": pool["z"].at[:, 2].set(2.5)}
    step = jax.jit(lambda pool, lens, toks, view: bd.paged_decode_step(
        params, pool, view, lens, toks, cfg))
    lens = jnp.asarray(cut + [0], jnp.int32)
    view = jnp.asarray([True, True, False])
    want = [_reference(cfg, params, s, None) for s in seqs]
    for t in range(8):
        toks = jnp.asarray([seqs[0][cut[0] + t], seqs[1][cut[1] + t], 0])
        logits, pool, lens = step(pool, lens, toks, view)
        for r in range(2):
            assert np.abs(np.asarray(logits[r])
                          - want[r][cut[r] + t]).max() < TOL, (t, r)
        assert np.isfinite(np.asarray(logits[2])).all()
    assert (np.asarray(pool["S"][:, 2]) == 1.5).all()
    assert (np.asarray(pool["z"][:, 2]) == 2.5).all()


def _moved(cfg, params, monkeypatch, n=37, chunk=16, **patches):
    """How far the chunked prefill's logits move from the reference's when
    pieces of the program are replaced."""
    from ray_tpu.models import brumby_decode as bd

    for name, fn in patches.items():
        monkeypatch.setattr(bd, name, fn)
    tokens = _tokens(cfg, n)
    got, _ = _prefilled(cfg, params, tokens, chunk)
    return float(np.abs(got - _reference(cfg, params, tokens,
                                         [n - 1])[0]).max())


def test_leaving_out_the_gate_moves_the_logits(model, monkeypatch):
    import jax.numpy as jnp

    from ray_tpu.models import brumby_decode as bd

    cfg, params = model
    project = bd._project

    def no_gate(layer, x, c, cos, sin):
        q, k, v, log_g = project(layer, x, c, cos, sin)
        return q, k, v, jnp.zeros_like(log_g)

    assert _moved(cfg, params, monkeypatch, _project=no_gate) > MOVES


def test_leaving_out_the_normaliser_moves_the_logits(model, monkeypatch):
    from ray_tpu.models import brumby_decode as bd

    cfg, params = model
    chunked = bd.retention_chunk

    def unnormalised(q, k, v, log_g, S, z, **kw):
        # A denominator of eps alone: the numerator over a constant.
        o, S, z = chunked(q, k, v, log_g, S, z, **{**kw, "eps": 1e6})
        return o * 1e6 / kw["scale"] ** 2, S, z

    assert _moved(cfg, params, monkeypatch,
                  retention_chunk=unnormalised) > MOVES


def test_leaving_out_the_sqrt_2_moves_the_logits(model, monkeypatch):
    """Off-diagonal monomials counted once and not twice: the state then
    holds another kernel than the square of the dot product, and a chunk
    that reads a state (every one but the first) answers otherwise."""
    import numpy as np_

    from ray_tpu.ops import power_retention as pr

    cfg, params = model
    pairs = pr._pairs

    def flat(head_dim, block):
        first, second, weight = pairs(head_dim, block)
        return first, second, np_.ones_like(weight)

    monkeypatch.setattr(pr, "_pairs", flat)
    assert _moved(cfg, params, monkeypatch) > MOVES


@pytest.mark.parametrize("piece", ["head_norm", "rotary"])
def test_leaving_out_a_piece_of_the_projection_moves_the_logits(
        model, monkeypatch, piece):
    from ray_tpu.models import brumby_decode as bd

    cfg, params = model
    if piece == "head_norm":
        norm = bd.rms_norm

        def one(x, w, eps):
            # The heads' norms only: theirs are the weights of width d.
            return x if w.shape[-1] == cfg.head_dim else norm(x, w, eps)

        patches = {"rms_norm": one}
    else:
        patches = {"rotate_pairs": lambda x, cos, sin: x}
    assert _moved(cfg, params, monkeypatch, **patches) > MOVES


def test_dropping_the_state_at_a_chunk_edge_moves_the_logits(model):
    import jax

    cfg, params = model
    tokens = _tokens(cfg, 37)
    got, _ = _prefilled(cfg, params, tokens, 16,
                        between=lambda pool: jax.tree.map(
                            lambda a: a * 0, pool))
    want = _reference(cfg, params, tokens, [36])[0]
    assert np.abs(got - want).max() > MOVES


@pytest.mark.parametrize("degree", [1, 3])
def test_another_degree_in_the_reference_is_another_model(model, degree):
    """The program holds degree 2; the reference at 1 or 3 disagrees with
    it (and the program refuses to be configured so)."""
    cfg, params = model
    tokens = _tokens(cfg, 37)
    got, _ = _prefilled(cfg, params, tokens, 16)

    class Other:
        def __getattr__(self, name):
            return degree if name == "degree" else getattr(cfg, name)

        def __hash__(self):
            return hash((cfg, degree))

        def __eq__(self, other):
            return isinstance(other, Other)

    want = _reference(Other(), params, tokens, [36])[0]
    assert np.abs(got - want).max() > MOVES


def test_the_control_in_8_bits_moves_the_references_own_choice(model):
    """``cut_prompt_margins`` at the debug size: the reference rounded to
    8 bits answers, and some answers lie below the unrounded maximum (the
    harness's control reads these margins on the chip)."""
    from benchmarks.reference import brumby_ref

    cfg, params = model
    prompts = [list(_tokens(cfg, n, seed=n)) for n in (40, 33)]
    margins = brumby_ref.cut_prompt_margins(params, cfg, prompts, 16, 4)
    assert len(margins) == 32 and min(margins) >= 0.0
    assert max(margins) > 0.0
    sound = brumby_ref.served_token_margins(
        params, cfg, [p[:-1] for p in prompts],
        [[int(np.argmax(_reference(cfg, params, np.asarray(p), [len(p) - 2])
                        [0]))] for p in prompts])
    assert max(sound) == 0.0
