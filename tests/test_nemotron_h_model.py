"""Nemotron-H (``models/nemotron_h.py``, ``models/nemotron_h_decode.py``)
against its plain reference (``benchmarks/reference/nemotron_h_ref.py``) at
the debug preset, in float32 on the CPU: the program computes a Mamba-2
layer by CHUNKS of matmuls with a state (whole prefill, padded waves,
chunks that hand state and convolution tail on, one token at a time) and
the reference by the RECURRENCE, LOGITS compared; each of the mechanism's
pieces changes the result when it is left out; and the shares of an expert
layer add up to the uncut layer."""

import dataclasses

import numpy as np
import pytest

TOL = 2e-4
MOVES = 1e-2
T = 4                 # tokens a page
PAGES = 16            # pages a slot


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_tpu.models import nemotron_h

    cfg = nemotron_h.PRESETS["debug"]
    return cfg, nemotron_h.init_params(cfg, jax.random.key(7))


def _tokens(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


def _program(cfg):
    """The suffix program, jitted under a NEW function: a trace is cached
    by the function it traced, and a test that patches the module's pieces
    must not be handed another test's."""
    import jax

    from ray_tpu.models import nemotron_h_decode as nd

    return jax.jit(lambda params, toks, pool, tables, plens, lens:
                   nd.paged_prefill_suffix(params, toks, pool, tables, cfg,
                                           plens, lens))


def _pages_of(slot):
    return 1 + slot * PAGES + np.arange(PAGES, dtype=np.int32)


def _pool(cfg, slots):
    from ray_tpu.models import nemotron_h_decode as nd

    return nd.init_page_pool(cfg, slots * PAGES, T, slots=slots)


def _chunk(cfg, params, pool, parts, starts, width, pads=0, slots=None,
           program=None):
    """One ``paged_prefill_suffix`` over rows ``parts`` (token arrays) that
    start at ``starts``, right-padded to ``width``, row ``r`` in slot
    ``slots[r]`` (``r``), with ``pads`` pad rows that repeat the last row's
    tokens and pages and name the scratch row of the state."""
    import jax.numpy as jnp

    n = len(parts)
    slots = list(range(n) if slots is None else slots)
    scratch = pool["ssm"].shape[1] - 1
    toks = np.zeros((n + pads, width), np.int32)
    plens = np.zeros((n + pads,), np.int32)
    lens = np.zeros((n + pads,), np.int32)
    for r in range(n + pads):
        src = min(r, n - 1)
        toks[r, :len(parts[src])] = parts[src]
        plens[r], lens[r] = starts[src], starts[src] + len(parts[src])
    tables = {
        "full": jnp.asarray(np.stack(
            [_pages_of(s) for s in slots + slots[-1:] * pads])),
        "slots": jnp.asarray(slots + [scratch] * pads, jnp.int32),
        "ends": jnp.ones((n + pads,), bool)}
    return (program or _program(cfg))(
        params, jnp.asarray(toks), pool, tables, jnp.asarray(plens),
        jnp.asarray(lens))


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
    from benchmarks.reference import nemotron_h_ref

    return np.asarray(nemotron_h_ref.logits(params, tokens, cfg, rows=rows))


def test_one_page_kind_beside_state_and_nothing_for_the_experts(model):
    from ray_tpu.models import nemotron_h_decode as nd

    cfg, _ = model
    assert list(nd.page_kinds(cfg)) == ["full"]
    assert nd.page_kinds(cfg)["full"]["window"] is None
    assert nd.slot_state(cfg) == ("ssm", "conv")
    pool = _pool(cfg, 3)
    assert sorted(pool) == ["conv", "full_k", "full_v", "ssm"]
    # 4 M layers, 2 * layers, 3 E layers: state for the M layers only,
    # pages for the * layers only.
    assert pool["ssm"].shape == (4, 4, cfg.mamba_heads, cfg.mamba_head_dim,
                                 cfg.ssm_state)
    assert pool["ssm"].dtype == np.float32
    assert pool["conv"].shape == (4, 4, cfg.d_conv - 1, cfg.conv_dim)
    assert pool["full_k"].shape == (2, 3 * PAGES + 1, T, cfg.kv_width)
    kinds = [(s.kind, s.layers, s.first) for s in cfg.segments()]
    # Two M layers in a row are one scan of two, and an M layer behind
    # other segments finds its state by its index among the M layers.
    assert ("mamba", 2, 1) in kinds and ("mamba", 1, 3) in kinds
    assert ("full", 1, 1) in kinds and ("experts", 1, 2) in kinds


def test_the_served_cut_holds_the_issues_count():
    from ray_tpu.models import nemotron_h as nh

    whole = nh.NemotronHConfig()
    assert len(nh.PATTERN) == 88 and whole.pattern[:11] == "MEMEMEM*EME"
    assert [whole.kind_layers(k) for k in ("mamba", "experts", "full")] \
        == [40, 40, 8]
    # The published count from the config's keys alone.
    assert round(nh.param_count(whole) / 1e9, 1) == 120.7
    cut = dataclasses.replace(whole, n_layers=11, vocab_size=32768,
                              experts_held=(0, 128))
    assert [cut.kind_layers(k) for k in ("mamba", "experts", "full")] \
        == [5, 5, 1]
    m_layer = 4096 * 18560 + 8192 * 4096 + 4 * 10240 + 10240 + 3 * 128 \
        + 4096 + 8192
    attn = 4096 * 4096 * 2 + 4096 * 512 + 4096
    e_rest = 4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096
    expert = 2 * 1024 * 2688
    assert nh.param_count(cut) == 5 * m_layer + attn + 5 * (
        e_rest + 128 * expert) + 2 * 32768 * 4096 + 4096
    assert round(nh.param_count(cut) / 1e9, 3) == 4.648
    with pytest.raises(ValueError, match="pattern"):
        dataclasses.replace(whole, pattern="MEM-")


@pytest.mark.parametrize("n,chunk", [(37, 64), (37, 16), (23, 8)])
def test_prefill_whole_and_chunked_gives_the_references_logits(model, n,
                                                               chunk):
    """A chunk's last part is shorter than its width: the padded positions
    leave state and tail as they were; sub-chunks of 8 cross every
    chunk."""
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
    S = np.asarray(pool["ssm"])
    assert np.abs(S[:, 3]).max() > 0           # the pad row wrote scratch
    np.testing.assert_allclose(S[:, 3], S[:, 2], rtol=1e-5, atol=1e-6)


def test_decode_through_state_and_pages_gives_the_references_logits(model):
    """Two slots prefilled in chunks, then eight tokens each one at a
    time (across a page edge), a third slot idle: every step's logits are
    the reference's at that position, and the idle slot's state stays bit
    for bit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import moe_decode
    from ray_tpu.models import nemotron_h_decode as nd

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
    pool = {**pool, "ssm": pool["ssm"].at[:, 2].set(1.5),
            "conv": pool["conv"].at[:, 2].set(2.5)}
    step = jax.jit(lambda pool, lens, toks, view: nd.paged_decode_step(
        params, pool, view, lens, toks, cfg))
    lens = jnp.asarray(cut + [0], jnp.int32)
    tables = np.stack([_pages_of(s) for s in range(3)])
    want = [_reference(cfg, params, s, None) for s in seqs]
    pairs = 0
    for t in range(8):
        counts = np.asarray([-(-(cut[0] + t + 1) // T),
                             -(-(cut[1] + t + 1) // T), 0])
        view = jnp.asarray(moe_decode.live_page_view(
            tables, counts, moe_decode.view_rows(counts)))
        toks = jnp.asarray([seqs[0][cut[0] + t], seqs[1][cut[1] + t], 0])
        logits, pool, lens, stats = step(pool, lens, toks, view)
        pairs += float(stats[0])
        for r in range(2):
            assert np.abs(np.asarray(logits[r])
                          - want[r][cut[r] + t]).max() < TOL, (t, r)
        assert np.isfinite(np.asarray(logits[2])).all()
    assert (np.asarray(pool["ssm"][:, 2]) == 1.5).all()
    assert (np.asarray(pool["conv"][:, 2]) == 2.5).all()
    # The idle slot's token meets no expert: at most 2 slots x top 3 x 3
    # expert layers a step are held pairs.
    assert 0 < pairs <= 8 * 2 * 3 * 3


def _moved(cfg, params, monkeypatch=None, n=37, chunk=16, ref_params=None,
           between=None, **patches):
    """How far the chunked prefill's logits move from the reference's (on
    ``ref_params``, else the same weights) when pieces of the program are
    replaced."""
    from ray_tpu.models import nemotron_h_decode as nd

    for name, fn in patches.items():
        monkeypatch.setattr(nd, name, fn)
    tokens = _tokens(cfg, n)
    got, _ = _prefilled(cfg, params, tokens, chunk, between=between)
    ref_cfg = dataclasses.replace(
        cfg, norm_topk_prob=True, routed_scale=5.0)
    want = _reference(ref_cfg, ref_params or params, tokens, [n - 1])[0]
    return float(np.abs(got - want).max())


def _with(params, kind_leaf, fn):
    """``params`` with leaf ``kind_leaf`` of every segment that has it
    replaced by ``fn(leaf)``."""
    return {**params, "segments": [
        {**seg, kind_leaf: fn(seg[kind_leaf])} if kind_leaf in seg else seg
        for seg in params["segments"]]}


def test_nothing_left_out_moves_nothing(model):
    cfg, params = model
    assert _moved(cfg, params) < TOL


@pytest.mark.parametrize("leaf", ["D", "bias", "w1", "w2"])
def test_leaving_out_a_leaf_moves_the_logits(model, leaf):
    """``D``'s skip term, the router's correction bias (another choice of
    experts) and either latent projection (the routed part is then
    gone)."""
    cfg, params = model
    less = _with(params, leaf, lambda a: a * 0)
    assert _moved(cfg, less, ref_params=params) > MOVES


@pytest.mark.parametrize("key,value", [("norm_topk_prob", False),
                                       ("routed_scale", 1.0)])
def test_leaving_out_the_renormalisation_or_the_factor_moves_the_logits(
        model, key, value):
    cfg, params = model
    assert _moved(dataclasses.replace(cfg, **{key: value}), params) > MOVES


def test_leaving_out_the_gate_moves_the_logits(model, monkeypatch):
    import jax.numpy as jnp

    from ray_tpu.models import nemotron_h_decode as nd

    cfg, params = model
    out = nd._mamba_out
    assert _moved(cfg, params, monkeypatch, _mamba_out=lambda layer, y, z,
                  c: out(layer, y, jnp.full_like(z, 1.278), c)) > MOVES


def test_one_group_for_the_norms_four_moves_the_logits(model, monkeypatch):
    from ray_tpu.models import nemotron_h_decode as nd

    cfg, params = model
    out = nd._mamba_out

    class OneGroup:
        def __getattr__(self, name):
            return 1 if name == "ssm_groups" else getattr(cfg, name)

    assert _moved(cfg, params, monkeypatch, _mamba_out=lambda layer, y, z,
                  c: out(layer, y, z, OneGroup())) > MOVES


@pytest.mark.parametrize("leaf", ["conv", "ssm"])
def test_dropping_tail_or_state_at_a_chunk_edge_moves_the_logits(model,
                                                                 leaf):
    cfg, params = model
    assert _moved(cfg, params, between=lambda pool: {
        **pool, leaf: pool[leaf] * 0}) > MOVES


def test_a_rotary_term_put_in_moves_the_logits(model, monkeypatch):
    """The attention layers have NO positional term: the program with the
    rotary of the inherited ``rope_theta`` is another model."""
    import jax.numpy as jnp

    from ray_tpu.models import nemotron_h_decode as nd
    from ray_tpu.ops.rotary import rope_at, rotate_pairs

    cfg, params = model
    qkv = nd._qkv
    inv = 1.0 / (10000.0 ** (np.arange(0, cfg.head_dim, 2) / cfg.head_dim))

    def turned(layer, x, c):
        q, k, v = qkv(layer, x, c)
        # Every test chunk of the one sequence: positions from the pool's
        # point of view are not known here, so turn by the position IN the
        # chunk, which is already another model.
        cos, sin = rope_at(jnp.arange(x.shape[1])[None], jnp.asarray(
            inv, jnp.float32))
        k = k.reshape(k.shape[:2] + (c.n_kv_heads, c.head_dim))
        q, k = (rotate_pairs(t, cos[:, :, None], sin[:, :, None])
                .astype(x.dtype) for t in (q, k))
        return q, k.reshape(k.shape[:2] + (c.kv_width,)), v

    assert _moved(cfg, params, monkeypatch, _qkv=turned) > MOVES


def test_a_plain_relu_in_the_reference_is_another_model(model, monkeypatch):
    """The experts' activation is the SQUARE of the ReLU, in the routed
    experts and in the shared one."""
    import jax

    from benchmarks.reference import nemotron_h_ref as ref

    cfg, params = model
    tokens = _tokens(cfg, 37)
    got, _ = _prefilled(cfg, params, tokens, 16)
    monkeypatch.setattr(
        ref, "_relu2", lambda x, up, down: jax.nn.relu(x @ up) @ down)
    # Another config object: the reference's jitted layers are cached by
    # theirs.
    other = dataclasses.replace(cfg, max_seq_len=cfg.max_seq_len - 1)
    want = _reference(other, params, tokens, [36])[0]
    assert np.abs(got - want).max() > MOVES


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips hold four experts each of a layer of 16: the parts they
    compute, with what every chip computes alike (the shared expert; the
    latent projections are linear, and stand once round the sum) counted
    once, add up to what the reference gives for the WHOLE layer."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import nemotron_h_ref as ref
    from ray_tpu.models import nemotron_h as nh
    from ray_tpu.models import nemotron_h_decode as nd

    whole = dataclasses.replace(nh.PRESETS["debug"], n_layers=2,
                                pattern="ME", experts_held=None)
    params = nh.init_params(whole, jax.random.key(3))
    seg = params["segments"][1]
    x = jax.random.normal(jax.random.key(4), (2, 24, whole.dim))
    keep = jnp.ones((2, 24), bool)

    def part(cfg, experts, w2=None):
        layer = {k: v[0] for k, v in seg.items()
                 if k not in ("experts", "shared")}
        layer = {**layer, "shared": {k: v[0] for k, v in
                                     seg["shared"].items()},
                 "experts": experts, "expert_layer": jnp.int32(0)}
        if w2 is not None:
            layer["w2"] = w2
        return np.asarray(nd._experts(layer, x, cfg, keep)[0])

    shared = part(whole, seg["experts"], w2=seg["w2"][0] * 0)
    total = np.zeros_like(shared)
    for first in range(0, 16, 4):
        cfg = dataclasses.replace(whole, experts_held=(first, 4))
        total += part(cfg, {k: v[:, first:first + 4]
                            for k, v in seg["experts"].items()}) - shared
    total += shared
    with jax.default_matmul_precision("highest"):
        u = ref._rms(x, seg["norm"][0], whole.norm_eps)
        want = np.stack([np.asarray(ref._experts(seg, 0, u[b], whole, None))
                         for b in range(2)])
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-4)
    # And one share alone is not the layer.
    assert np.abs(part(dataclasses.replace(whole, experts_held=(0, 4)),
                       {k: v[:, :4] for k, v in seg["experts"].items()})
                  - want).max() > MOVES


def test_the_control_in_few_bits_moves_the_references_own_choice(model):
    """``cut_prompt_margins`` at the debug size: the reference rounded to
    4 bits answers, and some answers lie below the unrounded maximum (the
    harness's control reads these margins on the chip)."""
    from benchmarks.reference import nemotron_h_ref

    cfg, params = model
    prompts = [list(_tokens(cfg, n, seed=n)) for n in (40, 33)]
    margins = nemotron_h_ref.cut_prompt_margins(params, cfg, prompts, 16, 4)
    assert len(margins) == 32 and min(margins) >= 0.0
    assert max(margins) > 0.0
    sound = nemotron_h_ref.served_token_margins(
        params, cfg, [p[:-1] for p in prompts],
        [[int(np.argmax(_reference(cfg, params, np.asarray(p), [len(p) - 2])
                        [0]))] for p in prompts])
    assert max(sound) == 0.0
