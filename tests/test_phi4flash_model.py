"""Phi-4-mini-flash (``models/phi4flash.py``, ``models/phi4flash_decode.py``)
against its plain reference (``benchmarks/reference/phi4flash_ref.py``) at
the debug preset, in float32 on the CPU: whole prefill, chunked prefill with
padded rows and decode through two kinds of page and a state a slot, LOGITS
compared, over prompts that cross the window (12), a page (4) and a chunk;
the prefill that skips what nothing reads gives the last position's logits
of the full forward (the reference runs every layer at every position); and
each of the model's own pieces changes the result when it is left out."""

import dataclasses

import numpy as np
import pytest

TOL = 2e-4
MOVES = 1e-2
T = 4


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_tpu.models import phi4flash

    cfg = phi4flash.PRESETS["debug"]
    return cfg, phi4flash.init_params(cfg, jax.random.key(7))


def _tokens(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


def _pool(cfg, rows, longest):
    """A hand-made pool: row ``r`` owns full and window pages ``r x P +
    1 ..`` (written through and never freed: the engine's freeing is
    ``test_page_kinds``'s) and the state of slot ``r``."""
    from ray_tpu.models import phi4flash_decode as pd

    per = -(-longest // T) + 2
    pool = pd.init_page_pool(cfg, {"full": rows * per, "window": rows * per},
                             T, slots=rows)
    return pool, [list(range(r * per + 1, (r + 1) * per + 1))
                  for r in range(rows)]


def _program(cfg):
    """The suffix program, jitted under a NEW function: a trace is cached
    by the function it traced, and a test that patches the module's pieces
    must not be handed another test's."""
    import jax

    from ray_tpu.models import phi4flash_decode as pd

    return jax.jit(lambda params, toks, pool, tables, plens, lens:
                   pd.paged_prefill_suffix(params, toks, pool, tables, cfg,
                                           plens, lens))


def _chunk(cfg, params, pool, ids, parts, starts, width, pads=0,
           slots=None, scratch=None, program=None):
    """One ``paged_prefill_suffix`` over rows ``parts`` (token arrays) that
    start at ``starts``, right-padded to ``width``, row ``r`` in slot
    ``slots[r]`` (``r``), with ``pads`` pad rows that repeat the last row
    and name the ``scratch`` row of the state (the one behind the rows)."""
    import jax.numpy as jnp

    n = len(parts)
    toks = np.zeros((n + pads, width), np.int32)
    plens = np.zeros((n + pads,), np.int32)
    lens = np.zeros((n + pads,), np.int32)
    wcols = -(-(width + cfg.window) // T) + 1
    full, window, first = [], [], []
    for r in range(n + pads):
        src = min(r, n - 1)
        toks[r, :len(parts[src])] = parts[src]
        plens[r], lens[r] = starts[src], starts[src] + len(parts[src])
        f = max(0, starts[src] - cfg.window + 1) // T
        full.append(ids[src])
        window.append((ids[src][f:] + [0] * wcols)[:wcols])
        first.append(f)
    tables = {"full": jnp.asarray(full, jnp.int32),
              "window": jnp.asarray(window, jnp.int32),
              "window_first": jnp.asarray(first, jnp.int32),
              "slots": jnp.asarray(
                  list(range(n) if slots is None else slots)
                  + [n if scratch is None else scratch] * pads, jnp.int32),
              "ends": jnp.ones((n + pads,), bool)}
    return (program or _program(cfg))(
        params, jnp.asarray(toks), pool, tables, jnp.asarray(plens),
        jnp.asarray(lens))


def _prefilled(cfg, params, tokens, chunk, between=None):
    """ONE sequence through the suffix program in chunks of ``chunk``
    (each padded to the chunk's width); ``between(pool)`` may change the
    pool between two chunks. Returns the last chunk's logits, the pool and
    the pages."""
    pool, ids = _pool(cfg, 1, len(tokens))
    logits, program = None, _program(cfg)
    for p in range(0, len(tokens), chunk):
        if p and between is not None:
            pool = between(pool)
        logits, pool = _chunk(cfg, params, pool, ids, [tokens[p:p + chunk]],
                              [p], chunk, program=program)
    return np.asarray(logits[0]), pool, ids[0]


def _reference(cfg, params, tokens, rows):
    from benchmarks.reference import phi4flash_ref

    return np.asarray(phi4flash_ref.logits(params, tokens, cfg, rows=rows))


@pytest.mark.parametrize("n,chunk", [(37, 64), (37, 16), (23, 8)])
def test_prefill_whole_and_chunked_gives_the_references_logits(model, n,
                                                               chunk):
    """A chunk's last part is shorter than its width: the padded positions
    leave the state and the convolution's tail as they were."""
    cfg, params = model
    tokens = _tokens(cfg, n)
    got, _, _ = _prefilled(cfg, params, tokens, chunk)
    want = _reference(cfg, params, tokens, [n - 1])[0]
    assert np.abs(got - want).max() < TOL


def test_a_wave_of_padded_rows_gives_each_rows_logits(model):
    """Three prompts of different lengths as one whole-prefill wave of
    four rows: every row gets the reference's logits at ITS last token,
    and the pad row's state lands in the scratch row."""
    cfg, params = model
    parts = [_tokens(cfg, n, seed=n) for n in (9, 30, 17)]
    pool, ids = _pool(cfg, 3, 32)
    logits, pool = _chunk(cfg, params, pool, ids, parts, [0, 0, 0], 32,
                          pads=1)
    for r, part in enumerate(parts):
        want = _reference(cfg, params, part, [len(part) - 1])[0]
        assert np.abs(np.asarray(logits[r]) - want).max() < TOL, r
    ssm = np.asarray(pool["ssm"])
    # The pad row repeats row 2 and wrote row 2's state to the scratch row.
    assert np.array_equal(ssm[:, 3], ssm[:, 2]) and ssm[:, 2].any()


def test_decode_steps_give_the_references_logits_and_idle_state_stays(model):
    """Eight decode steps after a chunked prefill of 30 tokens in slot 1,
    the view built by ``live_page_view``; slot 0, which owns no row of the
    view, keeps the state it was given bit for bit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import phi4flash_decode as pd

    cfg, params = model
    n, steps = 30, 8
    tokens = _tokens(cfg, n + steps, seed=1)
    pool, ids = _pool(cfg, 2, n + steps)
    _, pool = _chunk(cfg, params, pool, ids, [tokens[:9], tokens[:16]],
                     [0, 0], 16)
    _, pool = _chunk(cfg, params, pool, ids[1:], [tokens[16:n]], [16], 16,
                     slots=[1])
    idle = {k: np.asarray(pool[k][:, 0]) for k in ("ssm", "conv")}
    want = _reference(cfg, params, tokens, list(range(n, n + steps)))
    table = np.zeros((2, 16), np.int32)
    table[1, :len(ids[1])] = ids[1]
    step = jax.jit(pd.paged_decode_step, static_argnames=("config",))
    for j in range(steps):
        pos = n + j
        first = max(0, pos - cfg.window + 1) // T
        view = pd.live_page_view(
            {"full": table, "window": table},
            {"full": np.asarray([0, pos // T + 1]),
             "window": (np.asarray([0, first]),
                        np.asarray([0, pos // T + 1 - first]))},
            {"full": 16, "window": 4})
        logits, pool, lens = step(
            params, pool, {k: jnp.asarray(v) for k, v in view.items()},
            jnp.asarray([5, pos], jnp.int32),
            jnp.asarray([0, tokens[pos]], jnp.int32), config=cfg)
        assert np.abs(np.asarray(logits[1]) - want[j]).max() < TOL, j
        assert int(lens[1]) == pos + 1
    for k, was in idle.items():
        assert np.array_equal(np.asarray(pool[k][:, 0]), was), k


@pytest.mark.parametrize("block_pages", [16, 3])
def test_the_engine_serves_the_references_tokens(model, monkeypatch,
                                                 block_pages):
    """Through ``DecodeEngine``: admission, chunks, window pages freed as
    they are passed, five prompts over four slots; the decode's kernel
    reads a slot's groups of full pages where they lie (the running
    softmax across a slot's lists, a grid that stops at the live ones), a
    list at once and in sub-blocks that do not divide it."""
    from benchmarks.reference import phi4flash_ref
    from ray_tpu.models import phi4flash_decode
    from ray_tpu.ops import paged_decode_attention
    from ray_tpu.serve.decode import DecodeEngine

    monkeypatch.setattr(paged_decode_attention, "BLOCK_PAGES", block_pages)
    cfg, params = model
    eng = DecodeEngine(params, cfg, slots=4, capacity=256, page_tokens=4,
                       prefill_chunk_tokens=32, model=phi4flash_decode,
                       step_timeline=0, metrics_enabled=False,
                       trace_spans=False)
    prompts = [_tokens(cfg, n, seed=n).tolist()
               for n in (50, 7, 100, 33, 21)]
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, (20, 30, 10, 5, 12))]
    for _ in range(400):
        eng.step()
        if all(r.done.is_set() for r in reqs):
            break
    margins = phi4flash_ref.served_token_margins(
        eng.params, cfg, prompts, [r.output for r in reqs])
    assert len(margins) == 77 and max(margins) < TOL
    eng.shutdown()


def _left_out(monkeypatch, cfg, params, what):
    """The PROGRAM with one of the model's pieces left out: ``(cfg,
    params, between)`` to prefill with."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import phi4flash_decode as pd

    def leaves(name, fill):
        return jax.tree_util.tree_map_with_path(
            lambda path, w: jnp.full_like(w, fill) if str(getattr(
                path[-1], "key", "")) == name else w, params)

    def wiped(name):
        return lambda pool: {**pool, name: jnp.zeros_like(pool[name])}

    if what == "lam term":
        monkeypatch.setattr(pd, "_lam", lambda layer, lam0: 0.0)
    elif what == "sub-norm":
        monkeypatch.setattr(pd, "rms_norm", lambda x, w, eps: x)
    elif what == "m in the GMU":
        gmu = pd._gmu
        monkeypatch.setattr(
            pd, "_gmu", lambda layer, x, m, c: gmu(layer, x,
                                                   jnp.ones_like(m), c))
    elif what == "D":
        return cfg, leaves("D", 0.0), None
    elif what == "conv tail across a chunk edge":
        return cfg, params, wiped("conv")
    elif what == "state across a chunk edge":
        return cfg, params, wiped("ssm")
    elif what.startswith("window"):
        # 64 is wider than the prompt: no window at all.
        return dataclasses.replace(
            cfg, window={"window": 64, "window - 1": cfg.window - 1,
                         "window + 1": cfg.window + 1}[what]), params, None
    return cfg, params, None


@pytest.fixture(scope="module")
def sound(model):
    """21 tokens in chunks of 16 (over the window of 12 and one chunk's
    edge) through the whole program: the reference's logits at the last,
    and the program's."""
    cfg, params = model
    tokens = _tokens(cfg, 21, seed=2)
    want = _reference(cfg, params, tokens, [20])[0]
    got, _, _ = _prefilled(cfg, params, tokens, 16)
    assert np.abs(got - want).max() < TOL
    return tokens, want


@pytest.mark.parametrize("what", [
    "lam term", "sub-norm", "m in the GMU", "D", "window", "window - 1",
    "window + 1", "conv tail across a chunk edge",
    "state across a chunk edge"])
def test_each_piece_changes_the_result_when_left_out(model, sound,
                                                     monkeypatch, what):
    cfg, params = model
    tokens, want = sound
    less_cfg, less_params, between = _left_out(monkeypatch, cfg, params,
                                               what)
    less, _, _ = _prefilled(less_cfg, less_params, tokens, 16, between)
    assert np.abs(less - want).max() > MOVES, what


def test_layers_shapes_and_count_follow_the_published_config():
    from ray_tpu.models import phi4flash

    c = phi4flash.Phi4FlashConfig()
    kinds = c.kinds()
    assert kinds[:4] == ("mamba", "window", "mamba", "window")
    assert kinds[16:20] == ("mamba", "full", "gmu", "cross")
    assert [kinds.count(k) for k in
            ("mamba", "window", "full", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert (c.head_dim, c.d_inner, c.dt_rank, c.kv_width) == (
        64, 5120, 160, 1280)
    assert abs(c.lam0(17) - (0.8 - 0.6 * np.exp(-5.1))) < 1e-12
    n = phi4flash.param_count(c)
    assert 3.85e9 < n < 3.86e9, n
    with pytest.raises(ValueError):
        phi4flash.Phi4FlashConfig(n_layers=10)
