"""KV-cache decode correctness (VERDICT r4 Missing #2): prefill+decode must
reproduce the training-path forward exactly (same weights, same math, no
approximations), across GQA, padding, and sampling shapes."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def small_model():
    import jax

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=97, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, mlp_dim=64, max_seq_len=128)
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def test_prefill_matches_forward(small_model):
    import jax

    from ray_tpu.models import llama, llama_decode

    cfg, params = small_model
    tokens = jax.random.randint(jax.random.key(1), (2, 10), 0,
                                cfg.vocab_size)
    full = llama.forward(params, tokens, cfg)         # (B, S, V)
    cache = llama_decode.init_cache(cfg, 2, 16)
    last, cache = llama_decode.prefill(params, tokens, cache, cfg)
    np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, -1]),
                               rtol=2e-2, atol=2e-2)
    assert int(cache["length"][0]) == 10


@pytest.mark.slow  # 18.9s: step-by-step re-forward; the paged
# tests keep decode parity in tier-1 (PR 16 rebudget)
def test_decode_step_matches_incremental_forward(small_model):
    """Greedy decode through the cache == greedy decode by re-running the
    full forward on the growing sequence (the no-cache oracle)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, llama_decode

    cfg, params = small_model
    prompt = jax.random.randint(jax.random.key(2), (1, 6), 0,
                                cfg.vocab_size)

    # Oracle: argmax over full forward, re-run per token.
    seq = np.asarray(prompt)
    oracle = []
    for _ in range(5):
        logits = llama.forward(params, jnp.asarray(seq), cfg)
        nxt = int(jnp.argmax(logits[0, -1]))
        oracle.append(nxt)
        seq = np.concatenate([seq, [[nxt]]], axis=1)

    # Cache path: prefill once, then decode_step per token.
    cache = llama_decode.init_cache(cfg, 1, 32)
    logits, cache = llama_decode.prefill(params, prompt, cache, cfg)
    got = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    for _ in range(5):
        got.append(int(tok[0]))
        logits, cache = llama_decode.decode_step(params, cache, tok, cfg)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    assert got == oracle, (got, oracle)


def test_padded_prefill_ragged_lengths(small_model):
    """Right-padded rows of different lengths: each row's last-real-token
    logits match an unpadded forward of just that row."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, llama_decode

    cfg, params = small_model
    r1 = jax.random.randint(jax.random.key(3), (1, 9), 0, cfg.vocab_size)
    r2 = jax.random.randint(jax.random.key(4), (1, 4), 0, cfg.vocab_size)
    padded = jnp.zeros((2, 9), jnp.int32)
    padded = padded.at[0].set(r1[0])
    padded = padded.at[1, :4].set(r2[0])
    lengths = jnp.array([9, 4], jnp.int32)

    cache = llama_decode.init_cache(cfg, 2, 16)
    last, cache = llama_decode.prefill(params, padded, cache, cfg,
                                       lengths=lengths)
    solo1 = llama.forward(params, r1, cfg)[0, -1]
    solo2 = llama.forward(params, r2, cfg)[0, -1]
    np.testing.assert_allclose(np.asarray(last[0]), np.asarray(solo1),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(last[1]), np.asarray(solo2),
                               rtol=2e-2, atol=2e-2)
    # Decode continues each row at ITS OWN position.
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    _, cache = llama_decode.decode_step(params, cache, tok, cfg)
    assert list(np.asarray(cache["length"])) == [10, 5]


def test_generate_greedy_deterministic(small_model):
    from ray_tpu.models import llama_decode

    cfg, params = small_model
    prompt = np.array([[5, 17, 3]], np.int32)
    out1 = np.asarray(llama_decode.generate(params, prompt, cfg,
                                            max_new_tokens=6))
    out2 = np.asarray(llama_decode.generate(params, prompt, cfg,
                                            max_new_tokens=6))
    assert out1.shape == (1, 6)
    np.testing.assert_array_equal(out1, out2)
    assert (out1 >= 0).all() and (out1 < cfg.vocab_size).all()


def test_generate_eos_padding(small_model):
    """After a row samples eos, every later token is eos (the stream is
    closed — serving relies on this to free the slot)."""
    import jax

    from ray_tpu.models import llama, llama_decode

    cfg, params = small_model
    prompt = np.array([[1, 2]], np.int32)
    greedy = np.asarray(llama_decode.generate(params, prompt, cfg,
                                              max_new_tokens=8))
    eos = int(greedy[0, 2])  # force eos at the 3rd generated token
    out = np.asarray(llama_decode.generate(params, prompt, cfg,
                                           max_new_tokens=8, eos_id=eos))
    hit = np.where(out[0] == eos)[0]
    assert len(hit) > 0
    first = hit[0]
    assert (out[0, first:] == eos).all()


def test_gqa_cache_width(small_model):
    """The cache is allocated at KV-head width (the GQA bandwidth win)."""
    from ray_tpu.models import llama_decode

    cfg, params = small_model
    cache = llama_decode.init_cache(cfg, 3, 64)
    assert cache["k"].shape == (cfg.n_layers, 3, 64, cfg.n_kv_heads,
                                cfg.head_dim)
    assert llama_decode.cache_bucket(100) == 128
    assert llama_decode.cache_bucket(129) == 256
