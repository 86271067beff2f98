"""Compiles for a TPU v5e that is described and not attached (the chip's
compiler is installed here): what interpret mode cannot show of the kernels
on DeepSeek-V2's serving path at its published widths, about two seconds
each, at no chip time. Nothing runs, so nothing here says anything about
results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports this
file. All such tests live in this one file for the same reason."""

import pytest


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep it out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("rows,queries,keys", [
    (1, 2048, 16384),     # a chunk at the end of the longest document
    (1, 16, 4096),        # the smallest suffix bucket
    (4, 256, 256),        # an admission wave of short prompts
])
def test_latent_prefill_kernel_compiles_at_published_widths(
        one_chip, no_compile_cache, monkeypatch, rows, queries, keys):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import latent_attention

    # The backend here is the CPU; the kernel under test is the chip's.
    monkeypatch.setattr(latent_attention, "_interpret", lambda: False)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    heads, nope, rope, v = 128, 128, 64, 128
    compiled = jax.jit(
        lambda *a: latent_attention.latent_prefill_attention(*a, 0.1147)
    ).lower(shape(rows, heads, queries, nope),
            shape(rows, heads, queries, rope),
            shape(rows, heads, keys, nope), shape(rows, keys, rope),
            shape(rows, heads, keys, v),
            shape(rows, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_attn" in text
    # The scores never exist outside the kernel: no temporary of the
    # program comes near (heads x queries x keys) float32.
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28


def test_ragged_expert_matmul_is_one_kernel_without_a_copy_of_the_stack(
        one_chip, no_compile_cache):
    """``held_experts_ffn`` over a stack of layers: the chip's compiler
    turns each ragged matmul into one kernel that reads the stack where it
    lies (a layer sliced out of it would be a 1.9 GB temporary)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    layers, held, dim, width, tokens, k = 4, 40, 5120, 1536, 2048, 6
    experts = {"w_gate": shape(layers, held, dim, width),
               "w_up": shape(layers, held, dim, width),
               "w_down": shape(layers, held, width, dim)}

    def layer(x, idx, w, experts, which):
        return moe.held_experts_ffn(x, idx, w, experts, (0, held),
                                    layer=which)

    compiled = jax.jit(layer).lower(
        shape(tokens, dim), shape(tokens, k, dtype=jnp.int32),
        shape(tokens, k, dtype=jnp.float32), experts,
        shape(dtype=jnp.int32)).compile()
    assert compiled.as_text().count("ragged-dot") >= 3
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


@pytest.mark.parametrize("rows,kv,queries,keys,window", [
    (1, 4, 2048, 32768, None),   # a full layer's chunk at 30k of context
    (1, 8, 2048, 2240, 128),     # a window layer's: the chunk + its window
    (1, 8, 16, 256, 128),        # the smallest suffix bucket
    (4, 4, 256, 256, None),      # an admission wave of short prompts
])
def test_chunk_attention_kernel_compiles_at_mimos_widths(
        one_chip, no_compile_cache, monkeypatch, rows, kv, queries, keys,
        window):
    """``ops/chunk_attention.py`` at MiMo-V2.5's published widths: 64 query
    heads over 4 or 8 key heads, keys 192 wide (padded to 256 lanes in the
    wrapper), values 128, a sink, both static variants."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import chunk_attention

    monkeypatch.setattr(chunk_attention, "_interpret", lambda: False)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, k, v, qo, ko, s: chunk_attention.chunk_attention(
            q, k, v, qo, ko, 192 ** -0.5, window, s)
    ).lower(shape(rows, 64, queries, 192), shape(rows, kv, keys, 192),
            shape(rows, kv, keys, 128), shape(rows, dtype=jnp.int32),
            shape(rows, dtype=jnp.int32),
            shape(64, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    name = "chunk_attn_full" if window is None else "chunk_attn_window"
    assert "tpu_custom_call" in text and name in text
    # The scores never exist outside the kernel.
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28
