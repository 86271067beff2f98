"""Compiles for a TPU v5e that is described and not attached (the chip's
compiler is installed here): what interpret mode cannot show of the kernels
on DeepSeek-V2's and MiMo-V2.5's serving paths at their published widths,
about two seconds each, the decode's paged-attention kernel at
Phi-4-mini-flash's widths and, in its one-pool form, at DeepSeek-V2's,
DeepSeek-V2's decode step whole (eight seconds: it holds no copy of its
view's pages), Phi-4-mini-flash's decode and prefill programs
whole at its published widths (ten seconds each, with what they take of
the chip's memory), and what the chip's partitioner makes of the
four-chip FSDP train step (a quarter of a minute), and the three flash-attention training kernels at that step's
shape under the names the benchmark reads, at no chip time. Nothing runs, so nothing here says anything about
results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports this
file. All such tests live in this one file for the same reason."""

import pytest


@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(four_chips):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(four_chips[0])


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


@pytest.mark.parametrize("held,width,k,dim,mlp,layers,plan", [
    (16, 128, 8, 4096, 4096, 4, (5120, 256)),     # command-a-plus
    (40, 160, 6, 5120, 1536, 4, (6400, 128)),     # deepseek-v2
    (16, 256, 8, 4096, 2048, 6, (2560, 128)),     # mimo-v2.5
])
def test_a_chunks_held_pairs_compile_at_published_widths(
        one_chip, no_compile_cache, monkeypatch, held, width, k, dim, mlp,
        layers, plan):
    """A chunk's call of ``held_experts_ffn`` with its router (PR 50): the
    three kernels of ``ops/grouped_matmul.py`` pass Mosaic at the served
    widths with their column blocks in fast memory, once each in the one
    body the passes share, read the stack where it lies, and nothing of
    the call is as large as the ``tokens x k`` rows were."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import grouped_matmul, moe

    monkeypatch.setattr(grouped_matmul, "_interpret", lambda: False)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    tokens = 2048
    router = moe.Router(experts=width, top_k=k)
    experts = {"w_gate": shape(layers, held, dim, mlp),
               "w_up": shape(layers, held, dim, mlp),
               "w_down": shape(layers, held, mlp, dim)}

    def layer(x, idx, w, experts, which):
        return moe.held_experts_ffn(x, idx, w, experts, (0, held),
                                    layer=which, router=router)

    assert moe.held_rows(tokens * k, held, width) == plan
    compiled = jax.jit(layer).lower(
        shape(tokens, dim), shape(tokens, k, dtype=jnp.int32),
        shape(tokens, k, dtype=jnp.float32), experts,
        shape(dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3 and "ragged-dot" not in text
    for kernel in (grouped_matmul.SWIGLU, grouped_matmul.MATMUL,
                   grouped_matmul.ADD_ROWS):
        assert kernel in text
    # ``tokens x k`` rows of the model's width in bfloat16 are 126-134 MB
    # and the path before held four of them and a float32 one.
    assert compiled.memory_analysis().temp_size_in_bytes < 2.6e8


def _chunk_program_fits(module, groups, queries, keys, window, d, dv, heads):
    """The heads a program of ``ops/chunk_attention.py`` takes at this
    shape, and its reckoned fast memory inside the module's budget (Mosaic
    refuses a program past the limit it was given, so the compile above
    holds the reckoning to the truth from the other side)."""
    block_q, block_k = module.tiles(queries, window)
    block_k = min(block_k, -(-keys // 128) * 128)
    assert module.heads_a_step(groups, block_q, block_k, d, dv, 2) == heads
    assert module._vmem_bytes(heads, block_q, block_k, d, dv, 2) \
        <= module.VMEM_BUDGET


@pytest.mark.parametrize("rows,kv,queries,keys,window,heads", [
    (1, 4, 2048, 32768, None, 8),  # a full layer's chunk at 30k of context
    (1, 8, 2048, 2240, 128, 8),    # a window layer's: the chunk + its window
    (1, 8, 16, 256, 128, 8),       # the smallest suffix bucket
    (4, 4, 256, 256, None, 16),    # an admission wave of short prompts
])
def test_chunk_attention_kernel_compiles_at_mimos_widths(
        one_chip, no_compile_cache, monkeypatch, rows, kv, queries, keys,
        window, heads):
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
    # A program takes 8 of a full layer's 16 query heads a key head at
    # 512 x 1,024 tiles (keys 256 lanes wide), every one of them at the
    # smaller tiles, and all 8 of a window layer's.
    _chunk_program_fits(chunk_attention, 64 // kv, queries, keys, window,
                        256, 128, heads=heads)


def test_fsdp4_step_gathers_its_weights_once_and_fits_the_chip(
        four_chips, no_compile_cache, monkeypatch):
    """``internlm2-1.8b.pretrain_fsdp4``'s step as the benchmark builds it
    (its configuration, its traffic, ``build_train_step``), compiled for
    the four described chips: every weight is gathered whole once, outside
    the microbatch loop (depth 0); inside it no weight moves; the only
    whole-parameter reduction left is the head's, once a microbatch and
    not once a loss chunk (this compiler turns a reduce-scatter along a
    first dim into an all-reduce and a slice); and the program fits the
    chip's 15.75 GB with the whole bfloat16 copy in it."""
    import json
    import os
    import sys

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import families
    from ray_tpu.ops import flash_attention
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.parallel.sharding import batch_sharding, tree_shardings

    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", "internlm2-1.8b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "traffic", "pretrain_fsdp4.json")) as f:
        job = json.load(f)
    fam = families.load(cfg["family"]).Train(cfg["model"], job,
                                             cfg["train_flags"])
    mesh = MeshSpec(**job["mesh"]).build(four_chips)
    opt = optax.adamw(job["learning_rate"], weight_decay=job["weight_decay"])

    def shaped(tree, shardings):
        return jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            tree, shardings)

    shapes = jax.eval_shape(fam.init, jax.random.key(0))
    layout = tree_shardings(mesh, fam.axes())
    params_def = jax.tree.structure(shapes)

    def like_params(node):
        return jax.tree.structure(node) == params_def

    state_shapes = jax.eval_shape(opt.init, shapes)
    state_layout = jax.tree.map(
        lambda node: layout if like_params(node)
        else NamedSharding(mesh, P()), state_shapes, is_leaf=like_params)
    params = shaped(shapes, layout)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (fam.items, fam.seq + 1), jnp.int32,
        sharding=batch_sharding(mesh))}
    step = ts.build_train_step(fam.loss, opt, mesh,
                               accum_steps=int(job["accum"]))
    compiled = step.lower(params, shaped(state_shapes, state_layout),
                          batch).compile()
    table = ts.collective_table(compiled)

    def dims(shape):
        return tuple(sorted(d for d in shape if d != 1))

    weights = {path: leaf.shape for path, leaf in
               jax.tree_util.tree_leaves_with_path(shapes) if leaf.ndim >= 2
               and "norm" not in jax.tree_util.keystr(path)}
    whole = {dims(shape) for shape in weights.values()}
    sliced = {dims(shape[1:]) for path, shape in weights.items()
              if "layers" in jax.tree_util.keystr(path)}
    once = {dims(r["shape"]): r["count"] for r in table
            if r["kind"] == "all-gather" and r["depth"] == 0}
    assert all(once.get(d) == 1 for d in whole), once
    moved = [r for r in table if r["kind"] == "all-gather" and r["depth"]
             and dims(r["shape"]) in whole | sliced]
    assert not moved, moved
    reduced = [(r["shape"], r["depth"], r["count"]) for r in table
               if r["kind"] == "all-reduce"
               and dims(r["shape"]) in whole | sliced]
    assert reduced in ([], [(shapes["lm_head"].shape, 1, 1)]), reduced
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75e9), memory


def test_flash_kernels_compile_at_the_train_cell_shape_under_their_names(
        one_chip, no_compile_cache, monkeypatch):
    """``ops/flash_attention.py`` forward and backward at
    ``internlm2-1.8b.pretrain_fsdp4``'s call, ``[1,16,4096,128]`` with 8 KV
    heads in bfloat16, causal, with the tiles the code chooses: the three
    kernels lower through Mosaic inside the VMEM each asks for, and the
    benchmark's readers (``progtrace.kernel_of`` / ``result_shape``) find
    them by name, ``flash_bwd_dq``'s first result ``[1,16,4096,128]``."""
    import os
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import progtrace
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 4096, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 128), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, causal=True).astype(jnp.float32)),
        argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    calls = {}
    for line in compiled.as_text().splitlines():
        kernel = progtrace.kernel_of(line.strip())
        if kernel:
            calls.setdefault(kernel, []).append(
                progtrace.result_shape(line.strip()))
    assert calls == {
        "flash_fwd": [("bf16", (1, 16, 4096, 128))],
        "flash_bwd_dq": [("bf16", (1, 16, 4096, 128))],
        # The group's sum is written once, in the input's dtype.
        "flash_bwd_dkv": [("bf16", (1, 8, 4096, 128))],
    }, calls
    stats = fa.schedule_stats(4096, 4096, 128, jnp.bfloat16)
    for name, s in stats.items():
        assert s["visited"] == s["live"] < s["total"], (name, s)
        assert s["vmem_bytes"] <= fa._VMEM_BUDGET, (name, s)
    # No float32 copy of dK / dV a query head, no lane-broadcast rows for
    # dK/dV: what the three calls leave in HBM besides their results.
    assert compiled.memory_analysis().temp_size_in_bytes < 150e6


@pytest.mark.parametrize("lists,pages,pool,window,dtype", [
    (64, 9, 8 * 705, 512, "bfloat16"),     # a window layer: a list a slot
    (256, 16, 8193, None, "bfloat16"),     # the shared cache at its rung
    (512, 16, 8193, None, "float32"),      # the top rung, a float32 pool
])
def test_paged_decode_attention_kernel_compiles_at_phi4flashs_widths(
        one_chip, no_compile_cache, monkeypatch, lists, pages, pool, window,
        dtype):
    """``ops/paged_decode_attention.py`` at Phi-4-mini-flash's widths (40
    query rows over 1,280 flat lanes, pages of 64 tokens): one Mosaic
    kernel whose grid's length is traced, whose pools stay where they are
    (no temporary the size of a list of pages) and whose double buffer is
    asked for (10.5 MB at 16 pages of bfloat16, over the 16 MiB a call
    gets unasked in float32)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_decode_attention as pda

    monkeypatch.setattr(pda, "_interpret", lambda: False)

    def shape(*dims, dtype=jnp.dtype(dtype)):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    i32 = jnp.int32

    def attend(q, k, v, lists, owner, index, pos):
        return pda.paged_decode_attention(
            q, k, v, pda.page_lists(lists, owner, index, pos, 64, window),
            0.125)

    compiled = jax.jit(attend).lower(
        shape(64, 40, 1280), shape(pool, 64, 1280), shape(pool, 64, 1280),
        shape(lists, pages, dtype=i32), shape(lists, dtype=i32),
        shape(lists, pages, dtype=i32), shape(64, dtype=i32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and pda.NAME in text
    assert compiled.memory_analysis().temp_size_in_bytes < 30e6


@pytest.mark.parametrize("lists,dtype", [
    (256, "bfloat16"),     # the cell's rung: 4,096 rows of the view
    (512, "float32"),      # the top rung, a float32 pool
])
def test_paged_decode_attention_one_pool_compiles_at_deepseeks_widths(
        one_chip, no_compile_cache, monkeypatch, lists, dtype):
    """The ONE-POOL form at DeepSeek-V2's widths (128 absorbed query rows
    over the 640 lanes of a latent row, values its first 512, pages of 64
    tokens, a layer's offset in the flat pool of five): one Mosaic kernel
    that is handed the latent leaf alone, holds one double buffer and
    scores a list of 16 pages whole."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_decode_attention as pda

    monkeypatch.setattr(pda, "_interpret", lambda: False)

    def shape(*dims, dtype=jnp.dtype(dtype)):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    i32 = jnp.int32

    def attend(q, pool, lists, owner, index, pos, base):
        return pda.paged_decode_attention(
            q, pool, None, pda.page_lists(lists, owner, index, pos, 64),
            0.1, base, value_width=512)

    compiled = jax.jit(attend).lower(
        shape(32, 128, 640), shape(5 * 4097, 64, 640),
        shape(lists, 16, dtype=i32), shape(lists, dtype=i32),
        shape(lists, 16, dtype=i32), shape(32, dtype=i32),
        shape(dtype=i32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and pda.NAME in text
    assert compiled.memory_analysis().temp_size_in_bytes < 30e6
    m, l, acc = compiled.out_info
    assert acc.shape == (32, 128, 512) and l.shape == (32, 128)


@pytest.mark.parametrize("rows", [4096, 8192])
def test_deepseek_decode_compiles_at_published_widths_with_no_copy_of_the_view(
        one_chip, no_compile_cache, monkeypatch, rows):
    """``models/deepseek_decode.py::paged_decode_step`` at DeepSeek-V2's
    published widths and the cell's cut and layout (5 layers, 40 held
    experts, 32 slots, 4,096 latent pages of 64; shapes only) at the
    cell's rung and at the top one: every layer's attention is the kernel
    ``paged_decode_attn`` over the latent pool where it lies, so the
    program's temporaries are a few MB (the parent's copy of the view's
    rows and its float32 scores were 0.51 GB at 4,096 rows and 1.11 GB at
    8,192, by this same analysis)."""
    import dataclasses
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import deepseek, moe_decode
    from ray_tpu.models import deepseek_decode as dd
    from ray_tpu.ops import paged_decode_attention

    monkeypatch.setattr(paged_decode_attention, "_interpret", lambda: False)
    cfg = dataclasses.replace(deepseek.DeepseekConfig(), n_layers=5,
                              experts_held=(0, 40), vocab_size=25600)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        lambda path, spec: shape(
            spec[0], jnp.float32 if spec[1] is None else jnp.bfloat16),
        deepseek._shapes(cfg), is_leaf=moe_decode.is_spec)
    pool = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: dd.init_page_pool(cfg, 4096, 64)))
    i32 = jnp.int32
    compiled = jax.jit(
        lambda p, pool, view, lens, toks: dd.paged_decode_step(
            p, pool, view, lens, toks, cfg), donate_argnums=(1,)
    ).lower(params, pool, shape((3, rows), i32), shape((32,), i32),
            shape((32,), i32)).compile()
    mem = compiled.memory_analysis()
    # 10.33 GB of weights and the 1.68 GB pool, written where it lies.
    assert 12.0e9 < mem.argument_size_in_bytes < 12.02e9
    assert mem.alias_size_in_bytes > 1.67e9
    assert mem.temp_size_in_bytes < 30e6
    text = compiled.as_text()
    assert "paged_decode_attn" in text
    # No array of the view's rows of pages, as pages or as groups.
    assert not re.search(r"bf16\[%d,64,640\]|bf16\[%d,1024,640\]"
                         % (rows, rows // 16), text)


@pytest.mark.parametrize("program", ["decode:4096", "decode:8192",
                                     "chunk:1x512x32", "wave:8x512x8"])
def test_phi4flash_programs_compile_at_published_widths_inside_the_chip(
        one_chip, no_compile_cache, monkeypatch, program):
    """``models/phi4flash_decode.py`` at Phi-4-mini-flash's published
    widths and the cell's layout (64 slots, 8,192 full and 704 window pages
    of 64, shapes only): the decode step at the cell's rung (4,096 rows)
    and at the top one, whose kernel reads the pages where they lie (no
    copy of the view, at any rung), a 512-token chunk over 32 pages and a
    whole-prefill wave of ``PREFILL_TOKENS_MAX``. Arguments (12.45 GB:
    weights, both kinds of page, the state) and temporaries fit the chip's
    15.75 GB."""
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import phi4flash
    from ray_tpu.models import phi4flash_decode as pd
    from ray_tpu.ops import chunk_attention, paged_decode_attention

    monkeypatch.setattr(chunk_attention, "_interpret", lambda: False)
    monkeypatch.setattr(paged_decode_attention, "_interpret", lambda: False)
    cfg = phi4flash.Phi4FlashConfig()
    slots, T = 64, 64

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        lambda path, spec: shape(
            spec[0], jnp.float32 if str(path[-1].key)
            in phi4flash.FLOAT32_LEAVES else jnp.bfloat16),
        phi4flash._shapes(cfg), is_leaf=phi4flash._is_spec)
    pool = jax.tree.map(
        lambda a: shape(a.shape, a.dtype), jax.eval_shape(
            lambda: pd.init_page_pool(cfg, {"full": 8192, "window": 704}, T,
                                      slots=slots)))
    i32 = jnp.int32
    kind, dims = program.split(":")
    if kind == "decode":
        view = {"full": shape((3, int(dims)), i32),
                "window": shape((2, slots, 9), i32)}
        compiled = jax.jit(
            lambda p, pool, view, lens, toks: pd.paged_decode_step(
                p, pool, view, lens, toks, cfg), donate_argnums=(1,)
        ).lower(params, pool, view, shape((slots,), i32),
                shape((slots,), i32)).compile()
    else:
        rows, bucket, width = (int(x) for x in dims.split("x"))
        assert rows * bucket <= pd.PREFILL_TOKENS_MAX
        tables = {"full": shape((rows, width), i32),
                  "window": shape((rows, -(-(bucket + 512) // T) + 1), i32),
                  "window_first": shape((rows,), i32),
                  "slots": shape((rows,), i32),
                  "ends": shape((rows,), jnp.bool_)}
        compiled = jax.jit(
            lambda p, toks, pool, bt, plens, lens: pd.paged_prefill_suffix(
                p, toks, pool, bt, cfg, plens, lens), donate_argnums=(2,)
        ).lower(params, shape((rows, bucket), i32), pool, tables,
                shape((rows,), i32), shape((rows,), i32)).compile()
        assert "chunk_attn_window" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert 12.4e9 < mem.argument_size_in_bytes < 12.5e9
    # The pool is written where it lies: the donated buffers are aliased.
    assert mem.alias_size_in_bytes > 4.7e9
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < 14.5e9, (program, peak)
    if kind == "decode":
        text = compiled.as_text()
        assert text.count("paged_decode_attn") >= 3   # window, full, cross
        # No list of gathered pages, neither the view's groups of 16 nor a
        # slot's nine window pages, and no room for one.
        assert not re.search(r"bf16\[\d+,(1024|576),1280\]", text)
        assert mem.temp_size_in_bytes < 0.3e9


def test_chunk_attention_tiles_follow_the_window():
    """Tiles from the shape: mimo's window of 128 and phi-4-mini-flash's
    of 512 keep the 256 x 256 they had, Command A+'s 4,096 takes the full
    variant's 512 x 1,024 (6 steps a query tile where 256 x 256 has 18)."""
    from ray_tpu.ops.chunk_attention import tiles

    assert tiles(2048, None) == (512, 1024) and tiles(16, None) == (16, 1024)
    assert tiles(2048, 128) == tiles(2048, 512) == (256, 256)
    assert tiles(16, 128) == (16, 256) and tiles(32, 12) == (32, 256)
    assert tiles(2048, 4096) == (512, 1024) and tiles(16, 4096) == (16, 1024)


@pytest.mark.parametrize("queries,keys,window,heads", [
    (2048, 32768, None, 8),    # the full layer's chunk at 30k of context
    (2048, 6208, 4096, 8),     # a window layer's: the chunk + its window
    (16, 4224, 4096, 16),      # the smallest suffix bucket
])
def test_chunk_attention_kernel_compiles_at_command_a_widths(
        one_chip, no_compile_cache, monkeypatch, queries, keys, window,
        heads):
    """``ops/chunk_attention.py`` at Command A+'s published widths: 128
    query heads of 128 over 8 key heads, keys as wide as values, no sink,
    both static variants, the window variant at the tiles its window of
    4,096 chooses; a program takes ``heads`` of a key head's 16 query
    heads (what the budget allows at 512 x 1,024 tiles, all of them at 16
    queries)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import chunk_attention

    monkeypatch.setattr(chunk_attention, "_interpret", lambda: False)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, k, v, qo, ko: chunk_attention.chunk_attention(
            q, k, v, qo, ko, 128 ** -0.5, window)
    ).lower(shape(1, 128, queries, 128), shape(1, 8, keys, 128),
            shape(1, 8, keys, 128), shape(1, dtype=jnp.int32),
            shape(1, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    name = "chunk_attn_full" if window is None else "chunk_attn_window"
    assert "tpu_custom_call" in text and name in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28
    _chunk_program_fits(chunk_attention, 16, queries, keys, window, 128,
                        128, heads=heads)


@pytest.mark.parametrize("program", ["decode:8192", "decode:12288",
                                     "chunk:1x2048x512", "chunk:1x16x128"])
def test_command_a_programs_compile_at_published_widths_inside_the_chip(
        one_chip, no_compile_cache, monkeypatch, program):
    """``models/cohere2_moe_decode.py`` at Command A+'s published widths
    and the cell's layout (24 slots, 9,216 full and 1,752 window pages of
    64, shapes only): the decode step at the cell's rung and at the top
    one, both kinds' pages read where they lie by ``paged_decode_attn``
    (128 query rows over 1,024 flat lanes, a slot's 65 window pages in
    lists of 16: no list of gathered pages and no room for one), and a
    2,048-token chunk at 32k of context over the 97 window columns.
    Arguments (13.26 GB: 9.47 GB of weights, 2.42 + 1.38 GB of pages) and
    temporaries fit the chip's 15.75 GB."""
    import dataclasses
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import cohere2_moe, moe_decode
    from ray_tpu.models import cohere2_moe_decode as md
    from ray_tpu.ops import (chunk_attention, grouped_matmul,
                             paged_decode_attention)

    monkeypatch.setattr(chunk_attention, "_interpret", lambda: False)
    monkeypatch.setattr(paged_decode_attention, "_interpret", lambda: False)
    monkeypatch.setattr(grouped_matmul, "_interpret", lambda: False)
    cfg = dataclasses.replace(cohere2_moe.Cohere2MoeConfig(), n_layers=4,
                              experts_held=(0, 16), vocab_size=32768)
    slots, T = 24, 64

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        lambda path, spec: shape(
            spec[0], jnp.float32 if str(path[-1].key)
            in cohere2_moe.FLOAT32_LEAVES else jnp.bfloat16),
        cohere2_moe._shapes(cfg), is_leaf=moe_decode.is_spec)
    pool = jax.tree.map(
        lambda a: shape(a.shape, a.dtype), jax.eval_shape(
            lambda: md.init_page_pool(
                cfg, {"full": 9216, "window": 24 * 65 + 6 * 32}, T)))
    i32 = jnp.int32
    kind, dims = program.split(":")
    if kind == "decode":
        view = {"full": shape((3, int(dims)), i32),
                "window": shape((2, slots, 65), i32)}
        compiled = jax.jit(
            lambda p, pool, view, lens, toks: md.paged_decode_step(
                p, pool, view, lens, toks, cfg), donate_argnums=(1,)
        ).lower(params, pool, view, shape((slots,), i32),
                shape((slots,), i32)).compile()
    else:
        rows, bucket, width = (int(x) for x in dims.split("x"))
        tables = {"full": shape((rows, width), i32),
                  "window": shape((rows, -(-(bucket + 4096) // T) + 1), i32),
                  "window_first": shape((rows,), i32)}
        compiled = jax.jit(
            lambda p, toks, pool, bt, plens, lens: md.paged_prefill_suffix(
                p, toks, pool, bt, cfg, plens, lens), donate_argnums=(2,)
        ).lower(params, shape((rows, bucket), i32), pool, tables,
                shape((rows,), i32), shape((rows,), i32)).compile()
        text = compiled.as_text()
        assert "chunk_attn_window" in text and "chunk_attn_full" in text
        # A chunk of 2,048 is cut to the held pairs (PR 50), one of 16 is not.
        assert ("moe_grouped_swiglu" in text) == (bucket == 2048)
    mem = compiled.memory_analysis()
    assert 13.2e9 < mem.argument_size_in_bytes < 13.3e9
    # The pool is written where it lies: the donated buffers are aliased.
    assert mem.alias_size_in_bytes > 3.7e9
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < 14.2e9, (program, peak)
    if kind == "decode":
        text = compiled.as_text()
        assert text.count("paged_decode_attn") >= 2    # window, full
        # No slot's 65 (or 80) window pages and no view's groups gathered.
        assert not re.search(r"bf16\[\d+,(1024|4160|5120),1024\]", text)
        assert mem.temp_size_in_bytes < 0.3e9


@pytest.mark.parametrize("program", ["decode", "chunk:1x2048", "chunk:1x16"])
def test_brumby_programs_compile_at_published_widths_inside_the_chip(
        one_chip, no_compile_cache, monkeypatch, program):
    """``models/brumby_decode.py`` at Brumby's published widths and the
    cell's layout (8 layers, 16 slots, no page of any kind; shapes only):
    the ONE decode step, whose state tiles go through ``retention_step``
    where they lie (no copy of a 5.17 GB leaf), and a 2,048-token chunk.
    Arguments (13.57 GB: 8.40 GB of weights, 5.17 GB of state) and
    temporaries fit the chip's 15.75 GB."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import brumby, moe_decode
    from ray_tpu.models import brumby_decode as bd
    from ray_tpu.ops import power_retention

    monkeypatch.setattr(power_retention, "_interpret", lambda: False)
    cfg = dataclasses.replace(brumby.BrumbyConfig(), n_layers=8)
    slots = 16

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        lambda path, spec: shape(
            spec[0], jnp.float32 if str(path[-1].key)
            in brumby.FLOAT32_LEAVES else jnp.bfloat16),
        brumby._shapes(cfg), is_leaf=moe_decode.is_spec)
    pool = jax.tree.map(
        lambda a: shape(a.shape, a.dtype), jax.eval_shape(
            lambda: bd.init_page_pool(cfg, {}, 64, slots=slots)))
    assert pool["S"].shape == (8, 17, 8, 128, 9216)
    i32 = jnp.int32
    if program == "decode":
        compiled = jax.jit(
            lambda p, pool, view, lens, toks: bd.paged_decode_step(
                p, pool, view, lens, toks, cfg), donate_argnums=(1,)
        ).lower(params, pool, shape((slots,), jnp.bool_),
                shape((slots,), i32), shape((slots,), i32)).compile()
    else:
        rows, bucket = (int(x) for x in program.split(":")[1].split("x"))
        tables = {"slots": shape((rows,), i32),
                  "ends": shape((rows,), jnp.bool_)}
        compiled = jax.jit(
            lambda p, toks, pool, bt, plens, lens: bd.paged_prefill_suffix(
                p, toks, pool, bt, cfg, plens, lens), donate_argnums=(2,)
        ).lower(params, shape((rows, bucket), i32), pool, tables,
                shape((rows,), i32), shape((rows,), i32)).compile()
    mem = compiled.memory_analysis()
    assert 13.5e9 < mem.argument_size_in_bytes < 13.65e9
    # The state is written where it lies: the donated leaves are aliased.
    assert mem.alias_size_in_bytes > 5.1e9
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(program, "arguments", mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes, "peak", peak)
    assert peak < 15.0e9, (program, peak)
    if program == "decode":
        assert "retention_step" in compiled.as_text()
        assert mem.temp_size_in_bytes < 0.3e9


@pytest.mark.parametrize("program", ["decode:8192", "decode:30720",
                                     "chunk:1x2048:512", "chunk:1x16:64",
                                     "wave:2x2048"])
def test_nemotron_h_programs_compile_at_published_widths_inside_the_chip(
        one_chip, no_compile_cache, monkeypatch, program):
    """``models/nemotron_h_decode.py`` at Nemotron-3-Super's published
    widths and the cell's layout (11 layers ``MEMEMEM*EME``, 96 slots,
    24,576 pages; shapes only): the decode step at two rungs of its view,
    whose state tiles go through ``ssd_step`` where they lie (no copy of a
    2.03 GB leaf), a 2,048-token chunk behind 30k tokens of pages (the held
    pairs' way: 20,480 rows of 45,056), the smallest suffix bucket, and a
    whole-prefill wave at the wave cap. Arguments (13.0 GB: 9.30 GB of
    weights, 1.61 of pages, 2.06 of state) and temporaries fit the chip's
    15.75 GB."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import moe_decode, nemotron_h
    from ray_tpu.models import nemotron_h_decode as nd
    from ray_tpu.ops import (chunk_attention, grouped_matmul,
                             paged_decode_attention, ssd)

    for module in (chunk_attention, grouped_matmul, paged_decode_attention,
                   ssd):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    cfg = dataclasses.replace(nemotron_h.NemotronHConfig(), n_layers=11,
                              vocab_size=32768, experts_held=(0, 128))
    slots, pages, T = 96, 24576, 64

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        lambda path, spec: shape(
            spec[0], jnp.float32 if str(path[-1].key)
            in nemotron_h.FLOAT32_LEAVES else jnp.bfloat16),
        nemotron_h._shapes(cfg), is_leaf=moe_decode.is_spec)
    pool = jax.tree.map(
        lambda a: shape(a.shape, a.dtype), jax.eval_shape(
            lambda: nd.init_page_pool(cfg, pages, T, slots=slots)))
    assert pool["ssm"].shape == (5, 97, 128, 64, 128)
    assert pool["conv"].shape == (5, 97, 3, 10240)
    assert pool["full_k"].shape == (1, 24577, 64, 256)
    i32 = jnp.int32
    kind, *rest = program.split(":")
    if kind == "decode":
        rows = int(rest[0])
        compiled = jax.jit(
            lambda p, pool, view, lens, toks: nd.paged_decode_step(
                p, pool, view, lens, toks, cfg), donate_argnums=(1,)
        ).lower(params, pool, shape((3, rows), i32), shape((slots,), i32),
                shape((slots,), i32)).compile()
    else:
        n, bucket = (int(x) for x in rest[0].split("x"))
        width = int(rest[1]) if kind == "chunk" else bucket // T
        tables = {"full": shape((n, width), i32), "slots": shape((n,), i32),
                  "ends": shape((n,), jnp.bool_)}
        compiled = jax.jit(
            lambda p, toks, pool, bt, plens, lens: nd.paged_prefill_suffix(
                p, toks, pool, bt, cfg, plens, lens), donate_argnums=(2,)
        ).lower(params, shape((n, bucket), i32), pool, tables,
                shape((n,), i32), shape((n,), i32)).compile()
    mem = compiled.memory_analysis()
    assert 12.9e9 < mem.argument_size_in_bytes < 13.1e9
    # State and pages are written where they lie: the donated leaves are
    # aliased.
    assert mem.alias_size_in_bytes > 3.6e9
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(program, "arguments", mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes, "peak", peak)
    assert peak < 15.0e9, (program, peak)
    text = compiled.as_text()
    if kind == "decode":
        assert "ssd_step" in text and "paged_decode_attn" in text
        # 2,112 pairs over 128 small held experts: tiles of 64 rows.
        assert "moe_grouped_matmul" in text and "ragged-dot" not in text
        assert mem.temp_size_in_bytes < 0.5e9
    else:
        # Each Mamba-2 layer's chunk is the one kernel: no scan over
        # sub-chunks stacks a float32 ``y``.
        assert text.count('custom_call_target="tpu_custom_call"') >= 5
        assert "ssd_chunk" in text and "f32[16,1,128,128,64]" not in text
        if bucket == 2048 and n == 1:
            assert "moe_grouped_matmul" in text and "moe_add_rows" in text
            assert "chunk_attn" in text


@pytest.mark.parametrize("rows,bucket", [
    (1, 2048),      # a prefill chunk
    (1, 16),        # the smallest suffix bucket: one padded sub-chunk
    (4, 1024),      # a whole-prefill wave at the wave cap
])
def test_ssd_chunk_kernel_compiles_at_published_widths(
        one_chip, no_compile_cache, monkeypatch, rows, bucket):
    """``ops/ssd.py::ssd_chunk`` at Nemotron-3-Super's widths (128 heads of
    64 over 8 groups, a state of 128, bfloat16 operands, sub-chunks of 128)
    through the chip's compiler: the dynamic lane rotations that bring a
    group's and a pair's decays to lane 0, the transposed products, 0.5 MB
    of state scratch. With the tensors flat as the layer holds them nothing
    but the kernel touches ``y``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    monkeypatch.setattr(ssd, "_interpret", lambda: False)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    H, P, G, N = 128, 64, 8, 128
    f32 = jnp.float32

    def layer(xbc, dt, A, D, state, lengths):
        x = xbc[..., :H * P].reshape(rows, bucket, H, P)
        bm = xbc[..., H * P:H * P + G * N].reshape(rows, bucket, G, N)
        cm = xbc[..., H * P + G * N:].reshape(rows, bucket, G, N)
        y, s1 = ssd.ssd_chunk(x, dt, A, bm, cm, D, state, lengths)
        return y.reshape(rows, bucket, H * P), s1

    compiled = jax.jit(layer, donate_argnums=(4,)).lower(
        shape(rows, bucket, H * P + 2 * G * N),
        shape(rows, bucket, H, dtype=f32), shape(H, dtype=f32),
        shape(H, dtype=f32), shape(rows, H, P, N, dtype=f32),
        shape(rows, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "ssd_chunk" in text
    mem = compiled.memory_analysis()
    tokens = rows * max(bucket, ssd.SUB_CHUNK)
    # The kernel's inputs cut from the flat projection (bfloat16) and, under
    # one sub-chunk, the pad: no float32 copy of ``y`` (32 KB a token).
    assert mem.temp_size_in_bytes < tokens * (H * P + 2 * G * N) * 2 * 1.5
