"""What the TPU's compiler makes of the latent page, compiled here for a
chip that is described and not attached (no chip time, ~2 s): the reason
``models/axk1.py`` states a page of 640 columns for a latent of 576.

The chip holds an array whose innermost dimension is no multiple of its 128
lanes with another dimension innermost (for ``[layers, blocks, 1, 16, 576]``
the blocks), and a program that reads a block's positions by 576 columns
then starts by copying the whole pool into that order: 2.4 GB a step at the
benchmark's size, seen in the compiled decode step (PERF.md, Findings, PR
42). At 640 columns the pool lies as it is read.

And of the benchmark's 64-row step over that page (~15 s): the attention
over the filled tiles carries one running softmax a row, so the program
holds no float32 array of the table's capacity and gathers no queries to
it; and on a TPU it is the Pallas kernel of ``ops/latent_tiles.py`` a
latent attention (PR 45), which reads the tiles from the pool where they
lie: no chunk of gathered tiles, no loop. The same carry over pages of keys
and values, one reading layer of ``phi-4-mini-flash`` and of ``qwen3-next``
at their cells' rows (~2 s each): since PR 50 the kernel of
``ops/paged_tiles.py``; where a tile is past the kernel's buffers, and
where the pool lies on the four chips of the described host (``tp`` = 4 over
``yi-1.5-34b``'s heads, a latent page a copy a chip), the loop as the
parent's program had it.

And of LongCat-Flash's 64-row step over a page of 8 sublayers (~20 s): the
same page, the same carry and the same kernel in each of its two latent
attentions a layer, and its weights held as both programs read them (no
array of 30 MB is copied into another order).

And of the held experts' grouped products (PR 46): in a program compiled
for the chip every one of them is the Pallas kernel of ``ops/grouped.py``
(two a sparse layer, traced and lowered once a shape from one ``jit``), in
the step, in a prompt's landed-slabs loop and in ``qwen3-next``'s; the
compiler's own ``ragged-dot`` is in none.

And of ZAYA1's 64-row step (PR 49, ~10 s): 16 layers under one scan over a
page of one array of 512 columns and a row of tails a layer; the body of
the loop holds the kernel of the step's mixing and the kernel over the
filled tiles at 8 query rows, and reads a layer's experts out of the one
stack of all layers' where they lie; the device operations a step are
counted. And of its 1 024-token prefill (~5 s): the grouped kernel over
that stack.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demodel_tpu.models import (axk1, latent, longcat_flash, phi4flash,
                                qwen3_next, zaya)
from demodel_tpu.models.common import attend
from demodel_tpu.serve import kvcache


@pytest.fixture(scope="module")
def chips():
    """The four chips of a described v5e host."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # what is compiled for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(chips):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(chips[0])


#: the benchmark's pool: 2 520 MiB of blocks of 16 positions, and the scratch
BLOCKS = 18432 + 1


def _born(shape, sharding) -> list[int]:
    """The order (innermost first) in which the chip holds a pool array of
    ``shape`` born as a program's output, as ``KVBlockPool`` makes it."""
    text = jax.jit(lambda: jnp.zeros(shape, jnp.bfloat16),
                   out_shardings=sharding).lower().compile().as_text()
    layout = re.search(r"entry_computation_layout=\{\(\)->[^{]*\{([\d,]+)",
                       text)
    return [int(d) for d in layout.group(1).split(",")]


def _copied(text: str) -> list[str]:
    """What a compiled program copies or transposes of 30 MB and more (the
    pool, a weight, an expert)."""
    return [m.group(0) for m in re.finditer(
        r"= (bf16|f32)\[([\d,]+)\][^ ]* (copy|transpose)\(", text)
        if np.prod([int(d) for d in m.group(2).split(",")])
        * (2 if m.group(1) == "bf16" else 4) > 30e6]


def _in_place(text: str, attentions: int, rows: int, heads: int,
              values: int) -> None:
    """The compiled step holds the kernel's custom call a latent attention
    (the pool handed to it as it lies), no chunk of gathered tiles, no loop
    under ``attn.tiles``, and no copy of anything of 30 MB (the pool, a
    weight) into another order."""
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "latent_filled_tiles" in line]
    assert len(calls) == attentions
    assert all('custom_call_target="tpu_custom_call"' in c
               and "attn.tiles" in c for c in calls)
    assert not re.findall(r"\[2048,16,[\d,]*640\]", text)
    assert not [line for line in text.splitlines()
                if " while(" in line and "attn.tiles" in line]
    assert not _copied(text)
    # the carry: a row's weighted values, under every head
    assert re.search(rf"f32\[{rows},{heads},{values}\]", text)


def _apart_in_place(text: str, calls: int, Hkv: int, block_tokens: int,
                    hd: int) -> None:
    """The compiled program holds the kernel over pages of keys and values
    apart ``calls`` times (both pools handed to it as they lie), under
    ``attn.tiles``; no loop there, and no chunk of gathered tiles."""
    held = [line for line in text.splitlines()
            if " custom-call(" in line and "paged_filled_tiles" in line]
    assert len(held) == calls
    assert all('custom_call_target="tpu_custom_call"' in c
               and "attn.tiles" in c for c in held)
    assert not [line for line in text.splitlines()
                if " while(" in line and "attn.tiles" in line]
    chunk = kvcache.TILE_CHUNK * kvcache.TILE_BLOCKS
    assert not re.findall(rf"\[{chunk},{Hkv},{block_tokens},{hd}\]", text)
    assert not re.findall(rf"\[{kvcache.TILE_CHUNK},{kvcache.TILE_BLOCKS},"
                          rf"{Hkv},{block_tokens},{hd}\]", text)


def _grouped(lowered, sparse: int) -> str:
    """The program holds no ``ragged-dot``: each sparse layer's two grouped
    products are calls of the kernel, which the program traced and lowered
    once a shape (gate beside up, down) from one ``jit``. Returns the
    compiled text."""
    text = lowered.as_text()
    assert "ragged_dot" not in text
    assert len(re.findall(r"func\.func private @grouped_dot", text)) == 2
    assert text.count('kernel_name = "moe_grouped"') == 2
    assert len(re.findall(r"call @grouped_dot", text)) == 2 * sparse
    compiled = lowered.compile().as_text()
    assert "ragged-dot" not in compiled
    calls = [line for line in compiled.splitlines()
             if " custom-call(" in line and "moe_grouped" in line]
    assert len(calls) == 2 * sparse
    assert all('custom_call_target="tpu_custom_call"' in c
               and "moe.experts" in c for c in calls)
    return compiled


def test_the_latent_page_lies_as_it_is_read(one_chip):
    cfg = axk1.AxK1Config(num_hidden_layers=7, dtype="bfloat16")
    spec = axk1.cache_spec(cfg)
    assert (spec.head_dim, spec.values, cfg.latent_dim) == (640, 512, 576)
    assert spec.head_dim % latent.LANES == 0
    page = (spec.layers, BLOCKS, spec.kv_heads, 16, spec.head_dim)
    # columns innermost, then a block's positions
    assert _born(page, one_chip)[:2] == [4, 3]


def test_a_page_of_576_columns_would_lie_blocks_innermost(one_chip):
    """The finding itself (a pool of a thousand blocks is not laid out so,
    one of four thousand and more is): should a later compiler lay 576
    columns out as they are read, the page can shrink to the latent's own
    width."""
    assert _born((7, BLOCKS, 1, 16, 576), one_chip)[0] == 1
    # a page of K and V at a head of 128 never had the question
    assert _born((8, 4097, 8, 16, 128), one_chip)[:2] == [4, 3]
    assert kvcache.CacheSpec(8, 8, 128).values == 0


def test_the_wide_step_keeps_no_partials_of_the_tables_capacity(one_chip):
    """``ax-k1-519b-l7-ep16``'s decode step as ``axk1-reason`` runs it (64
    rows at 256 table slots each: a capacity of 1 024 tiles), compiled for
    the described chip. At the parent of PR 43 the loop over the filled
    tiles left ``f32[1024,1,64,1,514]`` (135 MB a layer: filled, written a
    chunk a trip, gathered by row into ``[64,16,...]``, copied into another
    order) and gathered its queries to capacity, ``bf16[1024,1,1,64,640]``
    (84 MB); the step's temporaries were 0.47 GB. Since PR 43 the carry is
    a row's (0.12 GB of temporaries, of them the loop's chunk of 128
    gathered tiles, ``bf16[2048,16,640]``, 42 MB); since PR 45
    each of the 7 latent attentions is the kernel that copies a tile's
    blocks from the pool into fast memory, and the temporaries 0.034 GB."""
    doc = json.loads((Path(__file__).parent.parent / "benchmark" / "configs"
                      / "ax-k1-519b-l7-ep16.json").read_text())
    engine = doc.pop("benchmark")["engine"]
    cfg = axk1.AxK1Config.from_hf(doc)
    spec = axk1.cache_spec(cfg)
    rows, slots = engine["max_batch"], 256

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(params, table, lengths, tokens, k):
        cache = kvcache.Paged(k, None, table)
        logits, new, *_stats = axk1.step_decode(params, tokens, cfg, cache,
                                                lengths)
        latents, _fresh = kvcache.parts(new)
        return logits, *kvcache.put_positions(
            k, None, latents, table[:, 0], lengths % engine["block_tokens"])

    params = jax.tree.map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: axk1.init_params(jax.random.key(1), cfg)))
    lowered = jax.jit(decode, donate_argnums=(4,)).lower(
        params, shaped((rows, slots), jnp.int32),
        *(shaped((rows,), jnp.int32),) * 2,
        shaped((spec.layers, BLOCKS, 1, engine["block_tokens"],
                spec.head_dim), jnp.bfloat16))
    compiled = lowered.compile()
    text = compiled.as_text()
    _grouped(lowered, cfg.sparse_layers)
    capacity = rows * slots // kvcache.TILE_BLOCKS
    assert capacity == 1024
    # no float32 partials and no queries a tile of the capacity
    assert not re.findall(rf"f32\[{capacity},[\d,]*51[24]\]", text)
    assert not re.findall(rf"bf16\[{capacity},[\d,]*{spec.head_dim}\]", text)
    _in_place(text, spec.layers, rows, cfg.num_attention_heads, spec.values)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9


@pytest.mark.parametrize("family,config,name", [
    (phi4flash, phi4flash.Phi4FlashConfig, "phi-4-mini-flash"),
    (qwen3_next, qwen3_next.Qwen3NextConfig, "qwen3-next-80b-l12-ep4"),
], ids=["phi-4-mini-flash", "qwen3-next"])
def test_keys_and_values_apart_are_read_where_they_lie(
        one_chip, family, config, name):
    """One reading layer's attention over the filled tiles of pages of keys
    and values, at the published head counts and the cell's rows, compiled
    for the described chip at both wide widths (256 and 2 048 table slots a
    row): since PR 50 the kernel of ``ops/paged_tiles.py``, which copies a
    tile's K blocks and V blocks from the pools into fast memory, and
    carries a row. The program holds no loop, no chunk of gathered tiles
    (``bf16[2048,10,16,128]`` and ``bf16[2048,2,16,256]``: 42 and 34 MB,
    once for the keys and once for the values, a trip) and no float32
    array of the table's capacity; its temporaries are 2.03 and 2.61 MB for
    ``phi-4-mini-flash`` (32 rows, 40 query heads over 10 pairs of 128) and
    1.35 and 2.24 MB for ``qwen3-next`` (16 rows, 16 heads over 2 of 256):
    the index of the tiles, which the kernel takes as scalars. The loop,
    which stays as every other platform's program, held 1.61 and 1.73 MB
    and 1.48 and 2.20 MB beside its chunks."""
    doc = json.loads((Path(__file__).parent.parent / "benchmark" / "configs"
                      / f"{name}.json").read_text())
    engine = doc.pop("benchmark")["engine"]
    cfg = config.from_hf(doc)
    spec = family.cache_spec(cfg)
    rows, bt = engine["max_batch"], engine["block_tokens"]
    H, Hkv, hd = cfg.num_attention_heads, spec.kv_heads, spec.head_dim
    # the cell's budget, all of it pages (the slots take a part of it)
    page = (spec.layers, (engine["kv_mb"] << 20) // (
        2 * spec.layers * bt * Hkv * hd * 2) + 1, Hkv, bt, hd)

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(q, k, v, lengths, table, pk, pv):
        cache = kvcache.Paged(pk, pv, table)
        return attend(q, k, v, lengths[:, None],
                      past=cache.past(0, cache.filled(lengths)))

    for slots in (256, 2048):
        compiled = jax.jit(layer).lower(
            shaped((rows, 1, H, hd)), *(shaped((rows, 1, Hkv, hd)),) * 2,
            shaped((rows,), jnp.int32), shaped((rows, slots), jnp.int32),
            *(shaped(page),) * 2).compile()
        text = compiled.as_text()
        _apart_in_place(text, 1, Hkv, bt, hd)
        capacity = rows * slots // kvcache.TILE_BLOCKS
        assert not re.findall(rf"f32\[{capacity},[\d,]*\]", text)
        assert re.search(rf"f32\[{rows},{Hkv},{H // Hkv},{hd}\]", text)
        assert compiled.memory_analysis().temp_size_in_bytes < 3e6


@pytest.mark.parametrize("Hkv,hd,bt,dtype,kernel", [
    (32, 128, 16, jnp.bfloat16, True), (8, 128, 32, jnp.bfloat16, True),
    (32, 128, 16, jnp.float32, False), (10, 128, 64, jnp.bfloat16, False)],
    ids=["32-heads-of-128", "blocks-of-32", "32-heads-in-float32",
         "blocks-of-64"])
def test_a_tile_past_the_kernels_buffers_keeps_the_loop(one_chip, Hkv, hd,
                                                        bt, dtype, kernel):
    """``block_tokens`` is the engine's argument and ``llama`` takes any
    checkpoint's heads, so a tile of keys (16 blocks, all heads) has no
    bound of its own; the kernel holds four. Where they fit
    ``kvcache.KERNEL_BYTES`` (8 MiB: 32 heads of 128 in bfloat16 at 16
    positions a block are exactly that, the largest tile the rule admits)
    the program compiled for the described chip holds the kernel, so what
    the rule admits does fit the fast memory a kernel is given; past it
    (the same heads in float32: 16 MiB; Phi-4-mini-flash's pairs at 64
    positions a block: 10 MiB) ``Tiles.in_place`` is false and the program
    is the loop, as the parent's was, and compiles too."""
    rows, g, slots = 8, 2, 256
    tile = kvcache.TILE_BLOCKS * Hkv * bt * hd * jnp.dtype(dtype).itemsize
    assert (4 * tile <= kvcache.KERNEL_BYTES) == kernel

    def shaped(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def layer(q, k, v, lengths, table, pk, pv):
        cache = kvcache.Paged(pk, pv, table)
        past = cache.past(0, cache.filled(lengths))
        assert past.in_place == kernel
        return attend(q, k, v, lengths[:, None], past=past)

    text = jax.jit(layer).lower(
        shaped((rows, 1, g * Hkv, hd)), *(shaped((rows, 1, Hkv, hd)),) * 2,
        shaped((rows,), jnp.int32), shaped((rows, slots), jnp.int32),
        *(shaped((1, 4096, Hkv, bt, hd)),) * 2).compile().as_text()
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "paged_filled_tiles" in line]
    loops = [line for line in text.splitlines()
             if " while(" in line and "attn.tiles" in line]
    assert (len(calls), len(loops)) == ((1, 0) if kernel else (0, 1))


def _gathered(text: str) -> list[int]:
    """The bytes of every array a compiled program gathers from the other
    chips (a result may be a tuple of them)."""
    sizes = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    return [int(np.prod([int(d) for d in dims.split(",")])) * sizes[kind]
            for line in text.splitlines()
            for op in (re.search(r"= (.*?) all-gather(-start)?\(", line),)
            if op
            for kind, dims in re.findall(r"(\w+)\[([\d,]+)\]", op.group(1))]


def test_a_pool_across_four_chips_keeps_the_loop(chips):
    """``yi-1.5-34b-l40-tp4``'s wide decode step (two of its layers, 8 rows
    at 256 table slots) compiled for the four described chips as the engine
    builds it under ``tp`` = 4, the pool split over its 8 KV heads
    (``KVBlockPool.meshed``): the program is the parent's, a loop over the
    filled tiles a layer which the compiler partitions by head (a chip
    gathers a chunk of ITS two heads, ``bf16[2048,2,16,128]``), no Pallas
    call, and nothing gathered from the other chips but a step's ids: no
    all-gather of a pool, which a custom call the compiler has no rule for
    would need. Without what the pool hands its programs the step does not
    lower for the four chips at all: JAX refuses a Pallas call in a
    program of several chips outside a ``shard_map``."""
    import dataclasses

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from demodel_tpu.models import llama

    mesh = Mesh(np.array(chips).reshape(1, 4), ("dp", "tp"))
    doc = json.loads((Path(__file__).parent.parent / "benchmark" / "configs"
                      / "yi-1.5-34b-l40-tp4.json").read_text())
    engine = doc.pop("benchmark")["engine"]
    cfg = dataclasses.replace(llama.LlamaConfig.from_hf(doc),
                              num_hidden_layers=2)
    rows, bt, slots = engine["max_batch"], engine["block_tokens"], 256
    made = []

    def make():
        made.append(kvcache.KVBlockPool(
            llama.cache_spec(cfg), block_tokens=bt,
            budget_mb=engine["kv_mb"], dtype=cfg.dtype, mesh=mesh))
        return made[0].arrays

    arrays = jax.eval_shape(make)
    pool, = made
    assert pool.meshed is True and pool.platform == "tpu"
    assert pool.sharding.spec == P(None, None, "tp", None, None)
    assert pool.positions_in_place(slots, 4096) == 0
    rep = NamedSharding(mesh, P())

    def lowered(meshed):
        def decode(params, table, lengths, tokens, k, v):
            cache = kvcache.Paged(k, v, table, meshed=meshed)
            logits, new = llama.step_decode(params, tokens, cfg, cache,
                                            lengths, mesh=mesh)
            pages, _ = kvcache.parts(new)
            return (jnp.argmax(logits, axis=-1), *kvcache.put_positions(
                k, v, pages, table[:, 0], lengths % bt))

        params = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            jax.eval_shape(lambda: llama.init_params(jax.random.key(1),
                                                     cfg)),
            llama.param_shardings(cfg, mesh))
        vector = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=rep)
        return jax.jit(decode, donate_argnums=(4, 5),
                       out_shardings=(rep, *pool.shardings)).lower(
            params, jax.ShapeDtypeStruct((rows, slots), jnp.int32,
                                         sharding=rep), vector, vector,
            *(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=pool.sharding)
              for a in arrays))

    text = lowered(pool.meshed).compile().as_text()
    assert "tpu_custom_call" not in text
    assert len([line for line in text.splitlines()
                if " while(" in line and "attn.tiles" in line]) == 2
    heads = cfg.num_key_value_heads // 4
    chunk = kvcache.TILE_CHUNK * kvcache.TILE_BLOCKS
    assert re.findall(rf"bf16\[{chunk},{heads},{bt},{cfg.head_dim}\]", text)
    gathered = _gathered(text)
    assert gathered and max(gathered) < 1 << 10
    with pytest.raises(NotImplementedError, match="shard_map"):
        lowered(None)


def test_a_latent_page_on_four_chips_keeps_the_loop(chips):
    """A latent attention over the filled tiles of a page of one array (16
    heads over one cached vector of 640 columns, a copy of the pool on each
    of four chips, as under ``ep``) lowered for the four described chips:
    with what the pool hands its programs it is the loop; without, as
    before PR 50, it does not lower (``ops/latent_tiles.py``'s kernel in a
    program of several chips)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    rep = NamedSharding(Mesh(np.array(chips), ("ep",)), P())

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    def lowered(meshed):
        def layer(q, k, lengths, table, pk):
            cache = kvcache.Paged(pk, None, table, meshed=meshed)
            return attend(q, k, k[..., :512], lengths[:, None], scale=0.1,
                          past=cache.past(0, cache.filled(lengths)))

        return jax.jit(layer).lower(
            shaped((8, 1, 16, 640)), shaped((8, 1, 1, 640)),
            shaped((8,), jnp.int32), shaped((8, 256), jnp.int32),
            shaped((2, 1024, 1, 16, 640)))

    text = lowered(True).as_text()
    assert "tpu_custom_call" not in text and "stablehlo.while" in text
    with pytest.raises(NotImplementedError, match="shard_map"):
        lowered(None)


def test_the_double_layers_step_reads_its_page_and_weights_as_held(one_chip):
    """``longcat-flash-omni-560b-l4-ep32``'s decode step as
    ``longcat-reason`` runs it (64 rows at 256 table slots each), compiled
    for the described chip: the pool of 8 paging layers of 640 columns (two
    sublayers a layer, 1 920 MiB) lies as it is read; every one of the 8
    attentions over the filled tiles is the kernel that reads them where
    they lie; and the weights are read in
    the layouts they are held in (``q_b`` ``[out, in]``, ``w_uk`` / ``w_uv``
    ``[H, 512, 128]``, the experts stacked ``[E, D, 2F]`` / ``[E, F, D]``):
    the program copies nothing of 30 MB into another order (an expert stack
    is 0.8 GB and 0.4 GB, a dense block's matrix 151 MB, ``o_proj`` 101 MB),
    and its temporaries are 0.03 GB (0.11 with the loop's chunks, before
    PR 45) beside 12.36 GB of arguments."""
    doc = json.loads((Path(__file__).parent.parent / "benchmark" / "configs"
                      / "longcat-flash-omni-560b-l4-ep32.json").read_text())
    engine = doc.pop("benchmark")["engine"]
    cfg = longcat_flash.LongcatFlashConfig.from_hf(doc)
    spec = longcat_flash.cache_spec(cfg)
    assert (spec.layers, spec.head_dim, spec.values) == (8, 640, 512)
    bt = engine["block_tokens"]
    blocks = (engine["kv_mb"] << 20) // (spec.layers * bt * spec.head_dim
                                         * 2) + 1
    assert blocks == 12288 + 1
    page = (spec.layers, blocks, spec.kv_heads, bt, spec.head_dim)
    assert _born(page, one_chip)[:2] == [4, 3]
    rows, slots = engine["max_batch"], 256

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(params, table, lengths, tokens, k):
        cache = kvcache.Paged(k, None, table)
        logits, new, *stats = longcat_flash.step_decode(
            params, tokens, cfg, cache, lengths)
        latents, _fresh = kvcache.parts(new)
        return logits, stats, *kvcache.put_positions(
            k, None, latents, table[:, 0], lengths % bt)

    params = jax.tree.map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: longcat_flash.init_params(jax.random.key(1),
                                                         cfg)))
    compiled = jax.jit(decode, donate_argnums=(4,)).lower(
        params, shaped((rows, slots), jnp.int32),
        *(shaped((rows,), jnp.int32),) * 2,
        shaped(page, jnp.bfloat16)).compile()
    text = compiled.as_text()
    capacity = rows * slots // kvcache.TILE_BLOCKS
    assert not re.findall(rf"f32\[{capacity},[\d,]*51[24]\]", text)
    _in_place(text, spec.layers, rows, cfg.num_attention_heads, spec.values)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.05e9
    assert 12.3e9 < memory.argument_size_in_bytes < 12.4e9


@pytest.mark.parametrize("family,config,name,tokens,sparse", [
    (axk1, axk1.AxK1Config, "ax-k1-519b-l7-ep16", 1024, 6),
    (qwen3_next, qwen3_next.Qwen3NextConfig, "qwen3-next-80b-l12-ep4", 1024,
     12),
], ids=["ax-k1", "qwen3-next"])
def test_a_prompts_grouped_products_are_the_kernel(one_chip, family, config,
                                                   name, tokens, sparse):
    """A 1 024-token prefill at the published widths, compiled for the
    described chip: its 8 192 (10 240) assignments a layer go through the
    landed-slabs loop, whose body holds the kernel twice; no ``ragged-dot``
    is left in the program."""
    doc = json.loads((Path(__file__).parent.parent / "benchmark" / "configs"
                      / f"{name}.json").read_text())
    doc.pop("benchmark")
    cfg = config.from_hf(doc)
    assert tokens * cfg.num_experts_per_tok > family.experts.SLAB

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: family.init_params(jax.random.key(1), cfg)))
    lowered = jax.jit(
        lambda params, tokens: family.step_prefill(params, tokens, cfg)
    ).lower(params, shaped((1, tokens), jnp.int32))
    text = _grouped(lowered, sparse)
    assert [line for line in text.splitlines()
            if " while(" in line and "moe" in line]


def _operation(line: str) -> tuple[str, str] | None:
    """``(result type, operation)`` of one instruction of a compiled
    program's text; the type of a multi-output fusion, a sort or a kernel
    is a tuple in brackets."""
    head = re.match(r"\s+(ROOT )?%?[\w.\-]+ = (.*)", line)
    if not head:
        return None
    rest = head.group(2)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if not depth:
                break
        kind, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        kind, _, rest = rest.partition(" ")
    op = re.match(r"([\w\-]+)\(", rest)
    return (kind, op.group(1)) if op else None


def _runs(line: str) -> bool:
    """An instruction that runs on the device as an operation of its own,
    as the profiler's trace lists them: no parameter, tuple, constant,
    bitcast or loop (its body's run), and nothing that is a scalar."""
    found = _operation(line)
    return bool(found) and found[1] not in (
        "parameter", "get-tuple-element", "tuple", "constant", "bitcast",
        "while") and not re.match(r"(s32|u32|pred)\[1?\]", found[0])


def _computations(text: str) -> dict[str, list[str]]:
    """A compiled program's computations by name (the entry's ``ENTRY``),
    each the lines of its instructions."""
    blocks, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            blocks[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            blocks[name].append(line)
    return blocks


def _operations(text: str) -> tuple[int, int]:
    """``(a trip of the program's one loop, the rest)``: the device
    operations (:func:`_runs`) of a compiled program."""
    blocks = _computations(text)
    (body,) = re.findall(r"while\(.*?body=%?([\w.\-]+)", text)
    return sum(map(_runs, blocks[body])), sum(map(_runs, blocks["ENTRY"]))


def _step_operations(text: str, loops: dict, scans: dict) -> int:
    """The device operations (:func:`_runs`) of one run of a compiled
    program whose loops nest: the entry's, and each loop's body's times
    its trips. The compiled text names no trip count, so the caller does:
    ``loops`` by a scope in the loop's own name (the loop over the chunks
    of filled tiles, a gather the compiler made a loop over its rows),
    ``scans`` for a loop under no scope (a ``lax.scan`` of layers) by a
    scope found in its body."""
    blocks = _computations(text)

    def run(name):
        total = sum(map(_runs, blocks[name]))
        for line in blocks[name]:
            if (_operation(line) or ("", ""))[1] != "while":
                continue
            body = re.search(r"body=%?([\w.\-]+)", line).group(1)
            scope = re.search(r'op_name="([^"]*)"', line).group(1)
            if scope.count("/") == 1:       # jit(decode)/while: a scan
                (trips,) = {n for key, n in scans.items()
                            if any(key in inner for inner in blocks[body])}
            else:
                (trips,) = {n for key, n in loops.items() if key in scope}
            total += trips * run(body)
        return total

    return run("ENTRY")


def _wide_step(one_chip, family, config, name: str):
    """``(compiled text, cache spec, block_tokens)`` of a configuration's
    decode step at its cell's rows and 256 table slots a row, compiled for
    the described chip as the engine builds it (the pools and the slots
    donated; keys and values apart, slots beside them)."""
    doc = json.loads((Path(__file__).parent.parent / "benchmark" / "configs"
                      / f"{name}.json").read_text())
    engine = doc.pop("benchmark")["engine"]
    cfg = config.from_hf(doc)
    spec = family.cache_spec(cfg)
    rows, bt, slots = engine["max_batch"], engine["block_tokens"], 256
    pool = jax.eval_shape(lambda: kvcache.KVBlockPool(
        spec, slots=rows, block_tokens=bt, budget_mb=engine["kv_mb"],
        dtype=cfg.dtype).arrays)
    names = [n for n, *_ in spec.state]

    def shaped(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def decode(params, table, lengths, tokens, slot, k, v, *state):
        cache = kvcache.Paged(k, v, table, dict(zip(names, state)), slot)
        logits, new, *stats = family.step_decode(params, tokens, cfg, cache,
                                                 lengths)
        pages, fresh = kvcache.parts(new)
        return (jnp.argmax(logits, axis=-1), stats,
                *kvcache.put_positions(k, v, pages, table[:, 0],
                                       lengths % bt),
                *kvcache.put_slots(state, names, fresh, slot))

    params = jax.tree.map(shaped, jax.eval_shape(
        lambda: family.init_params(jax.random.key(1), cfg)))
    vector = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        decode, donate_argnums=tuple(range(5, 5 + len(pool)))).lower(
        params, jax.ShapeDtypeStruct((rows, slots), jnp.int32,
                                     sharding=one_chip),
        vector, vector, vector, *map(shaped, pool)).compile()
    return compiled.as_text(), spec, bt


@pytest.mark.parametrize("family,config,name,calls,rows,parents", [
    (phi4flash, phi4flash.Phi4FlashConfig, "phi-4-mini-flash", 2, 32, 3983),
    (qwen3_next, qwen3_next.Qwen3NextConfig, "qwen3-next-80b-l12-ep4", 3, 16,
     3249),
], ids=["phi-4-mini-flash", "qwen3-next"])
def test_a_wide_step_reads_keys_and_values_where_they_lie(
        one_chip, family, config, name, calls, rows, parents):
    """``phi-4-mini-flash``'s and ``qwen3-next-80b-l12-ep4``'s decode steps
    as ``phi4flash-reason`` and ``qwen3next-doc`` run them (32 and 16 rows
    at 256 table slots each), compiled for the described chip: every
    attention over the filled tiles is the kernel of ``ops/paged_tiles.py``
    under ``attn.tiles`` (Phi-4-mini-flash's eight readers are two call
    sites, ``attn.full`` and the body of the scan that is the seven
    ``attn.cross`` layers; Qwen3-Next's three paging layers three), there
    is no loop under ``attn.tiles`` and nothing of a gathered chunk (42 and
    34 MB, for the keys and again for the values, a trip). **The device
    operations a step are fewer than the parent's**: 3 444 where 3 983 and
    3 104 where 3 249, both counted by :func:`_step_operations` with the
    parent's loops over the tiles at the two trips the cells' fills take
    (242 and ~160 filled tiles in chunks of 128), a gather the compiler
    made a loop over its rows at the rows, Phi-4-mini-flash's two scans at
    their 8 and 7 layers (PERF.md section 6, PR 50; a profile of the
    parent's step counted 4 230)."""
    text, spec, bt = _wide_step(one_chip, family, config, name)
    _apart_in_place(text, calls, spec.kv_heads, bt, spec.head_dim)
    count = _step_operations(text, {"attn.tiles": 2, "_take": rows},
                             {"attn.cross": 7, "attn.window": 8})
    assert count < parents - 100, count


def test_the_scanned_step_reads_its_page_tails_and_experts_as_held(one_chip):
    """``zaya1-8b-l16``'s decode step as ``zaya1-reason`` runs it (64 rows at
    256 table slots each), compiled for the described chip: the pool of 16
    layers of 512 columns (``[v | k^]``, 4 620 MiB with the slots) lies as
    it is read; the layers are one loop whose body holds two kernels, each
    once: the step's mixing (``ops/cca_mix.py``) and the filled tiles under
    8 zero-padded query rows (``ops/latent_tiles.py``); a step's 64 rows go
    through every expert of the layer in two plain products that read the
    layer's 16 experts out of the stack of 256 where they lie: the program
    copies nothing of 30 MB (a layer's experts are 0.27 and 0.13 GB of the
    stacks' 4.3 and 2.1), its temporaries are 0.01 GB beside 12.56 GB of
    arguments. **The device operations a step are held**: 56 a trip of the
    loop and 170 outside it (64 of them the rows' slice updates of the
    pages), 1 066 a step, where the first form of this step (the mixing in
    the compiler's hands, a step's rows routed, the tails written a row a
    slice update) held 121 and 228, 2 164 a step, of which a traced 48 s
    window kept the first 28.9 s (PERF.md section 6, PR 49)."""
    doc = json.loads((Path(__file__).parent.parent / "benchmark" / "configs"
                      / "zaya1-8b-l16.json").read_text())
    engine = doc.pop("benchmark")["engine"]
    cfg = zaya.ZayaConfig.from_hf(doc)
    spec = zaya.cache_spec(cfg)
    assert (spec.layers, spec.kv_heads, spec.head_dim, spec.values) \
        == (16, 1, 512, 256)
    assert spec.head_dim % latent.LANES == 0
    bt, rows, slots = engine["block_tokens"], engine["max_batch"], 256
    # the budget pays for the slots first and the blocks with the rest
    blocks = ((engine["kv_mb"] << 20) - rows * spec.layers * cfg.tail_dim
              * 2) // (spec.layers * bt * spec.head_dim * 2) + 1
    assert blocks == 18459 + 1
    page = (spec.layers, blocks, 1, bt, spec.head_dim)
    assert _born(page, one_chip)[:2] == [4, 3]

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(params, table, lengths, tokens, slot, k, tails):
        cache = kvcache.Paged(k, None, table, {"tail": tails}, slot)
        logits, new, *stats = zaya.step_decode(params, tokens, cfg, cache,
                                               lengths)
        pages, fresh = kvcache.parts(new)
        return (jnp.argmax(logits, axis=-1), stats,
                *kvcache.put_positions(k, None, pages, table[:, 0],
                                       lengths % bt),
                *kvcache.put_slots((tails,), ("tail",), fresh, slot))

    params = jax.tree.map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: zaya.init_params(jax.random.key(1), cfg)))
    lowered = jax.jit(decode, donate_argnums=(5, 6)).lower(
        params, shaped((rows, slots), jnp.int32),
        *(shaped((rows,), jnp.int32),) * 3, shaped(page, jnp.bfloat16),
        shaped((spec.layers, rows + 1, cfg.tail_dim), jnp.bfloat16))
    text = lowered.as_text()
    assert "ragged_dot" not in text and "grouped_dot" not in text
    assert text.count("call @over_filled_tiles") == 1
    assert text.count("call @step_rows") == 1
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if " custom-call(" in line
             and 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(name for c in calls
                  for name in ("latent_filled_tiles", "cca_mix_step")
                  if name in c) == ["cca_mix_step", "latent_filled_tiles"]
    assert all("attn.tiles" in c or "attn.cca.mix" in c for c in calls)
    # nothing of an expert's size is copied out of the stacks
    assert not _copied(text)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.05e9
    assert 12.5e9 < memory.argument_size_in_bytes < 12.6e9
    trip, rest = _operations(text)
    assert trip <= 60 and rest <= 180, (trip, rest)
    assert spec.layers * trip + rest <= 1150


def test_a_prompt_routes_its_rows_through_the_one_stack(one_chip):
    """A 1 024-token prefill of ``zaya1-8b-l16``, compiled for the described
    chip: its rows are routed (``experts.routed`` at ``first = 0``, ``K =
    1``, 1 024 assignments a layer: one pass, no slab loop), and the two
    grouped products of the loop's body are the kernel of ``ops/grouped.py``
    fed the stack of all 256 experts, from which nothing is copied."""
    doc = json.loads((Path(__file__).parent.parent / "benchmark" / "configs"
                      / "zaya1-8b-l16.json").read_text())
    doc.pop("benchmark")
    cfg = zaya.ZayaConfig.from_hf(doc)
    assert 1024 * cfg.num_experts > zaya.DENSE

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: zaya.init_params(jax.random.key(1), cfg)))
    lowered = jax.jit(
        lambda params, tokens: zaya.step_prefill(params, tokens, cfg)
    ).lower(params, shaped((1, 1024), jnp.int32))
    compiled = _grouped(lowered, 1)
    assert "cca_mix_step" not in compiled
    assert not _copied(compiled)
