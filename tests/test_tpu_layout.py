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
at their cells' rows (~2 s each): the portable loop, and its temporaries.

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
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # what is compiled for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


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
def test_the_portable_loop_carries_a_row_over_keys_and_values(
        one_chip, family, config, name):
    """One reading layer's attention over the filled tiles of pages of keys
    and values, at the published head counts and the cell's rows, compiled
    for the described chip at both wide widths (256 and 2 048 table slots a
    row): the loop of ``common._over_tiles``, which carries a row. The
    program holds no float32 array of the table's capacity, and its
    temporaries are 1.61 and 1.73 MB for ``phi-4-mini-flash`` (32 rows, 40
    query heads over 10 pairs of 128) and 1.48 and 2.20 MB for
    ``qwen3-next`` (16 rows, 16 heads over 2 of 256). The loop that left
    its partials a tile of the capacity, which these two ran up to PR 46,
    kept ``f32[512,10,4,1,130]`` and 26.96 MB (420.1 MB at the next width)
    and ``f32[256,2,8,1,258]`` and 1.10 MB (17.95 MB)."""
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
        assert [line for line in text.splitlines()
                if " while(" in line and "attn.tiles" in line]
        assert "tpu_custom_call" not in text    # no kernel: pages apart
        capacity = rows * slots // kvcache.TILE_BLOCKS
        assert not re.findall(rf"f32\[{capacity},[\d,]*\]", text)
        assert re.search(rf"f32\[{rows},{Hkv},{H // Hkv},1,{hd}\]", text)
        assert compiled.memory_analysis().temp_size_in_bytes < 3e6


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


def _operations(text: str) -> tuple[int, int]:
    """``(a trip of the program's one loop, the rest)``: the instructions
    of a compiled program that run on the device as operations of their
    own, counted as the profiler's trace lists them: no parameter, tuple,
    constant or bitcast, and nothing that is a scalar."""
    blocks, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            blocks[name] = 0
        elif line.startswith("}"):
            name = None
        elif name:
            found = _operation(line)
            if found and found[1] not in (
                    "parameter", "get-tuple-element", "tuple", "constant",
                    "bitcast", "while") and not re.match(
                    r"(s32|u32|pred)\[1?\]", found[0]):
                blocks[name] += 1
    (body,) = re.findall(r"while\(.*?body=%?([\w.\-]+)", text)
    return blocks[body], blocks["ENTRY"]


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
