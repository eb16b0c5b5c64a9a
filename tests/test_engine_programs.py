"""The engine's two programs of the three families that came before
Phi-4-flash, at their tiny configurations, lower to the text they lowered to
at the parent of PR 35 (locations stripped), in float32 and in bfloat16:
what a model module adds to ``serve/kvcache.py`` (a part of a slot written
for all layers at once, the rings), to ``models/common.attend`` (a scale of
its own) and to the engine's closures changes no other family's program.

The digests were taken from the parent's checkout by this file's
:func:`programs` (``PYTHONPATH=<checkout>``, the same eight virtual CPU
devices) and are the same here. A PR that means to change a family's
program pins its digests again, and says so. PR 41 did for the six
``decode`` ones: up to two tiles a row a table is two tiles wide, so rows of
9, 5 and 3 positions step through 32 slots where they stepped through 4.
``decode-past-16-blocks`` is from PR 41's parent, where only a longest row
past 16 blocks had a table of 32 slots: the program every shorter row now
runs is that one, text for text. ``decode-past-two-tiles`` is from PR 42's
parent: what that PR added to ``common._over_tiles`` (values narrower than
the keys, tiles with no values of their own) left the loop over the filled
tiles of every family that pages K and V as it was, down to the order of
its operations (a gather moved ahead of a reshape had made it another
program, which the machine's compile cache would not have known). PR 43
gave that loop a second carry (one running softmax a row) for the steps
whose partials outweigh their share of a tile's bytes, and moved none of
these digests: which step takes it is a rule on shapes
(``kvcache.Tiles.by_row``), held below at the benchmark's head counts and
widths.

PR 44 lifted A.X-K1's latent attention into ``models/latent.py`` for a
second family to call: A.X-K1's eight digests are from that PR's parent
and are the same with the attention where it now is. LongCat-Flash's are
its own first ones (a narrow step, a wide one over the filled tiles of its
two sublayers a layer, a prefill).

PR 45 put a Pallas kernel in the place of the row-carry loop where the
program is lowered for a TPU and the page is one array: the CPU's text of
every pinned program is the parent's (the tiny latent configurations carry
a tile), and which platform gets the kernel is held below.

PR 46 put a second kernel behind the same seam: the held experts' grouped
products (``models/experts._grouped``) are ``ops/grouped.py``'s kernel in a
program lowered for a TPU and ``lax.ragged_dot`` everywhere else. The four
expert families' digests are pinned again for it (the CPU's text now holds
the platform's choice, a ``case`` of one branch, around each product: all 30
of ``exaone_moe``, ``qwen3_next``, ``axk1`` and ``longcat_flash``);
``llama``'s eight are the parent's, as is every program of a family with no
expert layer.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demodel_tpu.models import (axk1, exaone_moe, llama, longcat_flash,
                                phi4flash, qwen3_next)
from demodel_tpu.serve import GenEngine, kvcache
from demodel_tpu.serve.scheduler import _Seq

FAMILIES = {"llama": (llama, llama.LlamaConfig),
            "exaone_moe": (exaone_moe, exaone_moe.ExaoneMoeConfig),
            "qwen3_next": (qwen3_next, qwen3_next.Qwen3NextConfig),
            "axk1": (axk1, axk1.AxK1Config),
            "longcat_flash": (longcat_flash,
                              longcat_flash.LongcatFlashConfig)}

PINNED = {
    ("llama", "float32", "decode"): "ff3c2a1e16526eb9",
    ("llama", "float32", "decode-past-16-blocks"): "ff3c2a1e16526eb9",
    ("llama", "float32", "decode-past-two-tiles"): "de10f278fba446b1",
    ("llama", "float32", "prefill"): "2384c9807e24cde3",
    ("llama", "bfloat16", "decode"): "fecca8e7814f2856",
    ("llama", "bfloat16", "decode-past-16-blocks"): "fecca8e7814f2856",
    ("llama", "bfloat16", "decode-past-two-tiles"): "185d9f2da608cc06",
    ("llama", "bfloat16", "prefill"): "b9db3f7fcec8e591",
    ("exaone_moe", "float32", "decode"): "69d4b9d9de4085a3",
    ("exaone_moe", "float32", "decode-past-16-blocks"): "69d4b9d9de4085a3",
    ("exaone_moe", "float32", "decode-past-two-tiles"): "73d57ce2e23b8fe9",
    ("exaone_moe", "float32", "prefill"): "7dfe2b7bf8e5c253",
    ("exaone_moe", "bfloat16", "decode"): "4bcc7f25d5f5de65",
    ("exaone_moe", "bfloat16", "decode-past-16-blocks"): "4bcc7f25d5f5de65",
    ("exaone_moe", "bfloat16", "decode-past-two-tiles"): "b7e005b08919f48a",
    ("exaone_moe", "bfloat16", "prefill"): "40d60075749c8570",
    ("qwen3_next", "float32", "decode"): "533379045d4ba4d7",
    ("qwen3_next", "float32", "decode-past-16-blocks"): "533379045d4ba4d7",
    ("qwen3_next", "float32", "decode-past-two-tiles"): "a97559f8dfb1d82d",
    ("qwen3_next", "float32", "prefill"): "5f15f0fdab1e84c0",
    ("qwen3_next", "bfloat16", "decode"): "edc6a71c70beef8f",
    ("qwen3_next", "bfloat16", "decode-past-16-blocks"): "edc6a71c70beef8f",
    ("qwen3_next", "bfloat16", "decode-past-two-tiles"): "ce7268ea6b01e42c",
    ("qwen3_next", "bfloat16", "prefill"): "429bff75fe08bade",
    ("axk1", "float32", "decode"): "fdae1f714571c2b7",
    ("axk1", "float32", "decode-past-16-blocks"): "fdae1f714571c2b7",
    ("axk1", "float32", "decode-past-two-tiles"): "6422879d22f324fd",
    ("axk1", "float32", "prefill"): "816cbba5e0584bdf",
    ("axk1", "bfloat16", "decode"): "d4742f382b311ef3",
    ("axk1", "bfloat16", "decode-past-16-blocks"): "d4742f382b311ef3",
    ("axk1", "bfloat16", "decode-past-two-tiles"): "94d098f3a7cc027d",
    ("axk1", "bfloat16", "prefill"): "99e8d79732ae7e2b",
    ("longcat_flash", "float32", "decode"): "ee441f5cd5a73a62",
    ("longcat_flash", "float32", "decode-past-two-tiles"):
        "b2c433294a47d517",
    ("longcat_flash", "float32", "prefill"): "144cbeab07fcc3c3",
    ("longcat_flash", "bfloat16", "decode"): "091f6ff8756c3bb3",
    ("longcat_flash", "bfloat16", "decode-past-two-tiles"):
        "cfd8e3f084509d10",
    ("longcat_flash", "bfloat16", "prefill"): "06bae7ddafc6572a",
}


def programs(module, cfg, platform: str | None = None) -> dict[str, str]:
    """The lowered text of the engine's decode step (three rows of 9, 5 and
    3 cached positions in a bucket of four; ``decode-past-16-blocks``: of
    70, 5 and 3, so that the longest row holds 18 blocks of 4;
    ``decode-past-two-tiles``: of 140, 5 and 3, 35 blocks, so that the table
    is 256 slots wide and the attention runs over the filled tiles) and of
    a 20-token prefill; lowered for the devices at hand (the CPU), or for
    ``platform`` with none attached."""
    params = module.init_params(jax.random.key(1), cfg)
    engine = GenEngine(params, cfg, max_batch=4, queue_limit=8,
                       max_new_tokens=8, kv_mb=1, block_tokens=4)
    pool = engine.pool
    lease = pool.alloc(40)

    def lower(program, *args):
        if platform is None:
            return program.lower(*args).as_text()
        return program.trace(*args).lower(
            lowering_platforms=(platform,)).as_text()

    def decode(*lengths):
        _w, rows = engine._decode_inputs(
            [_Seq(None, lease, n, 1) for n in lengths])
        return lower(engine._jdecode, engine.params, rows, engine._prev_ids,
                     *pool.arrays)

    try:
        blocks = np.asarray(
            lease.blocks[:5] + [lease.slot] * engine._slotted, np.int32)
        return {
            "decode": decode(9, 5, 3),
            "decode-past-16-blocks": decode(70, 5, 3),
            "decode-past-two-tiles": decode(140, 5, 3),
            "prefill": lower(
                engine._jprefill, engine.params, np.zeros((1, 20), np.int32),
                blocks, *pool.arrays)}
    finally:
        lease.free()
        engine.stop()


def digest(text: str) -> str:
    text = re.sub(r"loc\([^)]*\)", "", text)
    text = re.sub(r"#loc\d* = .*\n", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.fixture(scope="module")
def lowered():
    cache: dict = {}

    def of(family: str, dtype: str) -> dict[str, str]:
        if (family, dtype) not in cache:
            module, config = FAMILIES[family]
            cache[family, dtype] = programs(
                module, dataclasses.replace(config.tiny(), dtype=dtype))
        return cache[family, dtype]

    return of


@pytest.mark.parametrize("family,dtype,stage", sorted(PINNED))
def test_program_is_the_parents(lowered, family, dtype, stage):
    assert digest(lowered(family, dtype)[stage]) \
        == PINNED[family, dtype, stage]


@pytest.mark.parametrize("family,heads,in_place", [
    ("axk1", 32, True), ("axk1", 4, False), ("llama", 8, False)],
    ids=["a-latent-page-carried-a-row", "a-latent-page-carried-a-tile",
         "keys-and-values-apart"])
def test_the_platform_chooses_the_kernel_at_lowering(family, heads,
                                                     in_place):
    """The same trace lowered for the CPU and, with no chip attached, for a
    TPU: where the wide step's past is a page of one array carried a row
    (A.X-K1's absorbed step at 32 heads) the TPU's text holds the Pallas
    kernel's custom call, called by every latent attention, and no loop,
    the CPU's the loop and no custom call: ``lax.platform_dependent`` in
    ``common._over_tiles``, resolved when the program is lowered. No
    environment variable, configuration key or model name is consulted,
    and none is set here. A page carried a tile (4 heads) and a page of
    keys and values apart keep the loop on both platforms, and the narrow
    step and the prefill hold neither."""
    module, config = FAMILIES[family]
    tiny = config.tiny(num_attention_heads=heads) if family == "axk1" \
        else config.tiny()
    assert tiny.num_attention_heads == heads
    cfg = dataclasses.replace(tiny, dtype="bfloat16")
    layers = module.cache_spec(cfg).readers
    cpu, tpu = programs(module, cfg), programs(module, cfg, platform="tpu")
    wide = "decode-past-two-tiles"
    for stage in cpu:
        assert "tpu_custom_call" not in cpu[stage]
        assert cpu[stage].count("stablehlo.while") \
            == (layers if stage == wide else 0)
        # one function a program holds the kernel (traced and lowered
        # once, whatever the layers), and every latent attention calls it
        kernels = layers if in_place and stage == wide else 0
        assert tpu[stage].count('kernel_name = "latent_filled_tiles"') \
            == (kernels > 0)
        assert tpu[stage].count("call @over_filled_tiles") == kernels
        assert tpu[stage].count("stablehlo.while") \
            == cpu[stage].count("stablehlo.while") - kernels


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_platform_chooses_the_grouped_product_at_lowering(family):
    """The same trace lowered for the CPU and, with no chip attached, for a
    TPU: every grouped product of ``experts._slab`` (two a sparse layer, in
    the step and in the prefill) is on a TPU a call of the one function
    that holds ``ops/grouped.py``'s kernel, which the program traced and
    lowered once a shape (gate beside up, down), and no ``ragged_dot`` is
    left there; the CPU's text holds no custom call. A family with no
    expert layer holds neither on either platform."""
    module, config = FAMILIES[family]
    cfg = dataclasses.replace(config.tiny(), dtype="bfloat16")
    counted = {"exaone_moe": "sparse_layers", "axk1": "sparse_layers",
               "qwen3_next": "num_hidden_layers",
               "longcat_flash": "num_layers"}.get(family)
    sparse = getattr(cfg, counted) if counted else 0
    cpu, tpu = programs(module, cfg), programs(module, cfg, platform="tpu")
    for stage in cpu:
        assert "tpu_custom_call" not in cpu[stage]
        assert "ragged" not in tpu[stage]
        assert tpu[stage].count('kernel_name = "moe_grouped"') \
            == 2 * (sparse > 0)
        assert tpu[stage].count("call @grouped_dot") == 2 * sparse
        assert tpu[stage].count("stablehlo.while") \
            <= cpu[stage].count("stablehlo.while")


#: the benchmark's configurations: module, configuration class, the rows of
#: its cell's batch bucket, and whether its wide step carries a row
CONFIGS = {
    "yi-1.5-6b": (llama, llama.LlamaConfig, 8, False),
    "k-exaone-236b-l8-ep8": (exaone_moe, exaone_moe.ExaoneMoeConfig, 32,
                             False),
    "qwen3-next-80b-l12-ep4": (qwen3_next, qwen3_next.Qwen3NextConfig, 16,
                               False),
    "phi-4-mini-flash": (phi4flash, phi4flash.Phi4FlashConfig, 32, False),
    "ax-k1-519b-l7-ep16": (axk1, axk1.AxK1Config, 64, True),
    "longcat-flash-omni-560b-l4-ep32": (
        longcat_flash, longcat_flash.LongcatFlashConfig, 64, True),
}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_carry_follows_from_shapes_alone(name):
    """What the loop over the filled tiles carries, at each configuration's
    published head counts and widths in bfloat16 blocks of 16 positions, at
    both wide widths: a tile's float32 partials (query heads x (values + 2)
    x 4 B) against the bytes of the tile they were taken from. The two
    absorbed steps (A.X-K1's and LongCat-Flash's: 64 heads over one cached
    vector of 640, values of 512) read 0.40 and carry a row (LongCat-Flash
    in each of its 8 sublayers); every family that pages K and V reads
    0.016-0.032 and keeps a tile. The index inside the program
    (``Tiles.by_row``) and the host's count of the bytes
    (``KVBlockPool.partial_bytes``, the step span's ``attn_partial_bytes``)
    say the same, from the module's own statement of its cache."""
    module, config, rows, by_row = CONFIGS[name]
    doc = json.loads((Path(__file__).parent.parent / "benchmark" / "configs"
                      / f"{name}.json").read_text())
    cfg = config.from_hf({k: v for k, v in doc.items() if k != "benchmark"})
    spec = module.cache_spec(cfg)
    assert spec.readers and spec.query_heads == cfg.num_attention_heads
    # the pages alone, a few blocks of them: the rule reads shapes
    pool = kvcache.KVBlockPool(spec._replace(state=()), block_tokens=16,
                               budget_mb=1, dtype="bfloat16")
    vd = spec.values or spec.head_dim
    partial = spec.query_heads * (vd + 2) * 4
    tile = 256 * spec.kv_heads * spec.head_dim * 2 * pool.pages
    assert (partial / tile > 0.125) == by_row
    assert (0.39 < partial / tile < 0.41) if by_row \
        else (0.015 < partial / tile < 0.033)
    for slots in (256, 2048):
        cache = kvcache.Paged(pool.k, pool.v,
                              jnp.zeros((rows, slots), jnp.int32))
        tiles = cache.past(0, cache.filled(jnp.full((rows,), 700)))
        assert tiles.by_row(partial) == by_row
        places = rows if by_row else rows * slots // kvcache.TILE_BLOCKS
        assert pool.partial_bytes(rows, slots) \
            == spec.readers * places * partial
    assert pool.partial_bytes(rows, 2 * kvcache.TILE_BLOCKS) == 0
