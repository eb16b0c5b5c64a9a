"""The engine's programs of seven families, at their tiny configurations,
lower to the text pinned below (locations stripped), in float32 and in
bfloat16: what one family adds to ``serve/kvcache.py``,
``models/common.attend``, ``models/latent.py``, ``models/experts.py`` or the
engine's closures changes no other family's program.

The digests are this file's :func:`programs` and :func:`digest` on the
tests' eight virtual CPU devices (from another checkout:
``PYTHONPATH=<checkout>``). A PR that means to change a program pins its
digests again, and says so. The two ``decode`` stages of a family are one
program: up to two tiles a row a table is two tiles wide (PR 41).
``decode-past-two-tiles`` is the wide step, whose attention runs over the
filled tiles: PR 47 pinned those ten again when the loop that left its
partials a tile of the table's capacity went and the one that carries a
running softmax a row became the only loop (``common._over_tiles``); the
other 28 did not move. The four expert families' programs hold the
platform's choice around each grouped product (``experts._grouped``, PR 46).
**PR 50 pinned again the wide step of every family whose pool holds keys
and values apart** (``llama``, ``exaone_moe``, ``qwen3_next``: six digests):
it now holds the platform's choice around its attention over the filled
tiles, as the latent families' has since PR 45. The rule is the page's kind
and not a family's name (``kvcache._in_place``), so ``llama``'s and
``exaone_moe``'s moved with ``qwen3_next``'s though no cell runs their wide
step; the latent families' and every narrow step and prefill did not move.
``phi4flash`` joined the table with PR 50: its wide step is that PR's, its
other six digests are the parent's too (computed from the parent's
checkout).

Which platform gets which kernel is a matter of lowering and is held below:
the CPU's text is the loop and ``lax.ragged_dot``, a TPU's the Pallas
kernels of ``ops/latent_tiles.py`` (a page of one array),
``ops/paged_tiles.py`` (pages of keys and values apart) and
``ops/grouped.py``.
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
                                phi4flash, qwen3_next, zaya)
from demodel_tpu.models.common import attend
from demodel_tpu.serve import GenEngine, kvcache
from demodel_tpu.serve.scheduler import _Seq
from tests.test_attend import _float32

FAMILIES = {"llama": (llama, llama.LlamaConfig),
            "exaone_moe": (exaone_moe, exaone_moe.ExaoneMoeConfig),
            "qwen3_next": (qwen3_next, qwen3_next.Qwen3NextConfig),
            "axk1": (axk1, axk1.AxK1Config),
            "longcat_flash": (longcat_flash,
                              longcat_flash.LongcatFlashConfig),
            "zaya": (zaya, zaya.ZayaConfig),
            "phi4flash": (phi4flash, phi4flash.Phi4FlashConfig)}

PINNED = {
    ("llama", "float32", "decode"): "ff3c2a1e16526eb9",
    ("llama", "float32", "decode-past-16-blocks"): "ff3c2a1e16526eb9",
    ("llama", "float32", "decode-past-two-tiles"): "0005db6c858a8ad7",
    ("llama", "float32", "prefill"): "2384c9807e24cde3",
    ("llama", "bfloat16", "decode"): "fecca8e7814f2856",
    ("llama", "bfloat16", "decode-past-16-blocks"): "fecca8e7814f2856",
    ("llama", "bfloat16", "decode-past-two-tiles"): "4b42d427ccf0c088",
    ("llama", "bfloat16", "prefill"): "b9db3f7fcec8e591",
    ("exaone_moe", "float32", "decode"): "69d4b9d9de4085a3",
    ("exaone_moe", "float32", "decode-past-16-blocks"): "69d4b9d9de4085a3",
    ("exaone_moe", "float32", "decode-past-two-tiles"): "7b67a6baab5a3b14",
    ("exaone_moe", "float32", "prefill"): "7dfe2b7bf8e5c253",
    ("exaone_moe", "bfloat16", "decode"): "4bcc7f25d5f5de65",
    ("exaone_moe", "bfloat16", "decode-past-16-blocks"): "4bcc7f25d5f5de65",
    ("exaone_moe", "bfloat16", "decode-past-two-tiles"): "a7bd74db1ea6c9ba",
    ("exaone_moe", "bfloat16", "prefill"): "40d60075749c8570",
    ("qwen3_next", "float32", "decode"): "533379045d4ba4d7",
    ("qwen3_next", "float32", "decode-past-16-blocks"): "533379045d4ba4d7",
    ("qwen3_next", "float32", "decode-past-two-tiles"): "c29f35b38d268075",
    ("qwen3_next", "float32", "prefill"): "5f15f0fdab1e84c0",
    ("qwen3_next", "bfloat16", "decode"): "edc6a71c70beef8f",
    ("qwen3_next", "bfloat16", "decode-past-16-blocks"): "edc6a71c70beef8f",
    ("qwen3_next", "bfloat16", "decode-past-two-tiles"): "3057d884547ff87e",
    ("qwen3_next", "bfloat16", "prefill"): "429bff75fe08bade",
    ("axk1", "float32", "decode"): "fdae1f714571c2b7",
    ("axk1", "float32", "decode-past-16-blocks"): "fdae1f714571c2b7",
    ("axk1", "float32", "decode-past-two-tiles"): "a4a9688386cfeebb",
    ("axk1", "float32", "prefill"): "816cbba5e0584bdf",
    ("axk1", "bfloat16", "decode"): "d4742f382b311ef3",
    ("axk1", "bfloat16", "decode-past-16-blocks"): "d4742f382b311ef3",
    ("axk1", "bfloat16", "decode-past-two-tiles"): "89d9e8233af84f64",
    ("axk1", "bfloat16", "prefill"): "99e8d79732ae7e2b",
    ("longcat_flash", "float32", "decode"): "ee441f5cd5a73a62",
    ("longcat_flash", "float32", "decode-past-two-tiles"):
        "a04dd414b58b6f59",
    ("longcat_flash", "float32", "prefill"): "144cbeab07fcc3c3",
    ("longcat_flash", "bfloat16", "decode"): "091f6ff8756c3bb3",
    ("longcat_flash", "bfloat16", "decode-past-two-tiles"):
        "2710cde34e6272cc",
    ("longcat_flash", "bfloat16", "prefill"): "06bae7ddafc6572a",
    ("zaya", "float32", "decode"): "57f88d0e36fdc3c8",
    ("zaya", "float32", "decode-past-16-blocks"): "57f88d0e36fdc3c8",
    ("zaya", "float32", "decode-past-two-tiles"): "7c0f3e2d107ae842",
    ("zaya", "float32", "prefill"): "7f0fec9713f13f45",
    ("zaya", "bfloat16", "decode"): "abebf84341f4e880",
    ("zaya", "bfloat16", "decode-past-16-blocks"): "abebf84341f4e880",
    ("zaya", "bfloat16", "decode-past-two-tiles"): "32cf73d3ebd0db0f",
    ("zaya", "bfloat16", "prefill"): "cb0516a6c16b9224",
    ("phi4flash", "float32", "decode"): "5222e99e72323234",
    ("phi4flash", "float32", "decode-past-16-blocks"): "5222e99e72323234",
    ("phi4flash", "float32", "decode-past-two-tiles"): "b3ce41b320490afd",
    ("phi4flash", "float32", "prefill"): "0ae6cbace60daedc",
    ("phi4flash", "bfloat16", "decode"): "c308157850672aa1",
    ("phi4flash", "bfloat16", "decode-past-16-blocks"): "c308157850672aa1",
    ("phi4flash", "bfloat16", "decode-past-two-tiles"): "43c30c212b57bda6",
    ("phi4flash", "bfloat16", "prefill"): "73bad509276ced20",
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


@pytest.mark.parametrize("family,heads,kernel", [
    ("axk1", 32, "latent_filled_tiles"), ("axk1", 4, "latent_filled_tiles"),
    ("llama", 8, "paged_filled_tiles"),
    ("qwen3_next", 4, "paged_filled_tiles")],
    ids=["a-latent-page-32-heads", "a-latent-page-4-heads",
         "keys-and-values-apart", "keys-and-values-apart-beside-slots"])
def test_the_platform_chooses_the_kernel_at_lowering(family, heads, kernel):
    """The same trace lowered for the CPU and, with no chip attached, for a
    TPU: the TPU's text of the wide step holds a Pallas kernel's custom
    call, called by every attention over the filled tiles, and no loop over
    them, the CPU's the loop and no custom call: ``lax.platform_dependent``
    in ``common._over_tiles``, resolved when the program is lowered. Which
    kernel follows from the page: one array under one cached head (A.X-K1's
    absorbed step, whatever its query heads) is ``ops/latent_tiles.py``'s,
    keys and values apart (``llama``; ``qwen3_next``'s three paging layers
    of twelve) ``ops/paged_tiles.py``'s, and a program holds one of them.
    No environment variable, configuration key or model name is consulted,
    and none is set here. The narrow step and the prefill hold neither."""
    module, config = FAMILIES[family]
    tiny = config.tiny(num_attention_heads=heads) if family == "axk1" \
        else config.tiny()
    assert tiny.num_attention_heads == heads
    cfg = dataclasses.replace(tiny, dtype="bfloat16")
    layers = module.cache_spec(cfg).layers
    cpu, tpu = programs(module, cfg), programs(module, cfg, platform="tpu")
    wide = "decode-past-two-tiles"
    for stage in cpu:
        assert "tpu_custom_call" not in cpu[stage]
        # one function a program holds the kernel (traced and lowered
        # once, whatever the layers), and every such attention calls it
        kernels = layers if stage == wide else 0
        for name in ("latent_filled_tiles", "paged_filled_tiles"):
            assert tpu[stage].count(f'kernel_name = "{name}"') \
                == (kernels > 0 and name == kernel)
        assert tpu[stage].count("call @over_filled_tiles") == kernels

    def loops(texts, stage):
        """Those a step holds beside the narrow step's own."""
        return texts[stage].count("stablehlo.while") \
            - texts["decode"].count("stablehlo.while")

    assert loops(cpu, wide) == layers and loops(tpu, wide) == 0
    assert loops(cpu, "decode-past-16-blocks") == 0 \
        == loops(tpu, "decode-past-16-blocks")


def test_the_readers_of_one_page_call_one_kernel():
    """Phi-4-mini-flash's wide step reads the one paging layer's filled
    tiles eight times (``attn.full`` and the seven ``attn.cross`` readers,
    which are one ``lax.scan``: two call sites at any depth): lowered for a
    TPU it holds ``ops/paged_tiles.py``'s kernel once, called twice, and no
    loop over tiles beside its two scans; the CPU's text holds a loop over
    tiles at each site and no custom call. The narrow step and the prefill
    hold neither."""
    cfg = dataclasses.replace(phi4flash.Phi4FlashConfig.tiny(),
                              dtype="bfloat16")
    assert phi4flash.cache_spec(cfg).layers == 1
    cpu = programs(phi4flash, cfg)
    tpu = programs(phi4flash, cfg, platform="tpu")
    wide = "decode-past-two-tiles"
    for stage in cpu:
        assert "tpu_custom_call" not in cpu[stage]
        assert cpu[stage].count("stablehlo.while") \
            == tpu[stage].count("stablehlo.while") + 2 * (stage == wide)
        assert tpu[stage].count('kernel_name = "paged_filled_tiles"') \
            == (stage == wide)
        assert tpu[stage].count("call @over_filled_tiles") \
            == 2 * (stage == wide)
    assert tpu[wide].count("stablehlo.while") \
        == tpu["decode"].count("stablehlo.while") == 2


def test_a_scanned_stack_holds_each_kernel_once():
    """ZAYA1's layers are one ``lax.scan``: the wide step lowered for a TPU
    holds the kernel over the filled tiles of its one-array page (8 query
    rows under one cached head of ``[v | k^]``) once, called once, in the
    body of the loop that is the layers, and no loop over tiles; the CPU's
    text holds that loop inside the layers' and no custom call. The narrow
    step and the prefill hold the layers' loop alone on both."""
    cfg = dataclasses.replace(zaya.ZayaConfig.tiny(), dtype="bfloat16")
    cpu, tpu = programs(zaya, cfg), programs(zaya, cfg, platform="tpu")
    wide = "decode-past-two-tiles"
    for stage in cpu:
        assert "tpu_custom_call" not in cpu[stage]
        assert cpu[stage].count("stablehlo.while") == 1 + (stage == wide)
        assert tpu[stage].count("stablehlo.while") == 1
        for held in ('kernel_name = "latent_filled_tiles"',
                     "call @over_filled_tiles"):
            assert tpu[stage].count(held) == (stage == wide)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_platform_chooses_the_grouped_product_at_lowering(family):
    """The same trace lowered for the CPU and, with no chip attached, for a
    TPU: every grouped product of ``experts._slab`` (two a sparse layer, in
    the step and in the prefill) is on a TPU a call of the one function
    that holds ``ops/grouped.py``'s kernel, which the program traced and
    lowered once a shape (gate beside up, down), and no ``ragged_dot`` is
    left there; the CPU's text holds no custom call. A family with no
    expert layer holds neither on either platform, nor does ZAYA1 at its
    tiny size, whose programs of few rows send every row through every
    expert (``zaya.DENSE``; its routed prefill at the published size is
    held by ``tests/test_tpu_layout.py``)."""
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


#: the benchmark's configurations: module, configuration class and the rows
#: of its cell's batch bucket
CONFIGS = {
    "yi-1.5-6b": (llama, llama.LlamaConfig, 8),
    "k-exaone-236b-l8-ep8": (exaone_moe, exaone_moe.ExaoneMoeConfig, 32),
    "qwen3-next-80b-l12-ep4": (qwen3_next, qwen3_next.Qwen3NextConfig, 16),
    "phi-4-mini-flash": (phi4flash, phi4flash.Phi4FlashConfig, 32),
    "ax-k1-519b-l7-ep16": (axk1, axk1.AxK1Config, 64),
    "longcat-flash-omni-560b-l4-ep32": (
        longcat_flash, longcat_flash.LongcatFlashConfig, 64),
    "zaya1-8b-l16": (zaya, zaya.ZayaConfig, 64),
}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_a_wide_step_carries_a_row_and_nothing_of_the_capacity(name):
    """A wide step's attention at each configuration's published head
    counts and widths, in bfloat16 blocks of 16 positions over the rows of
    its cell's bucket, at both wide widths, lowered: its one loop carries
    the running softmax a row, ``[rows, Hkv, g, T, vd | 1 | 1]`` in
    float32 and no other float32 array, and where a trip takes less than
    the table's capacity of tiles no float32 array anywhere in the program
    is as long as the capacity (a loop that left its partials a tile held
    ``[capacity, Hkv, g, T, vd + 2]``). The shapes are the module's own
    statement of its cache."""
    module, config, rows = CONFIGS[name]
    doc = json.loads((Path(__file__).parent.parent / "benchmark" / "configs"
                      / f"{name}.json").read_text())
    cfg = config.from_hf({k: v for k, v in doc.items() if k != "benchmark"})
    spec = module.cache_spec(cfg)
    # the pages alone, a few blocks of them: only shapes are lowered
    pool = kvcache.KVBlockPool(spec._replace(state=()), block_tokens=16,
                               budget_mb=1, dtype="bfloat16")
    H, Hkv, hd = cfg.num_attention_heads, spec.kv_heads, spec.head_dim
    vd = spec.values or hd

    def step(q, k, v, lengths, table):
        cache = kvcache.Paged(pool.k, pool.v, table)
        return attend(q, k, v, lengths[:, None],
                      past=cache.past(0, cache.filled(lengths)))

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype)

    for slots in (256, 2048):
        text = jax.jit(step).lower(
            shape(rows, 1, H, hd), shape(rows, 1, Hkv, hd),
            shape(rows, 1, Hkv, vd), shape(rows, dtype=jnp.int32),
            shape(rows, slots, dtype=jnp.int32)).as_text()
        (loop,) = re.findall(r"stablehlo\.while\(.*\) : (.*)\n", text)
        assert _float32(loop) == [(rows, Hkv, H // Hkv, 1, vd)] \
            + [(rows, Hkv, H // Hkv, 1, 1)] * 2
        capacity = rows * slots // kvcache.TILE_BLOCKS
        if capacity > kvcache.TILE_CHUNK:
            assert all(dims[0] != capacity for dims in _float32(text))
