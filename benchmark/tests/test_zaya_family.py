"""The ZAYA1 family (``lib/families/zaya.py``) through the seam's cases: the
generator's bytes pinned at two seeds, the table's order and counts at the
published shapes of ``zaya1-8b-l16``, the costs against hand counts at a toy
size, its reader on a made-up window, the cell's entries in
``BENCHMARK.json``, the file's keys against the catalog's row, the int8
control standing out from the bfloat16 mode, and a rehearsed run of
``zaya1-reason`` to its result line.

Run by hand (``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q``);
``tests/test_benchmark_seam.py`` collects all but the rehearsed run for
tier-1.
"""

from __future__ import annotations

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

TOY = {"model_type": "zaya", "hidden_size": 128, "num_hidden_layers": 4,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "vocab_size": 1024, "num_experts": 4, "num_experts_per_tok": 1,
       "moe_intermediate_size": 64, "router_hidden_size": 32,
       "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
       "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                      "rope_theta": 10000,
                                      "rope_type": "default"},
                           "rope_type": "default"},
       "layer_types": ["hybrid"] * 4, "hidden_act": "silu",
       "attention_bias": False, "sliding_window": None,
       "tie_word_embeddings": True, "rms_norm_eps": 1e-5,
       "torch_dtype": "bfloat16"}
#: sha256 of TOY's shards, 3 of them, as this family's table makes them
PINNED = {
    2147483659: {
        "model-00001-of-00003.safetensors":
            "c8c54b8b9120ff6e40823efaaaf4fdc33051e6755b73e7638b27fbefaa3aa7be",
        "model-00002-of-00003.safetensors":
            "988346aba2b2e64b0e53a22d1c517d089f0e3ed843edd1b7361625d4f9d7e466",
        "model-00003-of-00003.safetensors":
            "ebfa5007d2625d4c831241715d81ad73b0e832d8a3affd6d04f88b11a4dd28ff",
    },
    7: {
        "model-00001-of-00003.safetensors":
            "3918cabde266965932f482f793382593c42e3adb607aa168bb03ecb6ff3092a8",
        "model-00002-of-00003.safetensors":
            "9e48af9804449ea66db044cf4bb27c34ffdd05c91bc6655c90b143658eb62049",
        "model-00003-of-00003.safetensors":
            "c44d00b467cd3946fede1c37a4a14ff42b05506ce96cda817cb3a12f02de8707",
    },
}
INDEX = "fa6a5322ff328a9c324cd5eac0eba7f516a3707508c9891bb01af75d7f1b61f4"
CELL = "zaya1-reason"
CONFIG = "zaya1-8b-l16"
#: the catalog's row ``ZAYA1-8B``: the numbers and switches at its top level
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05, "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}


def _zaya() -> dict:
    return json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())


@pytest.mark.parametrize("seed, name", [
    (seed, name) for seed, files in PINNED.items() for name in files])
def test_zaya_files_are_the_bytes_they_were(seed, name):
    from lib import checkpoint

    ckpt = checkpoint.Checkpoint(TOY, seed, n_shards=3)
    assert ckpt.files[name].sha256() == PINNED[seed][name]
    assert hashlib.sha256(
        ckpt.files["model.safetensors.index.json"]).hexdigest() == INDEX


def test_zaya_table_fills_what_the_configuration_says():
    from lib import families

    table = families.of(TOY).tensors(TOY)
    a, r = "model.layers.2.self_attn.", "model.layers.2.mlp.router."
    # the table is the head too: filled at the hidden size
    assert table["model.embed_tokens.weight"] == ((1024, 128), "normal", 128)
    assert "lm_head.weight" not in table
    assert table[a + "q_proj.weight"] == ((64, 128), "normal", 128)
    assert table[a + "k_proj.weight"] == table[a + "v_proj.weight"] \
        == ((32, 128), "normal", 128)
    # depthwise: two taps that differ; grouped, a head's 16 columns over 2
    # positions
    assert table[a + "conv_qk.0.weight"] == ((96, 1, 2), "normal", 2)
    assert table[a + "conv_qk.1.weight"] == ((96, 16, 2), "normal", 32)
    assert table[a + "conv_qk.1.bias"] == ((96,), "zeros", 0)
    assert table[a + "temp"] == ((2,), "ones", 0)
    assert table[r + "down_proj.weight"] == ((32, 128), "normal", 128)
    assert table[r + "mlp.1.weight"] == ((32, 32), "normal", 32)
    # the router's outputs spread (a 64th of the inputs, at least 1), and
    # the attention's output is a quarter of the plain fill's
    assert table[r + "mlp.2.weight"] == ((5, 32), "normal", 1)
    assert table[a + "o_proj.weight"] == ((128, 64), "normal", 16 * 64)
    big = families.of(TOY).tensors(_zaya())
    assert big["model.layers.2.mlp.router.mlp.2.weight"] \
        == ((17, 256), "normal", 4)
    assert big[a + "o_proj.weight"] == ((2048, 1024), "normal", 16384)
    assert table[r + "balancing_bias"] == ((5,), "zeros", 0)
    # the stream's scale: none into layer 0, drawn beyond
    assert "model.layers.0.mlp.router.depth_scale" not in table
    assert table[r + "depth_scale"] == ((32,), "normal", 4)
    for merge in ("self_attn_merge", "mlp_merge"):
        p = f"model.layers.2.{merge}."
        assert table[p + "residual_scale"] == table[p + "output_scale"] \
            == ((128,), "ones", 0)
        assert table[p + "residual_bias"] == table[p + "output_bias"] \
            == ((128,), "zeros", 0)
    held = sorted({int(n.split(".experts.")[1].split(".")[0])
                   for n in table if ".experts." in n})
    assert held == [0, 1, 2, 3]         # every expert, and no 17th tensor


def test_zaya_table_at_the_published_shapes():
    """The order the checkpoint holds them in and what they add up to: 16
    layers of 29 tensors outside the experts (28 in layer 0, which has no
    ``depth_scale``) and 16 experts of 3 matrices, 3 858 471 232 parameters
    with every vector (7.72 GB of bfloat16)."""
    from lib import families

    doc = _zaya()
    fam = families.of(doc)
    table = fam.tensors(doc)
    names = list(table)
    assert names[0] == "model.embed_tokens.weight"
    assert names[-1] == "model.norm.weight"
    assert names[1:4] == ["model.layers.0." + n for n in (
        "input_layernorm.weight", "self_attn.q_proj.weight",
        "self_attn.k_proj.weight")]
    experts = [n for n in names if ".experts." in n]
    assert len(experts) == 16 * 16 * 3
    assert len(names) == 2 + 16 * (29 + 48) - 1
    assert table["model.embed_tokens.weight"].shape == (262272, 2048)
    assert table["model.layers.15.self_attn.q_proj.weight"].shape \
        == (1024, 2048)
    assert table["model.layers.15.self_attn.v_proj.weight"].shape \
        == (256, 2048)
    assert table["model.layers.15.self_attn.conv_qk.1.weight"].shape \
        == (1280, 128, 2)
    assert table["model.layers.15.mlp.router.mlp.2.weight"].shape \
        == (17, 256)
    assert table["model.layers.15.mlp.experts.15.down_proj.weight"].shape \
        == (2048, 2048)
    total = sum(int(np.prod(t.shape)) for t in table.values())
    assert total == fam.parameters(doc) == 3_858_471_232
    # by the issue's count: attention 5.57 M, the router 0.66 M, an expert
    # 12.58 M, 16 384 B a position, 86 016 B of tails a sequence
    assert fam.attention_weights(doc) == 5_570_560 == 2048 * 1536 \
        + 2 * 10 * 128 * 128 + 1024 * 2048
    assert fam.router_weights(doc) == 659_712
    assert fam.expert_weights(doc) == 12_582_912
    assert fam.position_bytes(doc) == 16_384
    assert fam.tail_bytes(doc) == 86_016 == 16 * 2688 * 2


def test_zaya_costs_against_hand_counts():
    """TOY by hand. An attention sublayer: 128 x (64 + 32 + 32) + 2 x 6 x 16
    x 16 + 64 x 128 = 27 648; a router 128 x 32 + 2 x 32 x 32 + 32 x 5 = 6
    304; four layers of both 135 808; an expert 24 576, which 4 of a
    token's 5 equally likely choices reach."""
    from lib import families

    fam = families.of(TOY)
    assert fam.attention_weights(TOY) == 27_648
    assert fam.router_weights(TOY) == 6_304
    assert fam.unrouted_weights(TOY) == 135_808
    # 10 tokens: 2 x 10 x (135 808 + 4 x 0.8 x 24 576) + the head once, 2 x
    # 1024 x 128; 4 layers x 55 pairs x 4 heads x 2 x (16 + 16)
    assert fam.prefill_flops(TOY, 10) == pytest.approx(
        4_289_024 + 262_144 + 56_320)
    # 3 steps: (135 808 + 1024 x 128) x 2 B each; 15 experts hit x 24 576 x
    # 2 B; rows of 7 and 9 cached positions x 4 layers x 64 x 2 B; two
    # rows' tails in and out, 4 layers x 208 x 2 B each
    steps = [{"experts_hit": 5, "zero_tokens": 1}] * 3
    assert fam.decode_bytes(TOY, steps, [7, 9]) \
        == 3 * 533_760 + 15 * 49_152 + 16 * 512 + 4 * 1_664
    # no experts_hit on the span: no expert is counted
    assert fam.decode_bytes(TOY, [{}], []) == 533_760
    # at the published shapes a row of 2 000 positions reads 32.8 MB of
    # compressed cache a step over its 16 layers, and its tails twice
    doc = _zaya()
    assert families.of(doc).decode_bytes(doc, [], [2000]) \
        == 2000 * 16_384 + 2 * 86_016


def test_zaya_reader_on_a_made_up_window():
    from lib import loadgen, readers

    doc = _zaya()
    fam_bytes = 2 * 86_016
    obs = readers.Observed(t0=0.0, t1=10.0, model=doc, chips=1)
    spec = json.loads(
        (BENCH / "layer_metrics" / "cca_kv_hbm_share.json").read_text())
    assert spec["reader"] == "families.zaya:kv_share"
    assert readers.read(obs, spec) is None          # no span at all
    obs.spans = [
        {"name": "serve.decode-step", "ts": 1.0, "dur": 0.02,
         "attrs": {"batch": 64, "experts_hit": 250}},
        {"name": "serve.decode-step", "ts": 12.0, "dur": 0.02,
         "attrs": {"batch": 64, "cca_kv_bytes": 10 ** 9}}]
    # a program that names no such bytes (the parent's): nothing to read
    assert readers.read(obs, spec) is None
    obs.spans[0]["attrs"]["cca_kv_bytes"] = 1000 * 16_384
    # one request whose second token came inside the window from a step
    # that read its 1 000-token prompt
    record = loadgen.Record(caller=0, prompt=[1] * 1000, max_new=4)
    record.times = [0.5, 1.01]
    obs.records = [record]
    from lib import families

    fixed = (families.of(doc).unrouted_weights(doc) + 262272 * 2048) * 2
    assert readers.read(obs, spec) == pytest.approx(
        100 * 1000 * 16_384 / (fixed + 250 * 12_582_912 * 2
                               + 1000 * 16_384 + fam_bytes))


def _in_the_cells_order(bench: dict) -> bool:
    """Every list of cells names them in the order ``workloads`` has them:
    a cell is appended, never put in ahead of one that was there."""
    order = [w["name"] for w in bench["workloads"]]
    return all(m["workloads"] == sorted(m["workloads"], key=order.index)
               for kind in ("end_to_end", "per_layer") for m in bench[kind]
               if "workloads" in m)


def test_zaya_cell_is_in_every_list_it_was_promised():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "reason-deep-c64", 1)
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    doc = _zaya()
    assert config["reduced"] == doc["benchmark"]["reduced"] \
        == ["num_hidden_layers"]
    assert config["source"] == doc["benchmark"]["source"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", [])}
    assert listed == {
        "itl_p50_ms", "batch_occupancy", "prefill_stall_p99_ms",
        "moe_route_share", "moe_tokens_per_expert_hit", "prefill_mfu",
        "moe_zero_share", "cca_kv_hbm_share"}
    assert _in_the_cells_order(bench)
    new, = [m for m in bench["per_layer"] if m["name"] == "cca_kv_hbm_share"]
    assert (new["moves"], new["layer"], new["workloads"]) \
        == ("itl_p50_ms", "kv pool", [CELL])
    # the traffic is axk1-reason's, to the token
    axk1, = [w for w in bench["workloads"] if w["name"] == "axk1-reason"]
    assert axk1["traffic"] == cell["traffic"]
    from lib import loadgen

    traffic = json.loads(
        (BENCH / "traffic" / "reason-deep-c64.json").read_text())
    callers = loadgen.callers_of(traffic)
    assert len(callers) == 64 == doc["benchmark"]["engine"]["max_batch"]
    assert max(p + o for c in callers for p, o in c) == 3072
    # the pool holds every session at its longest, half as much again, at
    # the 512 columns a layer its page keeps of a position, and the slots
    engine = doc["benchmark"]["engine"]
    pages = 1.5 * 64 * 3072 * 16 * 512 * 2
    assert 0 <= (engine["kv_mb"] << 20) - pages - 65 * 86_016 < 8 << 20
    assert engine["max_new_tokens"] == 2048


def test_zaya_file_keeps_the_published_keys():
    """Every key of the catalog's row is in the file with its value, but
    the one that ``reduced`` lists; no width among them; the nested groups
    are copied whole."""
    doc = _zaya()
    differs = sorted(k for k, v in PUBLISHED.items() if doc.get(k) != v)
    assert differs == doc["benchmark"]["reduced"] == ["num_hidden_layers"]
    assert doc["benchmark"]["published"] == {"num_hidden_layers": 40}
    assert doc["num_hidden_layers"] == 16
    assert doc["layer_types"] == ["hybrid"] * 40
    assert doc["rope_parameters"] == {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5,
                           "rope_theta": 10000, "rope_type": "default"},
        "rope_type": "default"}
    # the family's switches the 8B's row leaves out, written in and listed
    for key in ("cca", "zaya_use_eda", "zaya_use_mod",
                "scale_residual_merge"):
        assert doc[key] is True and key in doc["benchmark"]["assumed"]
    assert all(k in doc["benchmark"]["assumed_note"]
               for k in doc["benchmark"]["assumed"])


@pytest.mark.parametrize("seed", [21, 2**31 + 22])
def test_zaya_control_int8_stands_out_from_bfloat16(seed):
    """As ``test_correct.py`` holds for the Llama family: under the float32
    reference, what the int8 mode puts first lies further below the best
    than what the bfloat16 mode does (1 024 tokens compared). The margin is
    narrower than the other families' 2x at this toy size: with one expert
    a token, a choice the bfloat16 mode exchanges at a near-tie moves the
    whole of the sublayer's sum (one such token lies 0.53 below the best
    at the second seed), and rows of 128 hardly lose to int8; the chip's
    readings at the published widths are in the cell's file."""
    import jax.numpy as jnp

    from lib import checkpoint, reference

    ckpt = checkpoint.Checkpoint(TOY, seed, n_shards=2)
    rng = np.random.default_rng([seed, 1])
    seqs = [[int(t) for t in rng.integers(0, TOY["vocab_size"], 160)]
            for _ in range(8)]
    wanted = [range(32, 160)] * 8
    ref = reference.logits(ckpt, seqs, wanted)

    def gaps(mode):
        low = reference.logits(ckpt, seqs, wanted, mode=mode)
        return np.concatenate([reference.gaps_below_best(
            r, np.asarray(jnp.argmax(lo, axis=1))[:128])
            for r, lo in zip(ref, low)])

    sound, control = gaps("bfloat16"), gaps("int8")
    assert control.mean() > 1.5 * sound.mean(), (sound.mean(),
                                                 control.mean())
    assert (control > 0).sum() > (sound > 0).sum()


def test_zaya_reason_rehearsed_to_its_result_line(tmp_path, monkeypatch,
                                                  capfd):
    """``run.py --workload zaya1-reason --rehearse`` with the cell's own
    traffic, metric files and family, at the rehearsal's toy sizes. The
    pool is cut to 8 MiB and the batch to 8 in a copy of the
    configuration, and the traffic to 8 callers of a 16th of the prompts
    (64 and 128 tokens: both past two tiles of the copy's blocks of 2, so
    that their steps run over the filled tiles of all 4 layers) and a 64th
    of the replies: on the CPU every row's write copies the pool."""
    import run as harness

    from demodel_tpu.utils import trace

    doc = _zaya()
    doc["benchmark"]["engine"].update(kv_mb=8, max_batch=8, block_tokens=2,
                                      max_new_tokens=64)
    traffic = json.loads(
        (BENCH / "traffic" / "reason-deep-c64.json").read_text())
    for group, callers in zip(traffic["groups"], (6, 2)):
        group["callers"] = callers
        for row in group["cycle"]:
            row["prompt"] //= 16
            row["output"] //= 64
    bench = tmp_path / "benchmark"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    (bench / "configs" / f"{CONFIG}.json").write_text(json.dumps(doc))
    (bench / "traffic" / "reason-deep-c64.json").write_text(
        json.dumps(traffic))
    for shared in ("cells", "peaks.json", "spans", "layer_metrics",
                   "end_to_end"):
        (bench / shared).symlink_to(BENCH / shared)
    (tmp_path / "BENCHMARK.json").write_text(
        (BENCH.parent / "BENCHMARK.json").read_text())
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "HERE", bench)
    try:
        code, result, reasons = harness.run(harness.parse(
            ["--workload", CELL, "--seed", "2147484001",
             "--seconds", "4", "--trace", "1", "--rehearse"]))
    finally:
        trace.reset()
    assert code == 0 and result["failed"] == 0 and result["attempted"] >= 8
    assert [r for r in reasons if not r.startswith("served_gap_")] \
        == ["a rehearsal is never a result"], reasons
    assert set(result["compared"]) == {"served_gap_max", "served_gap_mean"}
    # what the program names of the page, the skips and the experts reaches
    # the metrics (counts, which a rehearsal may say: 1 of the toy's 5
    # outputs is the skip)
    said = json.loads(capfd.readouterr().out.split(
        "rehearsal metrics (CPU, not device numbers): ")[1].splitlines()[0])
    assert 5 < said["moe_zero_share"] < 45
    assert 0 < said["cca_kv_hbm_share"] < 100
    assert said["moe_tokens_per_expert_hit"] >= 1
