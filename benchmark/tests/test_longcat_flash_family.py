"""The LongCat-Flash family (``lib/families/longcat_flash.py``) through the
seam's cases: the generator's bytes pinned at two seeds, the table's order
and counts at the published shapes of ``longcat-flash-omni-560b-l4-ep32``,
the costs against hand counts at a toy size, its reader on a made-up
window, the cell's entries in ``BENCHMARK.json``, the int8 control standing
out from the bfloat16 mode, and a rehearsed run of ``longcat-reason`` to its
result line.

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

TOY = {"model_type": "longcat_flash", "hidden_size": 128,
       "ffn_hidden_size": 256, "expert_ffn_hidden_size": 64,
       "num_layers": 4, "num_attention_heads": 4, "q_lora_rank": 48,
       "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
       "v_head_dim": 16, "vocab_size": 1024, "mla_scale_q_lora": True,
       "mla_scale_kv_lora": True, "n_routed_experts": 4, "ep_size": 4,
       "ep_rank": 2, "zero_expert_num": 8, "zero_expert_type": "identity",
       "moe_topk": 6, "routed_scaling_factor": 6, "attention_method": "MLA",
       "attention_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-5,
       "rope_theta": 10000, "torch_dtype": "bfloat16"}
#: sha256 of TOY's shards, 3 of them, as this family's table first made them
PINNED = {
    2147483659: {
        "model-00001-of-00003.safetensors":
            "dc6b21c278bb28d2ac19ab83603b397c38382b2480102d467bac2c22d189899f",
        "model-00002-of-00003.safetensors":
            "3baa514598839ca93df686556eeb88549821de54eb884e4188883a925d808a52",
        "model-00003-of-00003.safetensors":
            "fb46e26f0a9df2badfe47a870f39b4dec865822354713cee9167569452e0b1ec",
    },
    7: {
        "model-00001-of-00003.safetensors":
            "6ae02dcdd255516dab526a18a38b2d6bcaa01a2af6de78c23d401cbd96701593",
        "model-00002-of-00003.safetensors":
            "ce9492d63ac4b4b67a2368b5463a0fce02a6174b7fe2d0d19ca8ae8ce3c95ea1",
        "model-00003-of-00003.safetensors":
            "d3f4eadbb451351be3bcc2752034a68831d674e483c2f9bda6e26bf0c757086e",
    },
}
INDEX = "de0d89c753f08b3be1bb30c115994a5ab5b624d66b88040443cc7810cbb3a852"
CELL = "longcat-reason"
CONFIG = "longcat-flash-omni-560b-l4-ep32"


def _longcat() -> dict:
    return json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())


@pytest.mark.parametrize("seed, name", [
    (seed, name) for seed, files in PINNED.items() for name in files])
def test_longcat_files_are_the_bytes_they_were(seed, name):
    from lib import checkpoint

    ckpt = checkpoint.Checkpoint(TOY, seed, n_shards=3)
    assert ckpt.files[name].sha256() == PINNED[seed][name]
    assert hashlib.sha256(
        ckpt.files["model.safetensors.index.json"]).hexdigest() == INDEX


def test_longcat_table_holds_the_share_it_is_told():
    from lib import families

    table = families.of(TOY).tensors(TOY)
    held = sorted({int(n.split(".experts.")[1].split(".")[0])
                   for n in table if ".experts." in n})
    assert held == [8, 9, 10, 11]                   # ep_rank 2 of 4
    # the router keeps its whole width: 4 x 4 routed and 8 identity
    # experts, of which only the routed ones have tensors anywhere
    p = "model.layers.1.mlp.router."
    assert table[p + "classifier.weight"] == ((24, 128), "normal", 128)
    assert table[p + "e_score_correction_bias"] == ((24,), "zeros", 0)
    assert table["model.embed_tokens.weight"].fan_in == 1
    for i in (0, 1):        # two sublayers a layer, each with its own
        a = f"model.layers.2.self_attn.{i}."
        assert table[a + "kv_a_proj_with_mqa.weight"] == (
            (40, 128), "normal", 128)
        # filled at the fan-in the latent's scale gives them: rank x
        # (hidden / rank) = the hidden size, not the rank
        assert table[a + "kv_b_proj.weight"] == ((4 * 32, 32), "normal", 128)
        assert table[a + "q_b_proj.weight"] == ((4 * 24, 48), "normal", 128)
        assert table[a + "kv_a_layernorm.weight"] == ((32,), "ones", 0)
        assert table[f"model.layers.2.mlps.{i}.down_proj.weight"] == (
            (128, 256), "normal", 256)


def test_longcat_table_at_the_published_shapes():
    """The order the checkpoint holds them in and what they add up to: 4
    double layers of 2 x (9 + 3) tensors, a router with its bias and 16
    experts of 3 matrices, 5 172 749 312 parameters with the norms and the
    selection bias (10.35 GB of bfloat16)."""
    from lib import families

    doc = _longcat()
    fam = families.of(doc)
    table = fam.tensors(doc)
    names = list(table)
    assert names[0] == "model.embed_tokens.weight"
    assert names[-2:] == ["model.norm.weight", "lm_head.weight"]
    assert names[1:13] == ["model.layers.0." + n + ".weight" for n in (
        "input_layernorm.0", "self_attn.0.q_a_proj",
        "self_attn.0.q_a_layernorm", "self_attn.0.q_b_proj",
        "self_attn.0.kv_a_proj_with_mqa", "self_attn.0.kv_a_layernorm",
        "self_attn.0.kv_b_proj", "self_attn.0.o_proj",
        "post_attention_layernorm.0", "mlps.0.gate_proj", "mlps.0.up_proj",
        "mlps.0.down_proj")]
    assert names[13] == "model.layers.0.input_layernorm.1.weight"
    assert names[25:27] == [
        "model.layers.0.mlp.router.classifier.weight",
        "model.layers.0.mlp.router.e_score_correction_bias"]
    experts = [n for n in names if ".experts." in n]
    assert len(experts) == 4 * 16 * 3
    assert {int(n.split(".experts.")[1].split(".")[0])
            for n in experts} == set(range(16))
    assert table["model.layers.3.mlp.router.classifier.weight"].shape \
        == (768, 6144)
    assert table["model.layers.3.self_attn.1.kv_b_proj.weight"].shape \
        == (64 * 256, 512)
    assert table["model.layers.3.self_attn.1.q_b_proj.weight"].shape \
        == (64 * 192, 1536)
    assert table["lm_head.weight"].shape == (16384, 6144)
    assert len(names) == 3 + 4 * (2 * 12 + 2 + 16 * 3)
    total = sum(int(np.prod(t.shape)) for t in table.values())
    assert total == fam.parameters(doc) == 5_172_749_312
    # by the issue's count: a sublayer's attention 90.57 M, a layer outside
    # its routed experts 638.84 M, an expert 37.75 M, 9 216 B a position
    assert fam.attention_weights(doc) == 90_570_752
    assert fam.expert_weights(doc) == 37_748_736
    assert fam.unrouted_weights(doc) == 4 * 638_844_928 == 4 * (
        2 * 90_570_752 + 2 * 3 * 6144 * 12288 + 6144 * 768)
    assert fam.position_bytes(doc) == 9216 == 8 * 576 * 2


def test_longcat_costs_against_hand_counts():
    """TOY by hand. A sublayer's attention: 128 x 48 + 48 x 4 x 24 + 128 x
    40 + 32 x 4 x 32 + 64 x 128 = 28 160; a layer outside its routed
    experts: 2 x 28 160 + 2 x 3 x 128 x 256 + 128 x 24 = 256 000, four of
    them 1 024 000; an expert 24 576, of which a token's 6 choices land on
    6 x 4 / 24 = 1 a layer; an identity assignment costs nothing."""
    from lib import families

    fam = families.of(TOY)
    assert fam.attention_weights(TOY) == 28_160
    assert fam.unrouted_weights(TOY) == 1_024_000
    # 10 tokens: 2 x 10 x (1 024 000 + 4 x 24 576) + the head once, 2 x
    # 1024 x 128; 8 sublayers x 55 pairs x 4 heads x 2 x (16 + 8 + 16)
    assert fam.prefill_flops(TOY, 10) == 22_446_080 + 262_144 + 140_800
    # 3 steps: (1 024 000 + 1024 x 128) x 2 B each; 15 experts hit x 24 576
    # x 2 B; rows of 7 and 9 cached positions x 8 sublayers x 40 x 2 B
    steps = [{"experts_hit": 5, "zero_tokens": 9}] * 3
    assert fam.decode_bytes(TOY, steps, [7, 9]) \
        == 3 * 2_310_144 + 15 * 49_152 + 16 * 640
    # no experts_hit on the span: no expert is counted
    assert fam.decode_bytes(TOY, [{}], []) == 2_310_144
    # at the published shapes a row of 2 000 positions reads 18.4 MB of
    # latent a step over its 8 sublayers
    doc = _longcat()
    assert families.of(doc).decode_bytes(doc, [], [2000]) == 2000 * 9216
    assert [families.of(doc).prefill_flops(doc, t) for t in (512, 1024)] \
        == [2698598416384.0, 5482894852096.0]


def test_longcat_reader_on_a_made_up_window():
    from lib import readers

    doc = _longcat()
    obs = readers.Observed(t0=0.0, t1=10.0, model=doc, chips=1)
    spec = json.loads(
        (BENCH / "layer_metrics" / "moe_zero_share.json").read_text())
    assert readers.read(obs, spec) is None          # no span at all
    obs.spans = [
        {"name": "serve.decode-step", "ts": 1.0, "dur": 0.02,
         "attrs": {"batch": 64, "experts_hit": 40}},
        {"name": "serve.decode-step", "ts": 2.0, "dur": 0.02,
         "attrs": {"batch": 63, "experts_hit": 41}},
        {"name": "serve.decode-step", "ts": 12.0, "dur": 0.02,
         "attrs": {"batch": 64, "assignments": 3072, "zero_tokens": 3072}}]
    # a program that names no identity assignments: nothing to read, no
    # error
    assert readers.read(obs, spec) is None
    for s, (made, free) in zip(obs.spans, ((3072, 1000), (3024, 1032))):
        s["attrs"].update(assignments=made, zero_tokens=free)
    # the step after the window's end is not the window's
    assert readers.read(obs, spec) == pytest.approx(100 * 2032 / 6096)


def _in_the_cells_order(bench: dict) -> bool:
    """Every list of cells names them in the order ``workloads`` has them:
    a cell is appended, never put in ahead of one that was there."""
    order = [w["name"] for w in bench["workloads"]]
    return all(m["workloads"] == sorted(m["workloads"], key=order.index)
               for kind in ("end_to_end", "per_layer") for m in bench[kind]
               if "workloads" in m)


def test_longcat_cell_is_in_every_list_it_was_promised():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "reason-mid-c64", 1)
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    doc = _longcat()
    assert config["reduced"] == doc["benchmark"]["reduced"]
    assert config["source"] == doc["benchmark"]["source"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", [])}
    assert listed == {
        "itl_p50_ms", "batch_occupancy", "prefill_stall_p99_ms",
        "moe_expert_hbm_roofline", "moe_route_share",
        "moe_tokens_per_expert_hit", "prefill_mfu", "moe_zero_share"}
    assert _in_the_cells_order(bench)
    # the reader of moe_expert_hbm_roofline takes an expert's bytes from
    # the configuration through the EXAONE family's file, by the keys the
    # file keeps for it
    from lib import families
    from lib.families import exaone_moe

    assert exaone_moe.expert_weights(doc) == 3 * 6144 * 2048 \
        == families.of(doc).expert_weights(doc)
    # the traffic the issue gives
    from lib import loadgen

    traffic = json.loads(
        (BENCH / "traffic" / "reason-mid-c64.json").read_text())
    assert (traffic["loop"], traffic["stream"],
            traffic["stationary_start"]) == ("closed", True, True)
    callers = loadgen.callers_of(traffic)
    assert len(callers) == 64 == doc["benchmark"]["engine"]["max_batch"]
    assert sorted(callers[0]) == [(512, 1024), (512, 1024), (512, 1536),
                                  (512, 1536)]
    assert sorted(callers[63]) == [(1024, 256), (1024, 512), (1024, 512),
                                   (1024, 768)]
    assert sum(c[0][0] == 512 for c in callers) == 48
    assert max(p + o for c in callers for p, o in c) == 2048
    # the pool holds every session at its longest, half as much again, at
    # the 640 columns a sublayer its page keeps of a position (576 the
    # latent), two sublayers a layer
    engine = doc["benchmark"]["engine"]
    assert engine["kv_mb"] << 20 == 1.5 * 64 * 2048 * 8 * 640 * 2
    assert engine["max_new_tokens"] == 1536


def test_longcat_file_keeps_the_published_keys():
    """Every key of the catalog's row is in the file with its value, but
    the three that ``reduced`` lists; no width among them."""
    doc = _longcat()
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    differs = sorted(k for k, v in published.items() if doc.get(k) != v)
    assert differs == sorted(doc["benchmark"]["reduced"]) == sorted(
        ["num_layers", "n_routed_experts", "vocab_size"])
    assert doc["benchmark"]["published"] == {
        k: published[k] for k in doc["benchmark"]["reduced"]}
    assert (doc["num_layers"], doc["n_routed_experts"], doc["vocab_size"],
            doc["ep_size"], doc["ep_rank"]) == (4, 16, 16384, 32, 0)
    # the router's width is the published one: held x shares + identity
    assert doc["n_routed_experts"] * doc["ep_size"] \
        + doc["zero_expert_num"] == 768
    # the keys kept for the EXAONE family's reader say what the source's do
    assert (doc["num_hidden_layers"], doc["intermediate_size"],
            doc["moe_intermediate_size"], doc["num_experts_per_tok"],
            doc["num_experts"], doc["num_key_value_heads"]) \
        == (doc["num_layers"], doc["ffn_hidden_size"],
            doc["expert_ffn_hidden_size"], doc["moe_topk"],
            doc["n_routed_experts"], doc["num_attention_heads"])


@pytest.mark.parametrize("seed", [21, 2**31 + 22])
def test_longcat_control_int8_stands_out_from_bfloat16(seed):
    """As ``test_correct.py`` holds for the Llama family: under the float32
    reference, what the int8 mode puts first lies further below the best
    than what the bfloat16 mode does (1 024 tokens compared)."""
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
    assert control.mean() > 2 * sound.mean(), (sound.mean(), control.mean())
    assert (control > 0).sum() > (sound > 0).sum()


def test_longcat_reason_rehearsed_to_its_result_line(tmp_path, monkeypatch,
                                                     capfd):
    """``run.py --workload longcat-reason --rehearse`` with the cell's own
    traffic, metric files and family, at the rehearsal's toy sizes. The
    pool is cut to 8 MiB and the batch to 8 in a copy of the
    configuration, and the traffic to 8 callers of a 16th of the prompts
    (32 and 64 tokens: the longer ones past two tiles of the copy's blocks
    of 2, so that their steps run over the filled tiles of all 8
    sublayers) and a 64th of the replies: on the CPU every row's write
    copies the pool."""
    import run as harness

    from demodel_tpu.utils import trace

    doc = _longcat()
    doc["benchmark"]["engine"].update(kv_mb=8, max_batch=8, block_tokens=2,
                                      max_new_tokens=64)
    traffic = json.loads(
        (BENCH / "traffic" / "reason-mid-c64.json").read_text())
    for group, callers in zip(traffic["groups"], (6, 2)):
        group["callers"] = callers
        for row in group["cycle"]:
            row["prompt"] //= 16
            row["output"] //= 64
    bench = tmp_path / "benchmark"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    (bench / "configs" / f"{CONFIG}.json").write_text(json.dumps(doc))
    (bench / "traffic" / "reason-mid-c64.json").write_text(
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
    # what the program names of the identity experts reaches the metric
    # (a count, which a rehearsal may say: 8 of the toy's 24 outputs)
    said = capfd.readouterr().out.split(
        "rehearsal metrics (CPU, not device numbers): ")[1].splitlines()[0]
    assert 20 < json.loads(said)["moe_zero_share"] < 47
