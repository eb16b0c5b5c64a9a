"""The A.X-K1 family (``lib/families/axk1.py``) through the seam's cases:
the generator's bytes pinned at two seeds, the table's order and counts at
the published shapes of ``ax-k1-519b-l7-ep16``, the costs against hand
counts at a toy size, its reader on a made-up window, the cell's entries in
``BENCHMARK.json``, the int8 control standing out from the bfloat16 mode,
and a rehearsed run of ``axk1-reason`` to its result line.

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

TOY = {"model_type": "axk1", "hidden_size": 128, "intermediate_size": 256,
       "moe_intermediate_size": 64, "num_hidden_layers": 4,
       "num_attention_heads": 4, "num_key_value_heads": 4,
       "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab_size": 1024,
       "n_routed_experts": 4, "num_experts_per_tok": 4,
       "n_shared_experts": 1, "n_group": 4, "topk_group": 2, "ep_size": 4,
       "ep_rank": 2, "first_k_dense_replace": 1, "norm_topk_prob": True,
       "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
       "rope_theta": 10000,
       "rope_scaling": {"type": "yarn", "factor": 8, "beta_fast": 32,
                        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 16},
       "torch_dtype": "bfloat16"}
#: sha256 of TOY's shards, 3 of them, as this family's table first made them
PINNED = {
    2147483659: {
        "model-00001-of-00003.safetensors":
            "577e4cc687c0d4e64fe2cd7049eaa167a4850ce62939300f7a2710f3e5957974",
        "model-00002-of-00003.safetensors":
            "2e2d94235b2e500b4d51e60f4f93ffc8dd2d443a25b92b2e884387408c6783d7",
        "model-00003-of-00003.safetensors":
            "8e441be2357a77781446bfb0034f17919f35c29a37d7ea2d6ad3ee8e94f71eb0",
    },
    7: {
        "model-00001-of-00003.safetensors":
            "6c240d24907329a89692abc28e86eea1cf81b7b3c5433330c0475ad84574e0cb",
        "model-00002-of-00003.safetensors":
            "5beb055b27030b49614a9d4cece8ccc1f9a4a5d7b98bbeea92f9b16e9151a184",
        "model-00003-of-00003.safetensors":
            "9aac302a9e9691390ec1544f0470d9f12429b0256e0233c18a0be31fa3479c36",
    },
}
INDEX = "ebc18383b7cba3f4a57941cf765d67250a023ede40de25d2a086a03fababb300"
CELL = "axk1-reason"


def _axk1() -> dict:
    return json.loads(
        (BENCH / "configs" / "ax-k1-519b-l7-ep16.json").read_text())


@pytest.mark.parametrize("seed, name", [
    (seed, name) for seed, files in PINNED.items() for name in files])
def test_axk1_files_are_the_bytes_they_were(seed, name):
    from lib import checkpoint

    ckpt = checkpoint.Checkpoint(TOY, seed, n_shards=3)
    assert ckpt.files[name].sha256() == PINNED[seed][name]
    assert hashlib.sha256(
        ckpt.files["model.safetensors.index.json"]).hexdigest() == INDEX


def test_axk1_table_holds_the_share_it_is_told():
    from lib import families

    table = families.of(TOY).tensors(TOY)
    held = sorted({int(n.split(".experts.")[1].split(".")[0])
                   for n in table if ".experts." in n})
    assert held == [8, 9, 10, 11]                   # ep_rank 2 of 4
    assert table["model.layers.1.mlp.gate.weight"].shape == (16, 128)
    assert not any("e_score_correction_bias" in n for n in table)
    assert table["model.embed_tokens.weight"].fan_in == 1
    assert "model.layers.0.mlp.gate.weight" not in table   # the dense one
    a = "model.layers.2.self_attn."
    assert table[a + "kv_a_proj_with_mqa.weight"] == ((40, 128), "normal",
                                                      128)
    assert table[a + "kv_b_proj.weight"] == ((4 * 32, 32), "normal", 32)
    assert table[a + "q_b_proj.weight"] == ((4 * 24, 48), "normal", 48)
    assert table[a + "kv_a_layernorm.weight"] == ((32,), "ones", 0)


def test_axk1_table_at_the_published_shapes():
    """The order the checkpoint holds them in and what they add up to: 7
    layers, 6 of them with 12 experts of 3 matrices, 4 841 331 712
    parameters with the norms (9.68 GB of bfloat16)."""
    from lib import families

    doc = _axk1()
    fam = families.of(doc)
    table = fam.tensors(doc)
    names = list(table)
    assert names[0] == "model.embed_tokens.weight"
    assert names[-2:] == ["model.norm.weight", "lm_head.weight"]
    assert names[1:10] == ["model.layers.0." + n + ".weight" for n in (
        "input_layernorm", "self_attn.q_a_proj", "self_attn.q_a_layernorm",
        "self_attn.q_b_proj", "self_attn.kv_a_proj_with_mqa",
        "self_attn.kv_a_layernorm", "self_attn.kv_b_proj",
        "self_attn.o_proj", "post_attention_layernorm")]
    experts = [n for n in names if ".experts." in n]
    assert len(experts) == 6 * 12 * 3
    assert {int(n.split(".experts.")[1].split(".")[0])
            for n in experts} == set(range(12))
    assert sum(n.endswith("mlp.gate.weight") for n in names) == 6
    assert table["model.layers.3.mlp.gate.weight"].shape == (192, 7168)
    assert table["model.layers.3.self_attn.kv_b_proj.weight"].shape \
        == (64 * 256, 512)
    assert table["lm_head.weight"].shape == (20480, 7168)
    assert len(names) == 3 + 7 * 9 + 3 + 6 * (1 + 12 * 3 + 3)
    total = sum(int(np.prod(t.shape)) for t in table.values())
    assert total == fam.parameters(doc) == 4_841_331_712
    # by the issue's count: attention 101.1 M, a sparse layer outside its
    # routed experts 146.5 M, an expert 44.04 M, 8 064 B a position
    assert fam.attention_weights(doc) == 101_122_048
    assert fam.expert_weights(doc) == 44_040_192
    assert fam.unrouted_weights(doc) == 7 * 101_122_048 \
        + 3 * 7168 * 18432 + 6 * (44_040_192 + 7168 * 192)
    assert fam.position_bytes(doc) == 8064


def test_axk1_costs_against_hand_counts():
    """TOY by hand. Attention a layer: 128 x 48 + 48 x 4 x 24 + 128 x 40 +
    32 x 4 x 32 + 64 x 128 = 28 160; unrouted: 4 x 28 160 + 3 x 128 x 256
    + 3 x (3 x 128 x 64 + 128 x 16) = 290 816; an expert 24 576, of which a
    token's 4 choices land on 4 / 4 = 1 a sparse layer."""
    from lib import families

    fam = families.of(TOY)
    assert fam.attention_weights(TOY) == 28_160
    assert fam.unrouted_weights(TOY) == 290_816
    # 10 tokens: 2 x 10 x (290 816 + 3 x 24 576) + the head once, 2 x 1024
    # x 128; 4 layers x 55 pairs x 4 heads x 2 x (16 + 8 + 16)
    assert fam.prefill_flops(TOY, 10) == 7_290_880 + 262_144 + 70_400
    # 3 steps: (290 816 + 1024 x 128) x 2 B each; 15 experts hit x 24 576 x
    # 2 B; rows of 7 and 9 cached positions x 4 layers x 40 x 2 B
    steps = [{"experts_hit": 5}] * 3
    assert fam.decode_bytes(TOY, steps, [7, 9]) \
        == 3 * 843_776 + 15 * 49_152 + 16 * 320
    # no experts_hit on the span: no expert is counted
    assert fam.decode_bytes(TOY, [{}], []) == 843_776
    # at the published shapes a row of 2 000 positions reads 16.1 MB of
    # latent a step, where K and V of 64 heads of 192 | 128 would be 573 MB
    doc = _axk1()
    assert families.of(doc).decode_bytes(doc, [], [2000]) == 2000 * 8064
    assert [families.of(doc).prefill_flops(doc, t) for t in (1024, 2048)] \
        == [3240859009024.0, 6782072127488.0]


def test_axk1_reader_on_a_made_up_window():
    from lib import families, loadgen, readers

    doc = _axk1()
    fam = families.of(doc)
    obs = readers.Observed(t0=0.0, t1=10.0, model=doc, chips=1)
    spec = json.loads(
        (BENCH / "layer_metrics" / "latent_kv_hbm_share.json").read_text())
    assert readers.read(obs, spec) is None          # no span at all
    rec = loadgen.Record(0, [1] * 1024, 8)
    rec.times = [0.5, 1.5, 2.5, 11.0]       # tokens 2 and 3 in the window
    obs.records = [rec]
    obs.spans = [
        {"name": "serve.decode-step", "ts": 1.0, "dur": 0.02,
         "attrs": {"batch": 1, "experts_hit": 10}},
        {"name": "serve.decode-step", "ts": 2.0, "dur": 0.02,
         "attrs": {"batch": 1, "experts_hit": 12}}]
    # a parent that names no latent bytes: nothing to read, no error
    assert readers.read(obs, spec) is None
    for s, n in zip(obs.spans, (1024, 1025)):
        s["attrs"]["latent_bytes"] = n * 8064
    moved = (1024 + 1025) * 8064
    total = fam.decode_bytes(doc, [s["attrs"] for s in obs.spans],
                             [1024, 1025])
    assert total == 2 * (fam.unrouted_weights(doc) + 20480 * 7168) * 2 \
        + 22 * 44_040_192 * 2 + moved
    assert readers.read(obs, spec) == pytest.approx(100 * moved / total)


def test_axk1_cell_is_in_every_list_it_was_promised():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("ax-k1-519b-l7-ep16", "reason-deep-c64", 1)
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    doc = _axk1()
    assert config["reduced"] == doc["benchmark"]["reduced"]
    assert config["source"] == doc["benchmark"]["source"]
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", [])}
    assert listed == {
        "itl_p50_ms", "batch_occupancy", "prefill_stall_p99_ms",
        "moe_expert_hbm_roofline", "moe_route_share",
        "moe_tokens_per_expert_hit", "prefill_mfu", "latent_kv_hbm_share"}
    for m in bench["per_layer"]:
        if "workloads" in m:        # the new cell comes last where it is
            assert CELL not in m["workloads"][:-1]
    # the reader of moe_expert_hbm_roofline takes an expert's bytes from
    # the configuration through the EXAONE family's file
    from lib.families import exaone_moe

    assert exaone_moe.expert_weights(doc) == 3 * 7168 * 2048
    # the traffic the issue gives
    from lib import loadgen

    traffic = json.loads(
        (BENCH / "traffic" / "reason-deep-c64.json").read_text())
    callers = loadgen.callers_of(traffic)
    assert len(callers) == 64 == doc["benchmark"]["engine"]["max_batch"]
    assert sorted(callers[0]) == [(1024, 1024), (1024, 1536), (1024, 1536),
                                  (1024, 2048)]
    assert sorted(callers[63]) == [(2048, 256), (2048, 512), (2048, 512),
                                   (2048, 768)]
    assert sum(c[0][0] == 1024 for c in callers) == 48
    assert max(p + o for c in callers for p, o in c) == 3072
    # the pool holds every session at its longest, half as much again, at
    # the 640 columns a layer its page keeps of a position (576 the latent)
    engine = doc["benchmark"]["engine"]
    assert engine["kv_mb"] << 20 == 1.5 * 64 * 3072 * 7 * 640 * 2


def test_axk1_file_keeps_the_published_keys():
    """Every key of the catalog's row is in the file with its value, but
    the four that ``reduced`` lists; no width among them."""
    doc = _axk1()
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "axk1",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
        "n_routed_experts": 192, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 64, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "none", "v_head_dim": 128, "vocab_size": 163840}
    differs = sorted(k for k, v in published.items() if doc.get(k) != v)
    assert differs == sorted(doc["benchmark"]["reduced"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size", "ep_size"])
    assert doc["benchmark"]["published"] == {
        k: published[k] for k in doc["benchmark"]["reduced"]}
    assert (doc["num_hidden_layers"], doc["n_routed_experts"],
            doc["vocab_size"], doc["ep_size"], doc["ep_rank"]) \
        == (7, 12, 20480, 16, 0)


@pytest.mark.parametrize("seed", [21, 2**31 + 22])
def test_axk1_control_int8_stands_out_from_bfloat16(seed):
    """As ``test_correct.py`` holds for the Llama family: under the float32
    reference, what the int8 mode puts first lies further below the best
    than what the bfloat16 mode does (1 024 tokens compared: with 256, a
    handful of near-ties decide the mean of four layers)."""
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


def test_axk1_reason_rehearsed_to_its_result_line(tmp_path, monkeypatch):
    """``run.py --workload axk1-reason --rehearse`` with the cell's own
    traffic, metric files and family, at the rehearsal's toy sizes. The
    pool is cut to 8 MiB and the batch to 8 in a copy of the
    configuration, and the traffic to 8 callers of a 32nd of the lengths
    (prompts of 32 and 64, past two tiles of the copy's blocks of 2, so
    that the steps run over the filled tiles): on the CPU every row's
    write copies the pool."""
    import run as harness

    from demodel_tpu.utils import trace

    doc = _axk1()
    doc["benchmark"]["engine"].update(kv_mb=8, max_batch=8, block_tokens=2,
                                      max_new_tokens=64)
    traffic = json.loads(
        (BENCH / "traffic" / "reason-deep-c64.json").read_text())
    for group, callers in zip(traffic["groups"], (6, 2)):
        group["callers"] = callers
        for row in group["cycle"]:
            row["prompt"] //= 32
            row["output"] //= 128
    bench = tmp_path / "benchmark"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    (bench / "configs" / "ax-k1-519b-l7-ep16.json").write_text(
        json.dumps(doc))
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
