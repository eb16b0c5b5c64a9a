"""The Qwen3-Next family (``lib/families/qwen3_next.py``) through the
seam's cases: the generator's bytes pinned at two seeds, the table holding
the share it is told, the costs pinned at the published shapes of
``qwen3-next-80b-l12-ep4``, its own reader on a made-up window, the int8
control standing out from the bfloat16 mode where top-k choices differ, and
a rehearsed run of ``qwen3next-doc`` to its result line.

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

TOY = {"model_type": "qwen3_next", "hidden_size": 128,
       "num_hidden_layers": 4, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 32,
       "partial_rotary_factor": 0.25, "rope_theta": 10000000,
       "full_attention_interval": 4, "linear_num_key_heads": 2,
       "linear_num_value_heads": 4, "linear_key_head_dim": 32,
       "linear_value_head_dim": 32, "linear_conv_kernel_dim": 4,
       "moe_intermediate_size": 64, "shared_expert_intermediate_size": 64,
       "num_experts": 4, "num_experts_per_tok": 4, "norm_topk_prob": True,
       "ep_size": 4, "ep_rank": 2, "rms_norm_eps": 1e-6,
       "vocab_size": 1024, "torch_dtype": "bfloat16"}
#: sha256 of TOY's shards, 3 of them, as this family's table first made them
PINNED = {
    2147483659: {
        "model-00001-of-00003.safetensors": "7b4efa18a8025849c771d60a2bf72820dea7e2292409dbf0d65d8ac0134bd097",
        "model-00002-of-00003.safetensors": "9f1ae84f9318e48437ec4f294be05a789a925969d302738a66e0ebe6d81371bb",
        "model-00003-of-00003.safetensors": "389ceef685306a4795013f57f2466986e1ff0d2d9e1bd252f9e55da71f1e8df9",
    },
    7: {
        "model-00001-of-00003.safetensors": "ddf187356ea995e1e83a81892efa30ebbc9c29a5bec410d68fd02860f64b4ac9",
        "model-00002-of-00003.safetensors": "add3844cbbebb19261d693397e9ab322496f080c5d58fcdd99323169ce7274fd",
        "model-00003-of-00003.safetensors": "10cb643e849b3e5553f3ebb89783c8257695da3de5244e131ca311d000edf938",
    },
}
INDEX = "5cad5e440d0d309435dd64ea95b1a41b03bd2aee7a98621c577473170fe613af"


def _published() -> dict:
    return json.loads(
        (BENCH / "configs" / "qwen3-next-80b-l12-ep4.json").read_text())


@pytest.mark.parametrize("seed, name", [
    (seed, name) for seed, files in PINNED.items() for name in files])
def test_qwen3_next_files_are_the_bytes_they_were(seed, name):
    from lib import checkpoint

    ckpt = checkpoint.Checkpoint(TOY, seed, n_shards=3)
    assert ckpt.files[name].sha256() == PINNED[seed][name]
    assert hashlib.sha256(
        ckpt.files["model.safetensors.index.json"]).hexdigest() == INDEX


def test_qwen3_next_table_holds_the_share_it_is_told():
    from lib import families

    table = families.of(TOY).tensors(TOY)
    held = sorted({int(n.split(".experts.")[1].split(".")[0])
                   for n in table if ".experts." in n})
    assert held == [8, 9, 10, 11]                   # ep_rank 2 of 4
    assert table["model.layers.1.mlp.gate.weight"].shape == (16, 128)
    assert table["model.embed_tokens.weight"].fan_in == 1
    # three Gated-DeltaNet layers, then the full-attention one
    assert table["model.layers.0.linear_attn.in_proj_qkvz.weight"] \
        == ((2 * 2 * 32 + 2 * 4 * 32, 128), "normal", 128)
    assert table["model.layers.2.linear_attn.conv1d.weight"] \
        == ((2 * 2 * 32 + 4 * 32, 1, 4), "normal", 4)
    assert table["model.layers.2.linear_attn.A_log"] == ((4,), "zeros", 0)
    assert table["model.layers.2.linear_attn.dt_bias"] == ((4,), "ones", 0)
    assert table["model.layers.2.linear_attn.norm.weight"] \
        == ((32,), "ones", 0)
    assert "model.layers.3.linear_attn.A_log" not in table
    assert table["model.layers.3.self_attn.q_proj.weight"] \
        == ((2 * 4 * 32, 128), "normal", 128)
    assert table["model.layers.3.self_attn.q_norm.weight"] \
        == ((32,), "zeros", 0)
    assert table["model.layers.0.input_layernorm.weight"] \
        == ((128,), "zeros", 0)
    assert table["model.layers.2.mlp.experts.9.down_proj.weight"] \
        == ((128, 64), "normal", 64)
    assert table["model.layers.2.mlp.shared_expert_gate.weight"] \
        == ((1, 128), "normal", 128)


def test_qwen3_next_costs_at_the_published_shapes():
    from lib import families

    doc = _published()
    fam = families.of(doc)
    assert fam.parameters(doc) == 5_423_030_272
    assert fam.unrouted_weights(doc) == 435_609_600
    assert fam.gdn_weights(doc) == 33_718_272
    assert fam.attention_weights(doc) == 27_262_976
    assert fam.expert_weights(doc) == 3_145_728
    # a slot: nine layers' float32 states and bfloat16 convolution tails
    assert fam.slot_bytes(doc) == 9 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert fam.slot_bytes(doc) == 19_316_736
    assert fam.scan_flops(doc) == pytest.approx(5_330_261.33, abs=0.01)
    assert [fam.prefill_flops(doc, t) for t in (1024, 2048, 3840)] == [
        1160476229632.0, 2372336484352.0, 4617109110784.0]
    # no experts_hit on the span: no expert is counted
    fixed = (435_609_600 + 37_984 * 2048) * 2
    assert fam.decode_bytes(doc, [{}], []) == fixed
    # a decoded token: its slot read and written, 6144 B a cached position
    assert fam.decode_bytes(doc, [], [1000]) == 2 * 19_316_736 + 1000 * 6144
    assert fam.decode_bytes(doc, [{"experts_hit": 415}] * 100,
                            [2700] * 1600) \
        == 100 * (fixed + 415 * 3_145_728 * 2) \
        + 1600 * (2 * 19_316_736 + 2700 * 6144)


def test_qwen3_next_reader_on_a_made_up_window():
    from lib import families, readers

    doc = _published()
    fam = families.of(doc)
    obs = readers.Observed(t0=0.0, t1=10.0, model=doc, chips=1)
    assert fam.state_share(obs, "serve.decode-step", "state_bytes") is None
    slot = fam.slot_bytes(doc)

    class Rec:
        prompt = [0] * 2000
        times = [0.5, 1.01, 2.01]     # the first token is a prefill's

    obs.records = [Rec()]
    obs.spans = [
        {"name": "serve.decode-step", "ts": 1.0, "dur": 0.02,
         "attrs": {"batch": 1, "experts_hit": 100, "state_bytes": 2 * slot}},
        {"name": "serve.decode-step", "ts": 2.0, "dur": 0.02,
         "attrs": {"batch": 1, "experts_hit": 90, "state_bytes": 2 * slot}},
        {"name": "serve.decode-step", "ts": 11.0, "dur": 0.02,
         "attrs": {"batch": 1, "state_bytes": 2 * slot}}]   # past the window
    need = fam.decode_bytes(doc, [{"experts_hit": 100}, {"experts_hit": 90}],
                            [2000, 2001])
    assert fam.state_share(obs, "serve.decode-step", "state_bytes") \
        == pytest.approx(100 * 4 * slot / need)
    # a program that names no state (the parent's): nothing to read
    for s in obs.spans:
        del s["attrs"]["state_bytes"]
    assert fam.state_share(obs, "serve.decode-step", "state_bytes") is None


@pytest.mark.parametrize("seed", [21, 2**31 + 22])
def test_qwen3_next_control_int8_stands_out_from_bfloat16(seed, capsys):
    """As ``test_correct.py`` holds for the Llama family: under the float32
    reference, what the int8 mode puts first lies further below the best
    than what the bfloat16 mode does, though both choose other experts
    than float32 here and there (the count is printed)."""
    import jax.numpy as jnp

    from lib import checkpoint, reference

    ckpt = checkpoint.Checkpoint(TOY, seed, n_shards=2)
    rng = np.random.default_rng([seed, 1])
    seqs = [[int(t) for t in rng.integers(0, TOY["vocab_size"], 96)]
            for _ in range(4)]
    wanted = [range(32, 96)] * 4
    ref = reference.logits(ckpt, seqs, wanted)
    assert "top-4 choices differ between bfloat16 and float32" \
        in capsys.readouterr().out

    def gaps(mode):
        low = reference.logits(ckpt, seqs, wanted, mode=mode)
        return np.concatenate([reference.gaps_below_best(
            r, np.asarray(jnp.argmax(lo, axis=1))[:64])
            for r, lo in zip(ref, low)])

    sound, control = gaps("bfloat16"), gaps("int8")
    assert control.mean() > 2 * sound.mean(), (sound.mean(), control.mean())
    assert (control > 0).sum() > (sound > 0).sum()


def test_qwen3next_doc_rehearsed_to_its_result_line(tmp_path, monkeypatch):
    """``run.py --workload qwen3next-doc --rehearse`` with the cell's own
    metric files and family, at the rehearsal's toy sizes. In a copy of the
    configuration and the traffic the pool is cut to 8 MiB, the batch to 4
    sessions, prompts to a sixteenth and replies to an eighth: on the CPU
    every row's write copies the pool (on the chip it is in place)."""
    import run as harness

    from demodel_tpu.utils import trace

    doc = _published()
    doc["benchmark"]["engine"].update(kv_mb=8, max_batch=4)
    traffic = json.loads((BENCH / "traffic" / "doc-c16.json").read_text())
    for group, callers in zip(traffic["groups"], (1, 1, 2)):
        group["callers"] = callers
        for row in group["cycle"]:
            row["prompt"] //= 16
            row["output"] //= 8
    bench = tmp_path / "benchmark"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    (bench / "configs" / "qwen3-next-80b-l12-ep4.json").write_text(
        json.dumps(doc))
    (bench / "traffic" / "doc-c16.json").write_text(json.dumps(traffic))
    for shared in ("cells", "peaks.json", "spans", "layer_metrics",
                   "end_to_end"):
        (bench / shared).symlink_to(BENCH / shared)
    (tmp_path / "BENCHMARK.json").write_text(
        (BENCH.parent / "BENCHMARK.json").read_text())
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "HERE", bench)
    try:
        code, result, reasons = harness.run(harness.parse(
            ["--workload", "qwen3next-doc", "--seed", "2147484001",
             "--seconds", "4", "--trace", "1", "--rehearse"]))
    finally:
        trace.reset()
    assert code == 0 and result["failed"] == 0 and result["attempted"] >= 4
    assert [r for r in reasons if not r.startswith("served_gap_")] \
        == ["a rehearsal is never a result"], reasons
    assert set(result["compared"]) == {"served_gap_max", "served_gap_mean"}
