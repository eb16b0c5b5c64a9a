"""The EXAONE-MoE family (``lib/families/exaone_moe.py``) through the
seam's cases: the generator's bytes pinned at two seeds, the costs pinned
at the published shapes of ``k-exaone-236b-l8-ep8``, its own readers on a
made-up window, the int8 control standing out from the bfloat16 mode where
top-k choices differ, and a rehearsed run of ``kexaone-reason`` to its
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

TOY = {"model_type": "exaone_moe", "hidden_size": 128,
       "intermediate_size": 256, "moe_intermediate_size": 64,
       "num_hidden_layers": 4, "num_attention_heads": 8,
       "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 1024,
       "num_experts": 4, "num_experts_per_tok": 4, "num_shared_experts": 1,
       "ep_size": 4, "ep_rank": 2, "norm_topk_prob": True,
       "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5,
       "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
       "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
       "sliding_windows": [16, 16, 16, 0],
       "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
       "torch_dtype": "bfloat16"}
#: sha256 of TOY's shards, 3 of them, as this family's table first made them
PINNED = {
    2147483659: {
        "model-00001-of-00003.safetensors":
            "f2b5ed2b855a9b6886de083eb77ea97914a5dbae6a0cfef3c5e76b81cfdf402c",
        "model-00002-of-00003.safetensors":
            "16ecd4af345363a9b05706523647ba077a30ad309630e42b963da24a41ef8059",
        "model-00003-of-00003.safetensors":
            "f697f6499410126d58c2e1488a457e7b4938c3df758150977079a5a357c52949",
    },
    7: {
        "model-00001-of-00003.safetensors":
            "be71921d9a515f8bcbae2e8eb1893c5f271549a8b42ade8f9b14525fd44b03a6",
        "model-00002-of-00003.safetensors":
            "ae73934b5e5d89612a831abddcf4e0c60ddcebd0c46e50beae828f767a64df10",
        "model-00003-of-00003.safetensors":
            "58151c4044e0faaea85c003298324ca3b0ac838eb9914575c6c37477f0392eea",
    },
}
INDEX = "5fb0a229bac9964510ad4cb57080970cbeeb0326908dad2656f696c839f8f581"


def _kexaone() -> dict:
    return json.loads(
        (BENCH / "configs" / "k-exaone-236b-l8-ep8.json").read_text())


@pytest.mark.parametrize("seed, name", [
    (seed, name) for seed, files in PINNED.items() for name in files])
def test_exaone_files_are_the_bytes_they_were(seed, name):
    from lib import checkpoint

    ckpt = checkpoint.Checkpoint(TOY, seed, n_shards=3)
    assert ckpt.files[name].sha256() == PINNED[seed][name]
    assert hashlib.sha256(
        ckpt.files["model.safetensors.index.json"]).hexdigest() == INDEX


def test_exaone_table_holds_the_share_it_is_told():
    from lib import families

    table = families.of(TOY).tensors(TOY)
    held = sorted({int(n.split(".experts.")[1].split(".")[0])
                   for n in table if ".experts." in n})
    assert held == [8, 9, 10, 11]                   # ep_rank 2 of 4
    assert table["model.layers.1.mlp.gate.weight"].shape == (16, 128)
    assert table["model.layers.1.mlp.gate.e_score_correction_bias"] \
        == ((16,), "zeros", 0)
    assert table["model.embed_tokens.weight"].fan_in == 1
    assert "model.layers.0.mlp.gate.weight" not in table   # the dense one
    assert table["model.layers.2.mlp.experts.9.down_proj.weight"] \
        == ((128, 64), "normal", 64)


def test_exaone_costs_at_the_published_shapes():
    from lib import families

    doc = _kexaone()
    fam = families.of(doc)
    assert fam.parameters(doc) == 5_979_242_496
    assert fam.unrouted_weights(doc) == 1_515_454_464
    assert fam.expert_weights(doc) == 37_748_736
    assert [fam.prefill_flops(doc, t) for t in (64, 128, 256)] == [
        228582227968.0, 458002268160.0, 918440378368.0]
    # no experts_hit on the span: no expert is counted
    assert fam.decode_bytes(doc, [{}], [300] * 32) == 3445358592.0
    assert fam.decode_bytes(doc, [{"experts_hit": 98}] * 2000,
                            [100, 127, 128, 511]) == 21331200483328.0
    # a window layer reads window - 1 cached positions, a full one all
    one = fam.decode_bytes(doc, [], [1000]) / (2 * 8 * 128 * 2)
    assert one == 6 * 127 + 2 * 1000


def test_exaone_readers_on_a_made_up_window():
    from lib import families, readers, xplane

    doc = _kexaone()
    fam = families.of(doc)
    obs = readers.Observed(t0=0.0, t1=10.0, model=doc, chips=1,
                           peaks={"hbm_bytes_per_s": 819e9})
    assert fam.tokens_per_expert_hit(obs, "serve.decode-step") is None
    assert fam.expert_bytes_roofline(obs, "serve.decode-step",
                                     "^ragged-dot") is None
    obs.spans = [
        {"name": "serve.decode-step", "ts": 1.0, "dur": 0.02,
         "attrs": {"batch": 32, "expert_tokens": 230, "experts_hit": 100}},
        {"name": "serve.decode-step", "ts": 2.0, "dur": 0.02,
         "attrs": {"batch": 32, "expert_tokens": 250, "experts_hit": 92}},
        {"name": "serve.decode-step", "ts": 3.0, "dur": 0.02,
         "attrs": {"batch": 32}}]           # a program without the counts
    assert fam.tokens_per_expert_hit(obs, "serve.decode-step") == 480 / 192
    obs.trace = xplane.Trace({"/device:TPU:0": xplane.Ops.of([
        ("ragged-dot-none", 1.001, 0.008), ("fusion", 1.010, 0.004),
        ("ragged-dot-none", 2.001, 0.010), ("ragged-dot-none", 3.001, 0.5),
        ("ragged-dot-none", 5.0, 0.5)])})
    least = 192 * 37_748_736 * 2 / 819e9
    assert fam.expert_bytes_roofline(
        obs, "serve.decode-step", "^ragged-dot") == pytest.approx(
        100 * least / 0.018)
    assert fam.expert_bytes_roofline(obs, "serve.decode-step",
                                     "^nothing") is None


@pytest.mark.parametrize("seed", [21, 2**31 + 22])
def test_exaone_control_int8_stands_out_from_bfloat16(seed, capsys):
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


def test_kexaone_reason_rehearsed_to_its_result_line(tmp_path, monkeypatch):
    """``run.py --workload kexaone-reason --rehearse`` with the cell's own
    traffic, metric files and family, at the rehearsal's toy sizes. The
    pool is cut to 8 MiB and the batch to 8 in a copy of the
    configuration: on the CPU every row's write copies the pool, and a
    step of 32 rows over 1 GiB takes ten seconds (on the chip it is in
    place)."""
    import run as harness

    from demodel_tpu.utils import trace

    doc = _kexaone()
    doc["benchmark"]["engine"].update(kv_mb=8, max_batch=8)
    traffic = json.loads((BENCH / "traffic" / "reason-c32.json").read_text())
    for group, callers in zip(traffic["groups"], (2, 4, 2)):
        group["callers"] = callers
        for row in group["cycle"]:
            row["output"] //= 16
    bench = tmp_path / "benchmark"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    (bench / "configs" / "k-exaone-236b-l8-ep8.json").write_text(
        json.dumps(doc))
    (bench / "traffic" / "reason-c32.json").write_text(json.dumps(traffic))
    for shared in ("cells", "peaks.json", "spans", "layer_metrics",
                   "end_to_end"):
        (bench / shared).symlink_to(BENCH / shared)
    (tmp_path / "BENCHMARK.json").write_text(
        (BENCH.parent / "BENCHMARK.json").read_text())
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "HERE", bench)
    try:
        code, result, reasons = harness.run(harness.parse(
            ["--workload", "kexaone-reason", "--seed", "2147484001",
             "--seconds", "4", "--trace", "1", "--rehearse"]))
    finally:
        trace.reset()
    assert code == 0 and result["failed"] == 0 and result["attempted"] >= 8
    assert [r for r in reasons if not r.startswith("served_gap_")] \
        == ["a rehearsal is never a result"], reasons
    assert set(result["compared"]) == {"served_gap_max", "served_gap_mean"}
