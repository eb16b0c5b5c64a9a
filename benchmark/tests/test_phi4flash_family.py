"""The Phi-4-mini-flash family (``lib/families/phi4flash.py``) through the
seam's cases: the generator's bytes pinned at two seeds, the tensor table,
the parameter count and the costs at the published shapes of
``phi-4-mini-flash`` against hand counts, its reader on a made-up window,
the int8 control standing out from the bfloat16 mode, and a rehearsed run of
``phi4flash-reason`` to its result line.

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

TOY = {"model_type": "phi4flash", "hidden_size": 128,
       "intermediate_size": 192, "num_hidden_layers": 8,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "sliding_window": 16, "layer_norm_eps": 1e-5, "mb_per_layer": 2,
       "mamba_d_state": 8, "hidden_act": "silu",
       "tie_word_embeddings": True, "vocab_size": 1024,
       "torch_dtype": "bfloat16"}
#: sha256 of TOY's shards, 3 of them, as this family's table first made them
PINNED = {
    2147483659: {
        "model-00001-of-00003.safetensors":
            "8253f19efd0a835fa9aa604bca77acda573796deffd1e6bc724987039b855f86",
        "model-00002-of-00003.safetensors":
            "eb1a3f721e381971dd3381227da226442327c52a5036c9e8f98c0464a2fa0f76",
        "model-00003-of-00003.safetensors":
            "44b1fcc7219fee8258725057a2dc6687727b5140a3304f0f2cad735c6643f98a",
    },
    7: {
        "model-00001-of-00003.safetensors":
            "d6fffaf4decd4db65ae1a795ea69c148679c2ed6357ed09cd12fe33d77f1baba",
        "model-00002-of-00003.safetensors":
            "bf3fa6730005163b8849823cf3947a3b386bacba3c9c5b2c44404a8ae0dbc8ed",
        "model-00003-of-00003.safetensors":
            "97ace6500d75be9c0b0a0e42fa8568de73a1d3ce86f9230d554e21c298226bfb",
    },
}
INDEX = "3b08629ddddbf8a6138046010d62199a64565529fc78b411b14f4d0f0d562659"


def _published() -> dict:
    return json.loads(
        (BENCH / "configs" / "phi-4-mini-flash.json").read_text())


@pytest.mark.parametrize("seed, name", [
    (seed, name) for seed, files in PINNED.items() for name in files])
def test_phi4flash_files_are_the_bytes_they_were(seed, name):
    from lib import checkpoint

    ckpt = checkpoint.Checkpoint(TOY, seed, n_shards=3)
    assert ckpt.files[name].sha256() == PINNED[seed][name]
    assert hashlib.sha256(
        ckpt.files["model.safetensors.index.json"]).hexdigest() == INDEX


def test_phi4flash_table_holds_every_kind_of_layer():
    from lib import families

    table = families.of(TOY).tensors(TOY)
    names = list(table)
    assert names[0] == "model.embed_tokens.weight"
    assert table[names[0]] == ((1024, 128), "normal", 128)
    assert "lm_head.weight" not in table            # the head is the embedding
    a = "model.layers.{}.attn."
    # 0, 2, 4 Mamba; 1, 3 window; 5 full; 6 a memory unit; 7 cross
    for i in (0, 2, 4):
        assert table[a.format(i) + "in_proj.weight"] \
            == ((2 * 256, 128), "normal", 128)
        assert table[a.format(i) + "conv1d.weight"] \
            == ((256, 1, 4), "normal", 4)
        assert table[a.format(i) + "conv1d.bias"] == ((256,), "zeros", 0)
        assert table[a.format(i) + "x_proj.weight"] \
            == ((8 + 2 * 8, 256), "normal", 256)      # dt_rank ceil(128 / 16)
        assert table[a.format(i) + "dt_proj.weight"] \
            == ((256, 8), "normal", 8)
        assert table[a.format(i) + "dt_proj.bias"] == ((256,), "zeros", 0)
        assert table[a.format(i) + "A_log"] == ((256, 8), "zeros", 0)
        assert table[a.format(i) + "D"] == ((256,), "ones", 0)
        assert table[a.format(i) + "out_proj.weight"] \
            == ((128, 256), "normal", 256)
    for i in (1, 3, 5):
        assert table[a.format(i) + "Wqkv.weight"] \
            == (((4 + 2 * 2) * 32, 128), "normal", 128)
        assert table[a.format(i) + "Wqkv.bias"] == ((256,), "zeros", 0)
        assert table[a.format(i) + "out_proj.bias"] == ((128,), "zeros", 0)
        assert table[a.format(i) + "inner_cross_attn.lambda_q1"] \
            == ((32,), "normal", 100)
        assert table[a.format(i) + "inner_cross_attn.subln.weight"] \
            == ((64,), "ones", 0)
    assert table[a.format(6) + "in_proj.weight"] == ((256, 128), "normal", 128)
    assert table[a.format(6) + "out_proj.weight"] \
        == ((128, 256), "normal", 256)
    assert a.format(6) + "conv1d.weight" not in table
    assert table[a.format(7) + "Wqkv.weight"] == ((128, 128), "normal", 128)
    assert table[a.format(7) + "inner_cross_attn.lambda_k2"] \
        == ((32,), "normal", 100)
    for i in range(8):
        p = f"model.layers.{i}."
        assert table[p + "input_layernorm.weight"] == ((128,), "ones", 0)
        assert table[p + "post_attention_layernorm.bias"] \
            == ((128,), "zeros", 0)
        assert table[p + "mlp.fc1.weight"] == ((2 * 192, 128), "normal", 128)
        assert table[p + "mlp.fc2.weight"] == ((128, 192), "normal", 192)
    assert names[-2:] == ["model.final_layernorm.weight",
                          "model.final_layernorm.bias"]


def test_phi4flash_costs_at_the_published_shapes():
    """ISSUE 35's hand counts: the layers' parameters, a position's bytes,
    a slot's, a step's and a prefill's."""
    from lib import families

    doc = _published()
    assert doc["benchmark"]["reduced"] == []
    fam = families.of(doc)
    mlp_and_norms = 2560 * 20480 + 10240 * 2560 + 4 * 2560
    mamba = 2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120 \
        + 5120 * 16 + 5120 + 5120 * 2560
    attention = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    cross = 2560 * 2560 + 2560 + 2560 * 2560 + 2560 + 4 * 64 + 128
    gmu = 2 * 2560 * 5120
    assert (mlp_and_norms, mamba, attention, gmu, cross) == (
        78_653_440, 41_241_600, 19_668_864, 26_214_400, 13_112_704)
    assert fam.parameters(doc) == 32 * mlp_and_norms + 9 * mamba \
        + 9 * attention + 7 * gmu + 7 * cross + 200064 * 2560 + 2 * 2560 \
        == 3_852_562_944
    assert fam.page_bytes(doc) == 5120 and fam.readers(doc) == 8
    assert fam.ring_bytes(doc) == 8 * 5120
    assert fam.ssm_bytes(doc) == 9 * 5120 * (16 * 4 + 3 * 2) == 3_225_600
    assert fam.slot_bytes(doc) == 512 * 8 * 5120 + 3_225_600 == 24_197_120
    assert fam.scan_flops(doc) == 6.0 * 5120 * 16
    # a step: every weight once; a token: its pages eight times, the rings'
    # window and the position written, its Mamba slot read and written
    weights = 2 * 3_852_562_944
    assert fam.decode_bytes(doc, [{}], []) == weights
    assert fam.decode_bytes(doc, [], [100]) \
        == 100 * 5120 * 8 + 101 * 40960 + 2 * 3_225_600
    assert fam.decode_bytes(doc, [], [2180]) \
        == 2180 * 5120 * 8 + 513 * 40960 + 2 * 3_225_600
    assert fam.decode_bytes(doc, [{}] * 10, [2180] * 320) \
        == 10 * weights + 320 * (2180 * 40960 + 513 * 40960 + 6_451_200)
    # a prefill: layers 0-16 and layer 17's keys and values over the
    # prompt, the band of 8 window layers, the scans; one position from
    # layer 17's query on, its attention over the prompt in 8 layers, the
    # head once
    matmul = {"mamba": 2560 * 10240 + 4 * 5120 + 5120 * 192 + 160 * 5120
              + 5120 * 2560,
              "window": 2560 * 5120 + 2560 * 2560, "gmu": gmu,
              "cross": 2 * 2560 * 2560}
    mlp = 3 * 2560 * 10240
    over_prompt = 9 * (matmul["mamba"] + mlp) + 8 * (matmul["window"] + mlp) \
        + 2560 * 2560
    once = 2 * 2560 * 2560 + mlp + 7 * (gmu + mlp) \
        + 7 * (matmul["cross"] + mlp) + 200064 * 2560
    for T in (1024, 3072):
        band = 512 * 513 // 2 + (T - 512) * 512
        want = 2.0 * T * over_prompt + 2.0 * once + T * 9 * 6.0 * 5120 * 16 \
            + 6.0 * 64 * 40 * (8 * band + 8 * T)
        assert fam.prefill_flops(doc, T) == want
    assert fam.prefill_flops(doc, 100) == 2.0 * 100 * over_prompt \
        + 2.0 * once + 100 * 9 * 6.0 * 5120 * 16 \
        + 6.0 * 64 * 40 * (8 * 100 * 101 // 2 + 8 * 100)


def test_phi4flash_engine_settings_hold_the_worst_case():
    """The pool pays 32 slots and twice 32 sequences of 4 096 positions at
    5 120 B; the cell is in the table, one chip, with its lists."""
    from lib import families

    doc = _published()
    fam = families.of(doc)
    engine = doc["benchmark"]["engine"]
    need = 32 * fam.slot_bytes(doc) + 2 * 32 * 4096 * fam.page_bytes(doc)
    assert need <= engine["kv_mb"] << 20 < need + (64 << 20)
    assert engine["max_batch"] == 32 and engine["max_new_tokens"] == 2560
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == "phi4flash-reason")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("phi-4-mini-flash", "reason-long-c32", 1)
    assert all(len(x["why"]) <= 200
               for x in bench["workloads"] + bench["configs"])
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if "phi4flash-reason"
              in m.get("workloads", [])}
    assert listed == {"itl_p50_ms", "batch_occupancy", "prefill_stall_p99_ms",
                      "prefill_mfu", "shared_kv_hbm_share",
                      "window_state_hbm_share"}
    traffic = json.loads(
        (BENCH / "traffic" / "reason-long-c32.json").read_text())
    assert sum(g["callers"] for g in traffic["groups"]) == 32
    assert max(row["prompt"] + row["output"] for g in traffic["groups"]
               for row in g["cycle"]) == 3840


def test_phi4flash_reader_on_a_made_up_window():
    from lib import families, readers

    doc = _published()
    fam = families.of(doc)
    obs = readers.Observed(t0=0.0, t1=10.0, model=doc, chips=1)
    assert fam.span_share(obs, "serve.decode-step", "state_bytes") is None

    class Rec:
        prompt = [0] * 2000
        times = [0.5, 1.01, 2.01]     # the first token is a prefill's

    obs.records = [Rec()]
    state = 513 * 40960 + 2 * 3_225_600
    obs.spans = [
        {"name": "serve.decode-step", "ts": 1.0, "dur": 0.02,
         "attrs": {"batch": 1, "state_bytes": state,
                   "shared_kv_bytes": 2000 * 40960}},
        {"name": "serve.decode-step", "ts": 2.0, "dur": 0.02,
         "attrs": {"batch": 1, "state_bytes": state,
                   "shared_kv_bytes": 2001 * 40960}},
        {"name": "serve.decode-step", "ts": 11.0, "dur": 0.02,
         "attrs": {"batch": 1, "state_bytes": state}}]    # past the window
    need = fam.decode_bytes(doc, [{}, {}], [2000, 2001])
    assert fam.span_share(obs, "serve.decode-step", "state_bytes") \
        == pytest.approx(100 * 2 * state / need)
    assert fam.span_share(obs, "serve.decode-step", "shared_kv_bytes") \
        == pytest.approx(100 * 4001 * 40960 / need)
    both = fam.span_share(obs, "serve.decode-step", "state_bytes") \
        + fam.span_share(obs, "serve.decode-step", "shared_kv_bytes")
    assert both == pytest.approx(100 * (need - 2 * 2 * 3_852_562_944) / need)
    # a program that names no such bytes (the parent's): nothing to read
    for s in obs.spans:
        s["attrs"] = {"batch": 1}
    assert fam.span_share(obs, "serve.decode-step", "state_bytes") is None
    assert fam.span_share(obs, "serve.decode-step", "shared_kv_bytes") is None
    for name in ("shared_kv_hbm_share", "window_state_hbm_share"):
        spec = json.loads(
            (BENCH / "layer_metrics" / f"{name}.json").read_text())
        assert readers.read(obs, spec) is None


@pytest.mark.parametrize("seed", [21, 2**31 + 22])
def test_phi4flash_control_int8_stands_out_from_bfloat16(seed):
    """As ``test_correct.py`` holds for the Llama family: under the float32
    reference, what the int8 mode puts first lies further below the best
    than what the bfloat16 mode does."""
    import jax.numpy as jnp

    from lib import checkpoint, reference

    ckpt = checkpoint.Checkpoint(TOY, seed, n_shards=2)
    rng = np.random.default_rng([seed, 1])
    seqs = [[int(t) for t in rng.integers(0, TOY["vocab_size"], 96)]
            for _ in range(4)]
    wanted = [range(32, 96)] * 4
    ref = reference.logits(ckpt, seqs, wanted)

    def gaps(mode):
        low = reference.logits(ckpt, seqs, wanted, mode=mode)
        return np.concatenate([reference.gaps_below_best(
            r, np.asarray(jnp.argmax(lo, axis=1))[:64])
            for r, lo in zip(ref, low)])

    sound, control = gaps("bfloat16"), gaps("int8")
    assert control.mean() > 2 * sound.mean(), (sound.mean(), control.mean())
    assert (control > 0).sum() > (sound > 0).sum()


def test_phi4flash_reason_rehearsed_to_its_result_line(tmp_path, monkeypatch):
    """``run.py --workload phi4flash-reason --rehearse`` with the cell's own
    metric files and family, at the rehearsal's toy sizes. In a copy of the
    configuration and the traffic the pool is cut to 8 MiB, the batch to 4
    sessions, prompts to a sixteenth and replies to a sixty-fourth: on the
    CPU every row's write copies the pool (on the chip it is in place)."""
    import run as harness

    from demodel_tpu.utils import trace

    doc = _published()
    doc["benchmark"]["engine"].update(kv_mb=8, max_batch=4,
                                      max_new_tokens=64)
    traffic = json.loads(
        (BENCH / "traffic" / "reason-long-c32.json").read_text())
    for group, callers in zip(traffic["groups"], (3, 1)):
        group["callers"] = callers
        for row in group["cycle"]:
            row["prompt"] //= 16
            row["output"] //= 64
    bench = tmp_path / "benchmark"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    (bench / "configs" / "phi-4-mini-flash.json").write_text(json.dumps(doc))
    (bench / "traffic" / "reason-long-c32.json").write_text(
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
            ["--workload", "phi4flash-reason", "--seed", "2147484001",
             "--seconds", "4", "--trace", "1", "--rehearse"]))
    finally:
        trace.reset()
    assert code == 0 and result["failed"] == 0 and result["attempted"] >= 4
    assert [r for r in reasons if not r.startswith("served_gap_")] \
        == ["a rehearsal is never a result"], reasons
    assert set(result["compared"]) == {"served_gap_max", "served_gap_mean"}
