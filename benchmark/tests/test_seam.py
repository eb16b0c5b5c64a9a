"""The seam behind which everything that knows an architecture sits.

Run by hand (``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q``);
these cases take seconds.

``test_llama_*``: the Llama family's generator and costs give what the
tree before the seam gave (PR 26's): the sha256 of every virtual file of a
small configuration at a fixed seed, and the operations and bytes at
``yi-1.5-6b``'s shapes, pinned from that tree. (Its reference's logits were
held bit for bit against that tree's on the CPU once, in all three modes:
PERF.md, Findings, PR 27.)

``test_every_*``: each data file under ``end_to_end/`` and
``layer_metrics/`` names a reader that resolves, and each metric of
``BENCHMARK.json`` has its file.

``test_fixture_*``: ``fixture_family/toyfam.py``, a family that is no
model, goes through the generator, the hub and the reference, is given a
reader of its own by path, and through ``run.py`` itself as far as the
engine, which refuses its ``model_type``: none of ``run.py``,
``lib/checkpoint.py``, ``lib/reference.py`` and ``lib/readers.py`` knows it.
"""

from __future__ import annotations

import hashlib
import json
import sys
import urllib.request
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

TOY = {"model_type": "llama", "hidden_size": 256, "intermediate_size": 512,
       "num_hidden_layers": 4, "num_attention_heads": 8,
       "num_key_value_heads": 2, "vocab_size": 8192, "rms_norm_eps": 1e-6,
       "rope_theta": 5e6, "torch_dtype": "bfloat16"}
#: sha256 of TOY's files, seed 2147483659, 3 shards, on PR 26's tree
PINNED = {
    "config.json":
        "1fa512248a753b173ed31a641e066f68a1ca8d73168e4838916654af8df4d208",
    "model-00001-of-00003.safetensors":
        "14f357a3f19d970d199b48c417f2e278cda013e6c46e6568ac141ecb860a2820",
    "model-00002-of-00003.safetensors":
        "d773a1888821343c6d93e4dad365c31db935a06b609865ca9a24380b98bd70b5",
    "model-00003-of-00003.safetensors":
        "b28dac6a07602bec21540103d37be9d2de3c8ca2bfcaad837d173442d4d0a96f",
    "model.safetensors.index.json":
        "43e9aaedce4593d8ecdda97d0776b0a55243d8fe94476b8661f72c114c2294f0",
}


def _yi() -> dict:
    return json.loads((BENCH / "configs" / "yi-1.5-6b.json").read_text())


@pytest.mark.parametrize("name", sorted(PINNED))
def test_llama_files_are_the_bytes_they_were(name):
    from lib import checkpoint

    file = checkpoint.Checkpoint(TOY, 2147483659, n_shards=3).files[name]
    got = file.sha256() if isinstance(file, checkpoint.VirtualFile) \
        else hashlib.sha256(file).hexdigest()
    assert got == PINNED[name]


@pytest.mark.parametrize("tokens, flops", [
    (128, 1422192017408.0), (384, 4291297280000.0),
    (1024, 11614384291840.0), (2048, 23778000109568.0)])
def test_llama_prefill_flops_are_what_they_were(tokens, flops):
    from lib import families

    assert families.of(_yi()).prefill_flops(_yi(), tokens) == flops


def test_llama_decode_bytes_are_what_they_were():
    from lib import families

    llama = families.of(_yi())
    assert llama.decode_bytes(_yi(), [{}], [1000] * 8) == 12121538560.0
    assert llama.decode_bytes(_yi(), [{"batch": 8}] * 1503,
                              [3_456_000, 789]) == 17657211715584.0


def test_unknown_model_type_names_the_file_to_add():
    from lib import families

    with pytest.raises(ValueError, match=r"lib/families/exaone_moe\.py"):
        families.of({"model_type": "exaone-moe"})
    with pytest.raises(ValueError, match="no model_type"):
        families.of({"hidden_size": 8})


def _metric_files() -> list[Path]:
    return sorted((BENCH / "end_to_end").glob("*.json")) + sorted(
        (BENCH / "layer_metrics").glob("*.json"))


@pytest.mark.parametrize("path", _metric_files(), ids=lambda p: p.stem)
def test_every_metric_file_names_a_reader_that_resolves(path):
    import inspect

    from lib import readers

    spec = json.loads(path.read_text())
    fn = readers.resolve(spec["reader"])
    inspect.signature(fn).bind(readers.Observed(), **spec.get("args", {}))


def test_every_metric_of_the_table_has_its_file():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for kind, folder in (("end_to_end", "end_to_end"),
                         ("per_layer", "layer_metrics")):
        for m in bench[kind]:
            assert (BENCH / folder / f"{m['name']}.json").is_file(), m["name"]
    assert not {"kv_gather_p50_ms", "decode_release_p50_ms",
                "kv_pageout_p50_ms"} & {m["name"] for m in bench["per_layer"]}


def test_engine_spans_come_innermost_first():
    import run as harness

    order, programs = harness.engine_spans()
    assert programs == ["serve.decode-step", "serve.prefill"]
    for child, parent in (("serve.decode-device", "serve.decode-step"),
                          ("serve.decode-fetch", "serve.decode-step"),
                          ("serve.prefill-device", "serve.prefill"),
                          ("serve.decode-h2d", "serve.admit"),
                          ("serve.decode-post", "serve.admit"),
                          ("serve.prefill", "serve.admit"),
                          ("serve.admit", "serve.restore")):
        assert order.index(child) < order.index(parent)
    assert order[-1] == "serve.restore"


# ------------------------------------------------------ the fixture family


@pytest.fixture
def toyfam():
    """``model_type: toyfam`` found by the harness: its directory joins the
    path ``lib.families`` is searched on, as a file added there would."""
    from lib import families

    families.__path__.append(str(HERE / "fixture_family"))
    try:
        yield json.loads((HERE / "fixture_family" / "toyfam.json").read_text())
    finally:
        families.__path__.remove(str(HERE / "fixture_family"))
        sys.modules.pop("lib.families.toyfam", None)


def test_fixture_family_through_generator_hub_and_reference(toyfam):
    import jax.numpy as jnp

    from lib import checkpoint, hub, reference

    model = {k: v for k, v in toyfam.items() if k != "benchmark"}
    ckpt = checkpoint.Checkpoint(model, 2147483777, n_shards=2)
    scale = ckpt.tensor("mix.scale").astype(np.float32)
    assert scale.shape == (64,) and 0.2 < scale.std() < 0.9    # N(0, 1/4)
    assert not (scale == 1).any()
    assert not ckpt.tensor("mix.bias").astype(np.float32).any()
    assert (ckpt.tensor("norm.weight").astype(np.float32) == 1).all()
    embed = ckpt.tensor("embed.weight").astype(np.float32)
    assert abs(embed.std() - 1 / 8) < 0.01                     # N(0, 1/64)

    digests = ckpt.digests()
    with hub.serving("bench/toy", ckpt, digests) as endpoint:
        for name in ckpt.files:
            with urllib.request.urlopen(
                    f"{endpoint}/bench/toy/resolve/main/{name}") as reply:
                assert hashlib.sha256(reply.read()).hexdigest() \
                    == digests[name]

    seqs = [[3, 1, 4, 1, 5, 9, 2, 6], [200, 100, 50]]
    wanted = [range(4, 8), range(0, 3)]
    head = ckpt.tensor("head.weight").astype(np.float64)
    ref = reference.logits(ckpt, seqs, wanted)
    for lg, seq, want in zip(ref, seqs, wanted):
        x = embed[np.asarray(seq)][list(want)].astype(np.float64) * scale
        x = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
        assert lg.shape == (reference.ROWS, 256)
        np.testing.assert_allclose(np.asarray(lg)[:len(want)], x @ head.T,
                                   rtol=0, atol=1e-5)
    for mode, least in (("bfloat16", 1e-7), ("int8", 1e-5)):
        low = reference.logits(ckpt, seqs, wanted, mode=mode)
        apart = max(float(jnp.abs(a - b).max()) for a, b in zip(ref, low))
        assert least < apart < 0.2, (mode, apart)
    gaps = reference.gaps_below_best(ref[0], np.asarray(
        jnp.argmax(ref[0], axis=1))[:4])
    assert (gaps == 0).all()


def test_fixture_family_brings_its_costs_and_a_reader_by_path(toyfam):
    from lib import families, loadgen, readers

    fam = families.of(toyfam)
    assert fam.decode_bytes(toyfam, [{}, {}], [1, 9]) \
        == 2 * (2.0 * 256 * 64) + 2.0 * 64 * (1 + 4)
    assert readers.resolve("families.toyfam:requests_seen") \
        is fam.requests_seen
    obs = readers.Observed(model=toyfam)
    spec = {"reader": "families.toyfam:requests_seen", "args": {"scale": 2}}
    assert readers.read(obs, spec) is None        # nothing to read: no 0
    obs.records = [loadgen.Record(0, [1], 1)] * 3
    assert readers.read(obs, spec) == 6
    with pytest.raises(ValueError, match="no reader called 'nothing'"):
        readers.resolve("families.toyfam:nothing")
    with pytest.raises(ValueError, match="<module>:<function>"):
        readers.resolve("requests_seen")


def test_fixture_family_through_run_py_up_to_the_engine(toyfam, tmp_path,
                                                        monkeypatch):
    """A whole rehearsed run of a cell of the fixture's: ``run.py`` makes
    its weights, serves them, the program pulls and verifies them, and the
    engine refuses the ``model_type``. A family the engine served would go
    on from there."""
    import run as harness

    from demodel_tpu.utils import trace

    bench = tmp_path / "benchmark"
    (bench / "configs").mkdir(parents=True)
    (bench / "cells").mkdir()
    (bench / "configs" / "toyfam.json").write_text(json.dumps(toyfam))
    (bench / "cells" / "toy-cell.json").write_text(json.dumps(
        {"correct": {"sample_requests": 2, "limits": {
            "served_gap_max": 0.3, "served_gap_mean": 0.004}}}))
    for shared in ("traffic", "peaks.json", "spans"):
        (bench / shared).symlink_to(BENCH / shared)
    table = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    table["configs"] = [{"name": "toyfam", "source": "fixture",
                         "file": "benchmark/configs/toyfam.json",
                         "reduced": [], "why": "the seam"}]
    table["workloads"] = [{"name": "toy-cell", "config": "toyfam",
                           "traffic": "chat-c8", "chips": 1, "why": "seam"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(table))
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "HERE", bench)
    try:
        with pytest.raises(ValueError,
                           match="unsupported model_type 'toyfam'"):
            harness.run(harness.parse(
                ["--workload", "toy-cell", "--seed", "2147483778",
                 "--seconds", "1", "--trace", "0", "--rehearse"]))
    finally:
        trace.reset()
