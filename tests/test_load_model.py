"""``serve.load_model`` end to end — ``chip_smoke.py``'s own function at a
tiny config on the CPU: a two-shard checkpoint pulled from the fake hub,
placed, served over ``/generate`` and checked against the float32
reference. In bf16 on one device (the dtype path), and in float32 on four
of conftest's eight against one (the mesh path: float32 because a bf16
near-tie flips a greedy token between two reduction orders without
anything being wrong; bf16 over four devices is the four-chip run)."""

from __future__ import annotations

import pytest

pytest.importorskip("cryptography")  # ProxyConfig → pki

import chip_smoke  # noqa: E402
from demodel_tpu.parallel import make_mesh  # noqa: E402

TINY = dict(chip_smoke.TINYLLAMA, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=8,
            num_key_value_heads=4, vocab_size=256)


def _run(tmp_path, mesh, **over):
    return chip_smoke.boot_and_serve(dict(TINY, **over), tmp_path, mesh=mesh,
                                     n_shards=2, kv_mb=4)


def test_load_model_bf16_serves_generate(tmp_path):
    info = _run(tmp_path, make_mesh(1))
    assert len(info["tokens"]) == 2 + 3 + 2
    # 4 prompts of 17 and 3 of 24; each request's first token comes from
    # its prefill, the other 7 from decode steps
    assert info["engine_tokens"] == {"prefill": 4 * 17 + 3 * 24,
                                     "decode": 7 * 7}


def test_load_model_shards_over_four_devices(tmp_path):
    one = _run(tmp_path / "one", make_mesh(1), torch_dtype="float32")
    four = _run(tmp_path / "four", make_mesh(4), torch_dtype="float32")
    assert four["param_shardings"] == ["NamedSharding"]
    assert four["tokens"] == one["tokens"]
