"""Checkpoint parity with HF transformers (torch CPU reference).

Tiny random reference models are instantiated with ``transformers``, their
logits compared against our functional forwards fed by the SAME weights —
through each family's ``load_params`` directly and through the full pull→sink→auto path.
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")

# MITM PKI needs `cryptography` (pulled by `pip install -e .`); a
# dep-light checkout must skip-collect, not error (ISSUE 1 satellite)
pytest.importorskip("cryptography")

from demodel_tpu import delivery  # noqa: E402
from demodel_tpu.config import ProxyConfig  # noqa: E402
from demodel_tpu.formats import safetensors as st  # noqa: E402
from demodel_tpu.models import bert as bert_mod  # noqa: E402
from demodel_tpu.models import gpt2 as gpt2_mod  # noqa: E402
from demodel_tpu.models import llama as llama_mod  # noqa: E402
from demodel_tpu.models.auto import model_from_pull  # noqa: E402

from .fake_registries import make_hf_handler  # noqa: E402
from .servers import FakeUpstream  # noqa: E402


def _state_np(model) -> dict:
    return {k: v.detach().numpy() for k, v in model.state_dict().items()}


def test_llama_parity_gqa():
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=False)
    torch.manual_seed(0)
    ref = transformers.LlamaForCausalLM(hf_cfg).eval()
    toks = np.arange(2 * 12).reshape(2, 12) % 128
    with torch.no_grad():
        want = ref(torch.tensor(toks)).logits.numpy()

    cfg = llama_mod.LlamaConfig.from_hf(hf_cfg.to_dict())
    params = llama_mod.load_params(_state_np(ref), cfg)
    got = np.asarray(llama_mod.forward(params, jnp.asarray(toks, jnp.int32),
                                       cfg))
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_gpt2_logits_tied_head():
    hf_cfg = transformers.GPT2Config(
        vocab_size=96, n_positions=32, n_embd=48, n_layer=2, n_head=4)
    torch.manual_seed(1)
    ref = transformers.GPT2LMHeadModel(hf_cfg).eval()
    toks = np.arange(2 * 10).reshape(2, 10) % 96
    with torch.no_grad():
        want = ref(torch.tensor(toks)).logits.numpy()
    cfg = gpt2_mod.GPT2Config.from_hf(hf_cfg.to_dict())
    params = gpt2_mod.load_params(_state_np(ref), cfg)
    got = np.asarray(gpt2_mod.forward(params, jnp.asarray(toks, jnp.int32),
                                      cfg))
    np.testing.assert_allclose(got, want, atol=2e-4)


def _bert_rig():
    hf_cfg = transformers.BertConfig(
        vocab_size=120, hidden_size=48, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32)
    torch.manual_seed(2)
    ref = transformers.BertModel(hf_cfg).eval()
    cfg = bert_mod.BertConfig.from_hf(hf_cfg.to_dict())
    params = bert_mod.load_params(_state_np(ref), cfg)
    return ref, cfg, params


def test_bert_parity_with_padding_mask():
    ref, cfg, params = _bert_rig()
    toks = np.arange(2 * 12).reshape(2, 12) % 120
    mask = np.ones((2, 12), np.int64)
    mask[1, 7:] = 0
    with torch.no_grad():
        want = ref(torch.tensor(toks),
                   attention_mask=torch.tensor(mask)).last_hidden_state.numpy()
    got = np.asarray(bert_mod.encode(params, jnp.asarray(toks, jnp.int32),
                                     cfg, attention_mask=jnp.asarray(mask)))
    # padded positions' outputs are allowed to differ — compare valid ones
    np.testing.assert_allclose(got[0], want[0], atol=2e-4)
    np.testing.assert_allclose(got[1, :7], want[1, :7], atol=2e-4)


def test_bert_all_padding_row_is_finite():
    _ref, cfg, params = _bert_rig()
    toks = jnp.zeros((2, 8), jnp.int32)
    mask = jnp.zeros((2, 8), jnp.int32).at[0].set(1)  # row 1 fully padded
    out = np.asarray(bert_mod.encode(params, toks, cfg,
                                     attention_mask=mask))
    assert np.isfinite(out).all()  # -inf bias would NaN the softmax


def _files_from_hf(model, config: dict) -> dict:
    """filename → bytes, as save_pretrained would lay a repo out."""
    state = _state_np(model)
    return {
        "config.json": json.dumps(config).encode(),
        "model.safetensors": st.serialize(state),
    }


def test_gpt2_parity_via_sink(tmp_path, mesh8):
    """Full path: fake hub → pull_to_hbm (sharded) → ``load_params`` → logits
    parity with torch."""
    hf_cfg = transformers.GPT2Config(
        vocab_size=96, n_positions=32, n_embd=48, n_layer=2, n_head=4)
    torch.manual_seed(3)
    ref = transformers.GPT2LMHeadModel(hf_cfg).eval()
    cfgd = hf_cfg.to_dict()
    cfgd["model_type"] = "gpt2"
    files = _files_from_hf(ref, cfgd)
    handler = make_hf_handler({"org/g2": files})
    with FakeUpstream(handler=handler) as up:
        cfg = ProxyConfig(cache_dir=tmp_path / "cache",
                          data_dir=tmp_path / "data")
        report, placed = delivery.pull_to_hbm(
            "org/g2", cfg, endpoint=f"http://{up.authority}", mesh=mesh8)
        gcfg = gpt2_mod.GPT2Config.from_hf(cfgd)
        params = gpt2_mod.load_params(placed.arrays, gcfg)
        toks = np.arange(2 * 10).reshape(2, 10) % 96
        with torch.no_grad():
            want = ref(torch.tensor(toks)).logits.numpy()
        got = np.asarray(gpt2_mod.forward(
            params, jnp.asarray(toks, jnp.int32), gcfg))
        np.testing.assert_allclose(got, want, atol=2e-4)


def test_auto_model_from_pull_end_to_end(tmp_path, mesh8):
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=False)
    torch.manual_seed(4)
    ref = transformers.LlamaForCausalLM(hf_cfg).eval()
    cfgd = hf_cfg.to_dict()
    cfgd["model_type"] = "llama"
    cfgd.pop("rope_scaling", None)
    files = _files_from_hf(ref, cfgd)
    handler = make_hf_handler({"org/auto": files})
    with FakeUpstream(handler=handler) as up:
        cfg = ProxyConfig(cache_dir=tmp_path / "cache",
                          data_dir=tmp_path / "data")
        store = delivery.open_store(cfg)
        try:
            report, placed = delivery.pull_to_hbm(
                "org/auto", cfg, endpoint=f"http://{up.authority}",
                store=None, mesh=mesh8)
            store2 = delivery.open_store(cfg)
            try:
                fn, params, mcfg = model_from_pull(store2, report, mesh=mesh8,
                                                   placement=placed)
                toks = np.arange(2 * 8).reshape(2, 8) % 128
                with torch.no_grad():
                    want = ref(torch.tensor(toks)).logits.numpy()
                got = np.asarray(fn(params, jnp.asarray(toks, jnp.int32)))
                np.testing.assert_allclose(got, want, atol=2e-4)
            finally:
                store2.close()
        finally:
            store.close()


def test_auto_rejects_unsupported_config_fields(tmp_path, mesh8):
    files = {
        "config.json": json.dumps({
            "model_type": "llama", "vocab_size": 64, "hidden_size": 32,
            "num_hidden_layers": 1, "num_attention_heads": 4,
            "intermediate_size": 48,
            "rope_scaling": {"type": "linear", "factor": 2.0},
        }).encode(),
        "model.safetensors": st.serialize(
            {"x": np.zeros((2, 2), np.float32)}),
    }
    handler = make_hf_handler({"org/bad": files})
    with FakeUpstream(handler=handler) as up:
        cfg = ProxyConfig(cache_dir=tmp_path / "cache",
                          data_dir=tmp_path / "data")
        store = delivery.open_store(cfg)
        try:
            report = delivery.pull("org/bad", cfg,
                                   endpoint=f"http://{up.authority}",
                                   store=store)
            with pytest.raises(ValueError, match="rope_scaling"):
                model_from_pull(store, report, mesh=mesh8)
            # unknown families rejected too
            files2 = dict(files)
            with pytest.raises(ValueError, match="model_type"):
                bad = dict(report)
                store.remove(report["files"][0]["key"])
                store.put(report["files"][0]["key"],
                          json.dumps({"model_type": "mamba"}).encode(), {})
                model_from_pull(store, bad, mesh=mesh8)
        finally:
            store.close()


FAMILIES = ["llama", "gpt2", "bert", "exaone_moe", "qwen3_next", "phi4flash",
            "axk1", "longcat_flash", "zaya"]


@pytest.mark.parametrize("model_type", FAMILIES)
def test_a_family_is_the_module_of_its_name(model_type):
    """``auto.family`` finds a ``model_type``'s module by its name (``-``
    written ``_``), and the module states the three things
    ``model_from_pull`` asks of it: its configuration from a
    ``config.json``, its loader, and its forward function or None (a
    family only the engine runs states its step functions instead)."""
    import importlib
    import inspect

    from demodel_tpu.models import auto

    module = auto.family(model_type)
    assert module is importlib.import_module(
        f"demodel_tpu.models.{model_type}")
    assert auto.family(model_type.replace("_", "-")) is module
    assert model_type in auto.families()
    cfg = module.from_hf.__self__       # the configuration's own from_hf
    assert inspect.isclass(cfg) and cfg.__module__ == module.__name__
    assert list(inspect.signature(module.load_params).parameters) \
        == ["weights", "cfg", "mesh"]
    if module.forward is None:
        assert callable(module.step_prefill) and callable(module.step_decode)
    else:
        assert {"cfg", "mesh"} <= set(
            inspect.signature(module.forward).parameters)


@pytest.mark.parametrize("name", [
    "common", "latent", "experts", "moe", "auto", "hf_loader", "os", "a.b",
    "../llama", "", None, 7])
def test_what_states_no_family_is_not_offered(name, monkeypatch):
    """A module of ``models/`` that states none of the three names is no
    family, and neither is a name that is no module of ``models/``: the
    same ``ValueError``, which lists the families found by looking, and
    nothing is imported for it."""
    import importlib

    from demodel_tpu.models import auto

    assert auto.families() == sorted(FAMILIES)
    if isinstance(name, str) and name.isidentifier():
        assert name not in auto.families()
    imported = []
    real = importlib.import_module
    monkeypatch.setattr(auto.importlib, "import_module", lambda n, *a: (
        imported.append(n), real(n, *a))[1])
    with pytest.raises(ValueError) as refused:
        auto.family(name)
    assert str(refused.value).startswith(f"unsupported model_type {name!r} ")
    assert ", ".join(sorted(FAMILIES)) in str(refused.value)
    assert all(n.startswith("demodel_tpu.models") for n in imported)
