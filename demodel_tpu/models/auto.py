"""Auto dispatch: pulled checkpoint → (forward_fn, params, config).

Closes the delivery loop: ``pull_to_hbm`` lands sharded tensors, this maps
them onto a model family by the pulled ``config.json``'s ``model_type`` and
returns a ready forward function — a pulled model is runnable in one call.
Unknown architectures are rejected here, and config features a family does
not implement (rope scaling, a sliding window where the family has none) by
that family's ``from_hf``, loudly rather than silently mis-executed.
"""

from __future__ import annotations

import functools
import json

from demodel_tpu.models import axk1 as axk1_mod
from demodel_tpu.models import bert as bert_mod
from demodel_tpu.models import exaone_moe as exaone_moe_mod
from demodel_tpu.models import gpt2 as gpt2_mod
from demodel_tpu.models import llama as llama_mod
from demodel_tpu.models import longcat_flash as longcat_flash_mod
from demodel_tpu.models import phi4flash as phi4flash_mod
from demodel_tpu.models import qwen3_next as qwen3_next_mod
from demodel_tpu.models.hf_loader import (
    load_axk1_params,
    load_bert_params,
    load_exaone_moe_params,
    load_gpt2_params,
    load_llama_params,
    load_longcat_flash_params,
    load_phi4flash_params,
    load_qwen3_next_params,
)
from demodel_tpu.utils.logging import get_logger

log = get_logger("models.auto")

def model_from_pull(store, report, mesh=None, placement=None):
    """(forward_fn, params, cfg) from a pulled snapshot (``forward_fn`` is
    None for a family that only the serving engine runs, through its
    module's ``step_prefill`` / ``step_decode``).

    ``placement`` (a delivered :class:`~demodel_tpu.sink.hbm.Placement`)
    supplies the weight arrays when given; otherwise weights are delivered
    from the store now under the default plan. Either way the loader
    consumes them: ``placement.arrays`` is left holding only the tensors
    the model did not take.
    """
    files = report["files"] if isinstance(report, dict) else [
        vars(f) for f in report.files]
    cfg_file = next((f for f in files if f["name"] == "config.json"), None)
    if cfg_file is None:
        raise ValueError("pulled snapshot has no config.json")
    config = json.loads(bytes(store.get(cfg_file["key"])).decode())
    model_type = config.get("model_type")

    if placement is None:
        from demodel_tpu.sink.hbm import deliver_report_to_hbm

        placement = deliver_report_to_hbm(store, report, mesh=mesh)
    weights = placement.arrays
    n_tensors = len(weights)  # the loaders consume the mapping

    if model_type == "llama":
        cfg = llama_mod.LlamaConfig.from_hf(config)
        params = load_llama_params(weights, cfg, mesh=mesh)
        fn = functools.partial(llama_mod.forward, cfg=cfg, mesh=mesh)
    elif model_type == "gpt2":
        cfg = gpt2_mod.GPT2Config.from_hf(config)
        params = load_gpt2_params(weights, cfg)
        fn = functools.partial(gpt2_mod.forward, cfg=cfg, mesh=mesh)
    elif model_type == "bert":
        cfg = bert_mod.BertConfig.from_hf(config)
        params = load_bert_params(weights, cfg)
        fn = functools.partial(bert_mod.encode, cfg=cfg, mesh=mesh)
    elif model_type == "exaone_moe":
        cfg = exaone_moe_mod.ExaoneMoeConfig.from_hf(config)
        params = load_exaone_moe_params(weights, cfg, mesh=mesh)
        fn = None   # served through its step functions only
    elif model_type == "qwen3_next":
        cfg = qwen3_next_mod.Qwen3NextConfig.from_hf(config)
        params = load_qwen3_next_params(weights, cfg, mesh=mesh)
        fn = None   # served through its step functions only
    elif model_type == "phi4flash":
        cfg = phi4flash_mod.Phi4FlashConfig.from_hf(config)
        params = load_phi4flash_params(weights, cfg, mesh=mesh)
        fn = None   # served through its step functions only
    elif model_type == "axk1":
        cfg = axk1_mod.AxK1Config.from_hf(config)
        params = load_axk1_params(weights, cfg, mesh=mesh)
        fn = None   # served through its step functions only
    elif model_type == "longcat_flash":
        cfg = longcat_flash_mod.LongcatFlashConfig.from_hf(config)
        params = load_longcat_flash_params(weights, cfg, mesh=mesh)
        fn = None   # served through its step functions only
    else:
        raise ValueError(f"unsupported model_type {model_type!r} "
                         "(supported: llama, gpt2, bert, exaone_moe, "
                         "qwen3_next, phi4flash, axk1, longcat_flash)")
    log.info("auto: built %s from pulled snapshot (%d tensors)",
             model_type, n_tensors)
    return fn, params, cfg
