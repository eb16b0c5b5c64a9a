"""Auto dispatch: pulled checkpoint → (forward_fn, params, config).

Closes the delivery loop: ``pull_to_hbm`` lands sharded tensors, this maps
them onto a model family by the pulled ``config.json``'s ``model_type`` and
returns a ready forward function — a pulled model is runnable in one call.
Unknown architectures and config features this stack does not implement
(e.g. rope scaling) are rejected loudly rather than silently mis-executed.
"""

from __future__ import annotations

import functools
import json

from demodel_tpu.models import bert as bert_mod
from demodel_tpu.models import exaone_moe as exaone_moe_mod
from demodel_tpu.models import gpt2 as gpt2_mod
from demodel_tpu.models import llama as llama_mod
from demodel_tpu.models import qwen3_next as qwen3_next_mod
from demodel_tpu.models.hf_loader import (
    load_bert_params,
    load_exaone_moe_params,
    load_gpt2_params,
    load_llama_params,
    load_qwen3_next_params,
)
from demodel_tpu.utils.logging import get_logger

log = get_logger("models.auto")

#: config fields whose presence (non-null/non-default) changes numerics in
#: ways this stack does not implement — refuse rather than drift
_UNSUPPORTED = ("rope_scaling", "sliding_window", "attention_bias")


def _check_supported(config: dict) -> None:
    for fld in _UNSUPPORTED:
        v = config.get(fld)
        if v not in (None, False):
            raise ValueError(
                f"config field {fld}={v!r} is not supported by this stack")


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
        _check_supported(config)
        cfg = llama_mod.LlamaConfig.from_hf(config)
        params = load_llama_params(weights, cfg, mesh=mesh)
        fn = functools.partial(llama_mod.forward, cfg=cfg, mesh=mesh)
    elif model_type == "gpt2":
        _check_supported(config)
        cfg = gpt2_mod.GPT2Config.from_hf(config)
        params = load_gpt2_params(weights, cfg)
        fn = functools.partial(gpt2_mod.forward, cfg=cfg, mesh=mesh)
    elif model_type == "bert":
        _check_supported(config)
        cfg = bert_mod.BertConfig.from_hf(config)
        params = load_bert_params(weights, cfg)
        fn = functools.partial(bert_mod.encode, cfg=cfg, mesh=mesh)
    elif model_type == "exaone_moe":
        # its window layers are the model's own (``sliding_windows``), so
        # ``sliding_window`` is no unsupported feature here
        cfg = exaone_moe_mod.ExaoneMoeConfig.from_hf(config)
        params = load_exaone_moe_params(weights, cfg, mesh=mesh)
        fn = None   # served through its step functions only
    elif model_type == "qwen3_next":
        # the config's own checks refuse what is not implemented
        # (rope_scaling, use_sliding_window, ...)
        cfg = qwen3_next_mod.Qwen3NextConfig.from_hf(config)
        params = load_qwen3_next_params(weights, cfg, mesh=mesh)
        fn = None   # served through its step functions only
    else:
        raise ValueError(f"unsupported model_type {model_type!r} "
                         "(supported: llama, gpt2, bert, exaone_moe, "
                         "qwen3_next)")
    log.info("auto: built %s from pulled snapshot (%d tensors)",
             model_type, n_tensors)
    return fn, params, cfg
