"""Auto dispatch: pulled checkpoint → (forward_fn, params, config).

Closes the delivery loop: ``pull_to_hbm`` lands sharded tensors, this maps
them onto a model family by the pulled ``config.json``'s ``model_type`` and
returns a ready forward function — a pulled model is runnable in one call.

**A family is one module, found by its name**: ``model_type`` (``-``
written ``_``) names a module of this package which states three things
under three names: ``from_hf(config)`` (its configuration from a
``config.json``), ``load_params(weights, cfg, mesh=None)`` (its params tree
from a checkpoint's tensors) and ``forward`` (its forward function, taking
``cfg`` and ``mesh`` by keyword; None for a family that only the serving
engine runs, through its module's ``step_prefill`` / ``step_decode``). A
new family is a new module; nothing here names one. ``model_type`` is input
from outside: only a name this package's own directory lists is ever
imported. Unknown architectures are rejected here, and config features a
family does not implement (rope scaling, a sliding window where the family
has none) by that family's ``from_hf``, loudly rather than silently
mis-executed.
"""

from __future__ import annotations

import functools
import importlib
import json
import pkgutil
import sys

from demodel_tpu.utils.logging import get_logger

log = get_logger("models.auto")

#: what a module of this package states to be a family
STATED = ("from_hf", "load_params", "forward")


def _listed() -> list[str]:
    """The names of this package's modules, from its directory: nothing is
    imported."""
    return [m.name for m in pkgutil.iter_modules(
        sys.modules[__package__].__path__)]


def _stated(name: str):
    """The module ``name`` of this package if it states a family, else
    None."""
    module = importlib.import_module(f"{__package__}.{name}")
    return module if all(hasattr(module, n) for n in STATED) else None


def families() -> list[str]:
    """The families this package holds, found by looking."""
    return sorted(name for name in _listed() if _stated(name))


def family(model_type):
    """The module that states the family ``model_type``; a ``ValueError``
    for anything else, a name of no module of this package (which is
    never imported) and a module that states no family alike."""
    name = model_type.replace("-", "_") if isinstance(model_type, str) else ""
    module = _stated(name) if name in _listed() else None
    if module is None:
        raise ValueError(f"unsupported model_type {model_type!r} "
                         f"(supported: {', '.join(families())})")
    return module


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
    module = family(config.get("model_type"))

    if placement is None:
        from demodel_tpu.sink.hbm import deliver_report_to_hbm

        placement = deliver_report_to_hbm(store, report, mesh=mesh)
    weights = placement.arrays
    n_tensors = len(weights)  # the loader consumes the mapping

    cfg = module.from_hf(config)
    params = module.load_params(weights, cfg, mesh=mesh)
    fn = module.forward and functools.partial(module.forward, cfg=cfg,
                                              mesh=mesh)
    log.info("auto: built %s from pulled snapshot (%d tensors)",
             config["model_type"], n_tensors)
    return fn, params, cfg
