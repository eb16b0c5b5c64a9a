"""One architecture, one file: ``lib/families/<model_type>.py``.

Everything in the yardstick that knows an architecture sits in the file
named after the configuration's ``model_type`` (``-`` written ``_``), and
the rest of the harness asks :func:`of` for it. A family file has four
parts and imports nothing of the program under test:

``tensors(config)``
    ``{name: Filled(shape, fill, fan_in)}`` in the order the checkpoint
    holds them. ``fill`` is ``"normal"`` (N(0, 1/``fan_in``), the fan-in
    stated because it need not be ``shape[1]``), ``"ones"`` or ``"zeros"``.
    The order is part of the weights: tensor ``i`` draws from the streams
    seeded ``[seed, i, chunk]`` (:mod:`checkpoint`).
``logits(ckpt, sequences, wanted, mode)``
    the plain reference (``mode`` ``float32``) and its precisions
    (``int8`` the control, ``bfloat16`` the sound program of the CPU
    tests), built from the shared pieces of :mod:`reference`.
``prefill_flops(config, tokens)`` and ``decode_bytes(config, steps, lengths)``
    what the mathematics needs, for the roofline shares. ``steps`` are the
    attributes of the window's ``serve.decode-step`` spans, one dict a
    step; ``lengths`` the cached positions behind each decoded token, one
    number a token and not their sum, so that a family whose layers read
    ``min(length, window)``, or whose bytes follow a count the program
    reports on the span, can say so.
``rehearsal(config)``
    the toy sizes ``--rehearse`` swaps in on the CPU.

A reader that only this family's cells need can live in the same file: a
metric's data file names it ``"reader": "families.<model_type>:<function>"``
(:func:`readers.resolve`).
"""

from __future__ import annotations

import importlib
from typing import NamedTuple


class Filled(NamedTuple):
    """One tensor of a family's table: its shape and what fills it."""
    shape: tuple[int, ...]
    fill: str = "normal"        # "normal", "ones" or "zeros"
    fan_in: int = 0             # of "normal": N(0, 1/fan_in)


def of(config: dict):
    """The family module of ``config`` (a ``config.json`` as a dict)."""
    model_type = config.get("model_type")
    if not model_type:
        raise ValueError("the configuration states no model_type, so no "
                         "family of lib/families/ can be chosen for it")
    name = str(model_type).replace("-", "_")
    try:
        return importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise ValueError(
            f"no family for model_type {model_type!r}: add "
            f"benchmark/lib/families/{name}.py with tensors(), logits(), "
            "prefill_flops(), decode_bytes() and rehearsal() "
            "(benchmark/README.md, An architecture)") from None
