"""A family that is no model, found as ``model_type: toyfam`` once
``test_seam.py`` has put this directory on ``lib.families``'s path: an
embedding, a 1-D tensor that is no norm (and whose fan-in is not
``shape[1]``), a bias of zeros, a norm, a head. It is here to show what a
family's file has to hold, and that nothing else has to change."""

import jax
import numpy as np

from .. import reference
from . import Filled


def rehearsal(config: dict) -> dict:
    return {"hidden_size": 64, "vocab_size": 256}


def tensors(config: dict) -> dict[str, Filled]:
    D, V = config["hidden_size"], config["vocab_size"]
    return {"embed.weight": Filled((V, D), "normal", D),
            "mix.scale": Filled((D,), "normal", 4),
            "mix.bias": Filled((D,), "zeros"),
            "norm.weight": Filled((D,), "ones"),
            "head.weight": Filled((V, D), "normal", D)}


def logits(ckpt, sequences, wanted, mode="float32"):
    scale = ckpt.tensor("mix.scale").astype(np.float32)
    bias = ckpt.tensor("mix.bias").astype(np.float32)
    xs = [x * scale + bias
          for x in reference.embed(ckpt, "embed.weight", sequences)]
    return reference.head_rows(
        xs, wanted, jax.device_put(ckpt.tensor("norm.weight")),
        jax.device_put(ckpt.tensor("head.weight")),
        eps=float(ckpt.config["rms_norm_eps"]), mode=mode)


def prefill_flops(config: dict, tokens: int) -> float:
    return 2.0 * config["vocab_size"] * config["hidden_size"]


def decode_bytes(config: dict, steps: list[dict], lengths: list[int]) -> float:
    """The head once a step, and a window of 4 positions behind a token."""
    return 2.0 * config["vocab_size"] * config["hidden_size"] * len(steps) \
        + 2.0 * config["hidden_size"] * sum(min(n, 4) for n in lengths)


def requests_seen(obs, scale: float = 1.0):
    """A reader of the family's own: ``families.toyfam:requests_seen``."""
    return len(obs.records) * scale if obs.records else None
