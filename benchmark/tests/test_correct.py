"""How ``correct`` is decided, held to account at a size a test run holds.

Run by hand (``python -m pytest benchmark/tests -q``): the repository's own
suite collects ``tests/`` only.

``test_control_*``: the control of the comparison, the reference computed
in int8 (the nearest precision below the configurations' bfloat16) and put
in the program's place, has to stand out from a sound bfloat16 program in
the numbers the benchmark compares. At the published sizes that was read
on the chip (PERF.md, section 2); here the same functions at a toy size on
the CPU, bfloat16 arithmetic (``mode="bfloat16"``) standing for the sound
program.

``test_broken_*``: a whole run of the harness on the CPU (the look for a
chip skipped by ``--rehearse``), once sound and once with the timed path
broken underneath, a token altered where the engine emits it, has to end
not correct for that reason.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent.parent))

TOY = {"model_type": "llama", "hidden_size": 256,
       "intermediate_size": 512, "num_hidden_layers": 4,
       "num_attention_heads": 8, "num_key_value_heads": 2,
       "vocab_size": 8192, "rms_norm_eps": 1e-6, "rope_theta": 5e6,
       "torch_dtype": "bfloat16"}


def _gaps(seed: int, mode: str) -> np.ndarray:
    """Gaps, under the float32 reference, of the tokens that ``mode`` puts
    first at 256 positions of four seeded sequences."""
    import jax.numpy as jnp

    from lib import checkpoint, reference

    ckpt = checkpoint.Checkpoint(TOY, seed, n_shards=2)
    rng = np.random.default_rng([seed, 1])
    seqs = [[int(t) for t in rng.integers(0, TOY["vocab_size"], 192)]
            for _ in range(4)]
    wanted = [range(128, 192)] * 4
    ref = reference.logits(ckpt, seqs, wanted)
    low = reference.logits(ckpt, seqs, wanted, mode=mode)
    return np.concatenate([
        reference.gaps_below_best(r, np.asarray(jnp.argmax(lo, axis=1))[:64])
        for r, lo in zip(ref, low)])


@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
def test_control_int8_stands_out_from_bfloat16(seed):
    sound, control = _gaps(seed, "bfloat16"), _gaps(seed, "int8")
    assert control.mean() > 3 * sound.mean(), (sound.mean(), control.mean())
    assert control.max() > sound.max()
    assert (control > 0).sum() > (sound > 0).sum()


def _rehearse(extra_patch=None):
    import run as harness

    args = harness.parse(["--workload", "yi6b-chat", "--seed", "2147483999",
                          "--seconds", "2", "--trace", "0", "--rehearse"])
    if extra_patch is not None:
        extra_patch()
    return harness.run(args)


def test_broken_timed_path_is_not_correct(monkeypatch):
    code, result, reasons = _rehearse()
    assert code == 0 and result["failed"] == 0
    assert reasons == ["a rehearsal is never a result"], reasons

    def break_emit():
        from demodel_tpu.serve import scheduler

        emit = scheduler.Request._emit
        count = {"n": 0}

        def altered(self, tok):          # every 7th token is another one
            count["n"] += 1
            emit(self, tok + 1 if count["n"] % 7 == 0 and tok > 0 else tok)

        monkeypatch.setattr(scheduler.Request, "_emit", altered)

    code, result, reasons = _rehearse(break_emit)
    assert code == 0 and result["correct"] is False
    assert any(r.startswith("served_gap_") for r in reasons), reasons
