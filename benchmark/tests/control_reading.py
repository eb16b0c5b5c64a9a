"""Read the control of ``correct`` for a cell without booting the engine.

    python3 benchmark/tests/control_reading.py --workload yi6b-chat \
        --seeds 2147483801 2147483802 2147483803

For each seed it makes the cell's weights and the prompts its callers send
first, and at the last 32 positions of each prompt reads how far below the
float32 reference's best logit lies the token that the reference computed
in int8 puts first: the numbers ``run.py --control int8`` reads on served
tokens, at the cell's own widths and lengths, on one device (the reference
runs layer by layer, so a model sharded over four chips fits one). For a
four-chip cell this costs a quarter of a run's chip time and no set-up.
Needs an accelerator only to be quick; prints one line a seed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from lib import checkpoint, families, loadgen, reference  # noqa: E402

TAIL = 32


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="the family's toy model, to try the script on the "
                         "CPU")
    args = ap.parse_args()
    import jax.numpy as jnp

    bench = json.loads((HERE.parent.parent / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    doc = json.loads((HERE.parent.parent / config["file"]).read_text())
    model = {k: v for k, v in doc.items() if k != "benchmark"}
    if args.rehearse:
        model.update(families.of(model).rehearsal(model))
    traffic = json.loads((HERE.parent / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    for seed in args.seeds:
        ckpt = checkpoint.Checkpoint(model, seed,
                                     n_shards=doc["benchmark"]["shards"])
        prompts = []
        for k, cycle in enumerate(loadgen.callers_of(traffic)):
            stream = loadgen.request_stream(cycle, seed, k,
                                            model["vocab_size"])
            prompts += [next(stream)[0] for _ in range(2)]
        wanted = [range(len(p) - TAIL, len(p)) for p in prompts]
        ref = reference.logits(ckpt, prompts, wanted)
        low = reference.logits(ckpt, prompts, wanted, mode="int8")
        gaps = np.concatenate([
            reference.gaps_below_best(
                r, np.asarray(jnp.argmax(lo, axis=1))[:TAIL])
            for r, lo in zip(ref, low)])
        print(f"control int8 {args.workload} seed {seed}: served_gap_max = "
              f"{gaps.max():.6g}, served_gap_mean = {gaps.mean():.6g}, "
              f"{int((gaps > 0).sum())} of {gaps.size} not the reference's "
              "first choice", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
